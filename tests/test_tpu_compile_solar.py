"""The hybrid block without positions (``models/solar.py``) at the
longreason cell's sizes, compiled for a described v5e
(``tests/_tpu_compile.py`` says how and why): its decode chunk and its
one-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    _dead_branch_hands_on_and_makes_zeros, _flash_fwd_bodies,
    _flash_fwd_calls,
    _kda_chunk_calls, _kda_inputs_calls, KERNEL,
    lowered_counting_kda_bodies, _lower_prefill, _mem, MIB, _on, once,
    _loops_add_nothing_unscoped, _segment_branches, topo)
from ray_tpu.models import decode_engine as de


def _solar_cell(topo, monkeypatch):
    """``solar-open2-250b-ep8-1chip.longreason-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import solar
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_step", functools.partial(
        solar._kda_step, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_chunk", functools.partial(
        solar._kda_chunk, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_qkvg", functools.partial(
        solar._kda_qkvg, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("solar-open2-250b-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: solar.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def test_solar_decode_chunk_keeps_both_kinds_of_state_where_they_lie(
        topo, monkeypatch):
    """The cell's decode program (4 layers, 40 of 320 experts held, 32
    slots of 34,832 rows): a step calls ``kda_step`` once a KDA layer at
    64 heads (``S`` operand 3, returned in its buffer, nothing else
    moves a state: the call carries no ``cost_estimate``, as Ling's),
    ``decode_attn`` once on the GQA layer's stack in place (23 MiB of
    temporaries beside a k stack of 2.28 GB), and
    ``moe_gmm`` three times a layer; no matrix exists in float32;
    arguments and temporaries stay under 13 GiB of the chip's 16."""
    from ray_tpu.models import solar

    fam, m, cfg, eng, params, state, vec = _solar_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    # (a step's assignments give the expert layer no capacity,
    # ``moe.compact_rows``: its lines are the parent's, no branch)
    assert " conditional(" not in text
    calls = [line for line in text.splitlines() if KERNEL in line]
    kda_calls = [c for c in calls if "kda_step" in c.split(" = ")[0]]
    assert len(kda_calls) == cfg.kda_layers == 3
    assert sum("decode_attn" in c.split(" = ")[0] for c in calls) \
        == cfg.full_layers == 1
    assert len(calls) == 3 + 1 + 3 * cfg.n_layers == 16
    # (a step's one row takes the XLA body of ``ops.kda_inputs`` even
    # where the kernel is asked for: the chunk's text is the parent's)
    assert not re.search(r"%\S*kda_inputs\S* = ", text)
    s_dims = f"f32[{slots},64,128,128]"
    for line in kda_calls:
        assert line.split(" = ")[1].startswith(f"({s_dims}"), line
        assert "output_to_operand_aliasing={{0}: (3, {})}" in line, line
    moves = re.compile(r"\s*%(copy|copy-start|slice-start|async-start|"
                       r"dynamic-slice-start)[.\d]* = ")
    moved = [line[:160] for line in text.splitlines() if moves.match(line)
             and re.search(rf"f32\[({slots}|{slots // 4}),64,128,128\]",
                           line)]
    assert not moved, moved[:3]
    stack = f"bf16[1,{slots},{max_len},1024]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(solar.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nsolar decode chunk: {_mem(compiled)}")
    # (the one-layer stack is bitcast to [slots, max_len, 1024] and
    # scattered into in place: no temporary is the size of a layer's k)
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def _widest_prefill(cfg, params, state, vec):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048, lowered from nothing (no
    earlier trace of this shape) and compiled once for the two tests
    that read it -> (the compiled program, its text, the modules whose
    kernel's body the lowering ran, in order)."""
    def make():
        lowered, traced = lowered_counting_kda_bodies(
            lambda: _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                                   (params, state, vec)))
        compiled = lowered.compile()
        return compiled, compiled.as_text(), [of for of, _ in traced]

    return once("solar prefill 32768", make)


def test_solar_32768_row_prefill_runs_its_tokenwise_work_in_segments(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048: ``flash_fwd`` once on 64 / 8
    heads of 128 and ``moe_gmm`` three times a layer; NO float32 array
    of ``[32768, 64, 128]`` (q, k, v or g of a KDA layer whole would be
    1.07 GB each), no gather of ``32768 x 8`` assignment rows, no
    ``32768 x 32768`` scores, no ``[P, vocabulary]`` logits exist; the
    donated state is updated in place; beside 32 slots the call fits
    the chip's 16 GiB (temporaries 3,213 MiB: the stream in and out of
    a layer and the GQA layer's q and o in both layouts, 537 MB each);
    the chunkwise delta rule is ONE ``kda_chunk`` call a KDA layer
    inside its segment scan (``_kda_chunk_calls``), fed by ONE
    ``kda_inputs`` call (``_kda_inputs_calls``)."""
    from ray_tpu.models import solar

    fam, m, cfg, eng, params, state, vec = _solar_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert solar.SLOTS.prefill_segments(cfg, 32768) == 16
    compiled, text, _ = _widest_prefill(cfg, params, state, vec)
    assert text.count("flash_fwd") >= cfg.full_layers and "moe_gmm" in text
    # the SERVING call (PR 69): the forward-only body, ONE result (no
    # lse; the parent's program held the differentiable call's kernel,
    # ``(2, "a636016a79e4c55c")``, which ``forward`` alone keeps), its
    # text PR 67's at this shape: a cell a kv head's 8 query heads over
    # 256 rows, the scores 1,024 keys by the cell's 2,048 rows
    assert _flash_fwd_calls(text) == [(1, "b6168d4c08bf4ecb")]
    body = _flash_fwd_bodies(text)[0][1]
    assert "memref<1x8x256x128xbf16" in body
    assert "vector<1024x2048xf32>" in body
    assert "vector<2048x1024xf32>" not in body
    # the chunkwise delta rule: one kernel call a KDA layer, in the scan
    calls = _kda_chunk_calls(text)
    assert len(calls) == cfg.kda_layers == 3
    assert all("/while/body/" in c and "f32[1,2048,8192]" in c
               for c in calls), calls[0][:300]
    # and what makes its q, k, v and g ONE ``kda_inputs`` call before it,
    # on the product's rows as they lie: no row array of a segment with
    # the three rows before it laid in front exists
    inputs = _kda_inputs_calls(text, calls)
    assert len(inputs) == cfg.kda_layers == 3
    assert all("/while/body/" in c and "bf16[1,2048,24576]" in c
               and c.split(" = ")[1].startswith("(f32[1,2048,8192]")
               for c in inputs), inputs[0][:300]
    assert "2051,24576" not in text
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    whole = [(dt, dims) for dt, dims in arrays if dt == "f32"
             and np.prod(dims) >= 32768 * 64 * 128]
    assert not whole, whole[:4]
    assert not [d for _, d in arrays if 32768 * 8 in d], "a whole gather"
    assert "32768,32768" not in text and "32768,24576" not in text
    assert ("bf16", (1, 32768, 64, 128)) in arrays \
        or ("bf16", (1, 64, 32768, 128)) in arrays  # (flash's q, whole)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        solar.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nsolar prefill 1 x 32768: {_mem(compiled)} (temporaries "
          "with the XLA body, PR 42: 3,213 MiB)")
    # (no larger than before the ``kda_inputs`` kernel: 3,215.4 MiB of
    # temporaries beside 11,061.9 MiB of arguments on PR 56's tree,
    # 3,213.4 with it)
    assert mem.temp_size_in_bytes <= 3216 * MIB, _mem(compiled)
    assert mem.argument_size_in_bytes <= 11062 * MIB, _mem(compiled)


def test_solar_prefill_skips_the_segments_behind_the_prompts_last_live_one(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket (32,768 rows,
    sixteen segments; at eight the program is the same lines): each
    of a layer's loops (the GQA layer's projections and its rest, a KDA
    layer's one) holds one ``conditional`` on a segment's first row
    against the prompt's rows, which the program reads from
    ``true_lens`` (``moe.in_segments`` with ``live``). The dead branch
    hands the carry on and makes zeros, nothing else: no kernel, no
    fusion, no copy of the ``S`` it carries, and no loop copies it
    either; ``kda_chunk`` and ``kda_inputs`` are called once a KDA layer
    in the live branch and each body is traced once for the three; the branch lands nothing
    in ``unscoped`` (three instructions on the parent, ``PERF.md`` §6 PR
    51)."""
    from ray_tpu.models import solar
    from ray_tpu.ops import kda_chunk as kc
    from ray_tpu.ops import kda_inputs as ki

    fam, m, cfg, eng, params, state, vec = _solar_cell(topo, monkeypatch)
    assert solar.SLOTS.prefill_segments(cfg, 32768) == 16
    _, text, traced = _widest_prefill(cfg, params, state, vec)
    assert sorted(traced) == [kc.__name__, ki.__name__], traced
    branches = _segment_branches(text)
    assert len(branches) == text.count(" while(") \
        == 2 * cfg.full_layers + cfg.kda_layers == 5
    # (and one more a layer: the expert layer's, ``moe.moe``)
    assert text.count(" conditional(") == 5 + cfg.n_layers
    for loop, dead, _ in branches:
        _dead_branch_hands_on_and_makes_zeros(dead)
        copied = [ln[:160] for ln in loop if re.search(
            r"= f32\[1,64,128,128\]\S* (copy|copy-start)\(", ln)]
        assert not copied, copied
    calls = _kda_chunk_calls(text)
    assert len(calls) == 3 and all(
        "/while/body/closed_call/cond/" in c for c in calls)
    inputs = _kda_inputs_calls(text, calls)
    assert len(inputs) == 3 and all(
        "/while/body/closed_call/cond/" in c for c in inputs)
    _loops_add_nothing_unscoped(text)
