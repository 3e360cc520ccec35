"""The hybrid block (``models/ling.py``) at the reason cell's sizes,
compiled for a described v5e (``tests/_tpu_compile.py`` says how and
why): its decode chunk and its one-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    _kda_chunk_calls, _kda_inputs_calls, KERNEL, _lower_prefill, _mem, MIB,
    _on, lowered_counting_kda_bodies, once, topo)
from ray_tpu.models import decode_engine as de


def _ling_cell(topo, monkeypatch):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    from benchmark import manifest
    from ray_tpu.models import ling
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(ling, "_kda_step", functools.partial(
        ling._kda_step, use_kernel=True))
    monkeypatch.setattr(ling, "_kda_chunk", functools.partial(
        ling._kda_chunk, use_kernel=True))
    monkeypatch.setattr(ling, "_kda_qkvg", functools.partial(
        ling._kda_qkvg, use_kernel=True))
    with open("benchmark/traffic/reason-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("ling-3.0-flash-vl-ep4-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: ling.SLOTS.init_state(
        prog.cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, prog.cfg, eng, params, state, vec


def test_ling_decode_chunk_keeps_its_state_and_weights_where_they_lie(
        topo, monkeypatch):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s decode program
    (7 layers, 128 of 512 experts held, 32 slots x 3088 rows): three
    kernel calls an expert layer; the donated state is updated in place
    and never copied (the six float32 ``[32,32,128,128]`` KDA states,
    the latent rows ``[32,3088,512]``); no matrix exists in float32 (the tree arrives in
    the serving types: a cast of one 250 M expert stack is 1 GB); and
    arguments and temporaries stay under 13 GiB of the chip's 16."""
    from ray_tpu.models import ling

    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    # (a step's assignments give the expert layer no capacity,
    # ``moe.compact_rows``: its lines are the parent's, no branch)
    assert " conditional(" not in text
    kda_calls = [line for line in text.splitlines()
                 if KERNEL in line and "kda_step" in line.split(" = ")[0]]
    assert len(kda_calls) == sum(
        cfg.attn_kind(i) == "kda" for i in range(cfg.n_layers)) == 6
    assert text.count(KERNEL) == 3 * cfg.moe_layers + len(kda_calls) == 24
    # (a step's one row takes the XLA body of ``ops.kda_inputs`` even
    # where the kernel is asked for: the chunk's text is the parent's)
    assert not re.search(r"%\S*kda_inputs\S* = ", text)
    for dims in (f"f32[{slots},32,128,128]", f"bf16[{slots},{max_len},512]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    # a KDA layer's step is one call that takes its state as operand 3
    # (behind ``active``, the vectors and v) and returns it in that
    # buffer; nothing else moves a state, whole or a quarter of it (the
    # XLA body's slices between memories; and with the kernel's operand
    # left to the compiler, its own: it brought four layers' states into
    # VMEM in quarters before the call and copied them back behind it)
    for line in kda_calls:
        assert line.split(" = ")[1].startswith(
            f"(f32[{slots},32,128,128]"), line
        assert "output_to_operand_aliasing={{0}: (3, {})}" in line, line
    moves = re.compile(r"\s*%(copy|copy-start|slice-start|async-start|"
                       r"dynamic-slice-start)[.\d]* = ")
    moved = [line[:160] for line in text.splitlines() if moves.match(line)
             and re.search(rf"f32\[({slots}|{slots // 4}),32,128,128\]", line)]
    assert not moved, moved[:3]
    # (the 64-wide rotated keys, 2% of the state, change their layout
    # once a chunk on the way in and out of the step loop: XLA's choice
    # for a minor dimension of half a lane tile, outside the loop)
    assert len(re.findall(rf"bf16\[{slots},{max_len},64\]\S* copy\(",
                          text)) <= 2
    matrices = {a.shape for a in jax.tree_util.tree_leaves(params)
                if a.dtype == jnp.bfloat16 and a.size > 1 << 20}
    assert (128, 2560, 768) in matrices and (2560, 12288) in matrices
    for shape in matrices:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(ling.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    print(f"\nling decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def _prefill(cfg, params, state, vec, bucket):
    """The cell's cold prefill call at ``bucket`` rows, lowered from
    nothing (no earlier trace of this shape) with the two KDA kernels'
    bodies counted, and compiled, once for the tests that read it. ->
    (the lowered module's text, the keywords each body was traced with,
    the compiled program)."""
    def make():
        lowered, traced = lowered_counting_kda_bodies(
            lambda: _lower_prefill(cfg, vec(jnp.int32).sharding, bucket,
                                   (params, state, vec)))
        return lowered.as_text(), [kw for _, kw in traced], lowered.compile()

    return once(("ling prefill", bucket), make)


@pytest.mark.parametrize("bucket", [
    256, pytest.param(512, marks=pytest.mark.slow),
    pytest.param(1024, marks=pytest.mark.slow)])
def test_ling_prefill_holds_one_kda_chunk_call_a_kda_layer(
        topo, monkeypatch, bucket):
    """``ling-3.0-flash-vl-ep4-1chip.reason-saturated``'s cold prefill
    call at each of its buckets (32 heads, 4 to 16 chunks): the
    chunkwise delta rule is one ``kda_chunk`` call a KDA layer, six a
    program, on the arrays as the projections leave them
    (``_kda_chunk_calls``); arguments and temporaries stay under 13 GiB
    of the chip's 16. Tier-1 holds the narrowest bucket, whose four
    chunks a call already cross the chunk boundary and whose operands
    XLA does prefetch; 512 and 1,024 rows (50 to 60 s each of the TPU's
    compiler for the same lines at other extents) are ``-m slow``, and
    the cell compiles both on the chip in every PR's check."""
    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    assert tuple(eng["prompt_buckets"]) == (256, 512, 1024)
    _, _, compiled = _prefill(cfg, params, state, vec, bucket)
    # (at 256 and 512 rows XLA prefetches a layer's 4 to 8 MB ``g`` into
    # VMEM ahead of two of the calls; at 1,024 nothing moves)
    text = compiled.as_text()
    calls = _kda_chunk_calls(text, prefetched_ok=bucket < 1024)
    assert len(calls) == sum(
        cfg.attn_kind(i) == "kda" for i in range(cfg.n_layers)) == 6
    assert all(f"f32[1,{bucket},4096]" in c for c in calls), calls[0][:300]
    # each fed by one ``kda_inputs`` call on the product's rows
    inputs = _kda_inputs_calls(text, calls)
    assert len(inputs) == 6
    assert all(f"bf16[1,{bucket},12288]" in c for c in inputs), \
        inputs[0][:300]
    mem = compiled.memory_analysis()
    print(f"\nling prefill 1 x {bucket}: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def test_lowering_lings_prefill_traces_the_kda_kernels_once(
        topo, monkeypatch):
    """What the kernel costs a process's start is its trace
    (``ops/kda_chunk.py``: a thousand lines of columns, seconds each):
    the call is jitted by itself, so lowering the prefill with its six
    KDA layers runs the kernel's body ONCE, not once a layer, and the
    lowered module holds one copy of the kernel that the six layers
    call. (Traced a layer, Ling's set-up read 120-160 s for the
    parent's 80-88: ``PERF.md`` §6, PRs 45-47.) Read off the 256-row
    call that the test above compiles (1,024 rows before PR 66: the
    layers and the kernels' keywords are the same at every bucket)."""
    fam, m, cfg, eng, params, state, vec = _ling_cell(topo, monkeypatch)
    text, traced, _ = _prefill(cfg, params, state, vec, 256)
    assert sorted(traced, key=len) == [
        {"hb": 16}, {"hb": 8, "dk": 128, "lower_bound": cfg.kda_lower_bound}]
    # (``ops/kda_inputs.py``'s call is jitted by itself for the same
    # reason: one private function, which the six layers call)
    for fn in ("_kda_chunk", "_kda_inputs"):
        assert len(re.findall(rf"func\.func private @{fn}\w*\(", text)) == 1
        assert len(re.findall(rf"call @{fn}\w*\(", text)) == 6
