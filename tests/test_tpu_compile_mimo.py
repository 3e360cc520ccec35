"""The block with sink-carrying window layers beside full ones, keys wider
than values (``models/mimo.py``) at the longreason cell's sizes, compiled
for a described v5e (``tests/_tpu_compile.py`` says how and why): both
attention kernels at the published head widths, the decode chunk and the
32,768-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, MIB, MOSAIC_BODY, _dead_branch_hands_on_and_makes_zeros,
    _expert_branches, _flash_fwd_bodies, _loops_add_nothing_unscoped,
    _lower_prefill, _made_by, _mem, _mosaic_text, _on, _segment_branches,
    once, topo)
from ray_tpu.models import decode_engine as de


def _mimo_cell(topo, monkeypatch):
    """``mimo-v2.5-ep16-1chip.longreason-saturated``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import mimo
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("mimo-v2.5-ep16-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: mimo.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _widest_prefill(cfg, params, state, vec):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048, compiled once for the three
    tests that read it -> (the compiled program, its text)."""
    def make():
        compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                                  (params, state, vec)).compile()
        return compiled, compiled.as_text()

    return once("mimo prefill 32768", make)


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def test_mimo_decode_chunk_reads_four_stacks_in_place(topo, monkeypatch):
    """The cell's decode program (7 layers, 16 of 256 experts held, 32
    slots: two full stacks of 34,832 rows of 768 + 512 numbers, five
    rings of 128 rows of 1,536 + 1,024): a step calls ``decode_attn``
    once a layer (16 query rows a kv head on the full stacks, 8 and the
    sink on the rings; never the XLA body, which would read all 34,832
    rows of every slot) and ``moe_gmm`` three times an expert layer at
    4096 x 2048, with no ``conditional`` (256 assignments give the
    expert layer no capacity: ``moe.compact_rows``); the donated stacks
    are updated in place, never copied; no matrix exists in float32; arguments and temporaries stay under
    13.5 GiB of the chip's 16."""
    from ray_tpu.models import mimo

    fam, m, cfg, eng, params, state, vec = _mimo_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum("decode_attn" in c for c in calls) == cfg.n_layers == 7
    assert len(calls) == cfg.n_layers + 3 * cfg.moe_layers == 25
    assert " conditional(" not in text
    for dims in (f"bf16[2,{slots},{max_len},768]",
                 f"bf16[2,{slots},{max_len},512]",
                 f"bf16[5,{slots},128,1536]", f"bf16[5,{slots},128,1024]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(mimo.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 32 * 181_616_640
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nmimo decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13.5 * 1024 * MIB), _mem(compiled)


def test_mimo_32768_row_prefill_is_segments_and_two_flash_kernels(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048, every layer one scan:
    ``flash_fwd`` in the two full layers (d_qk 192, d_v 128, a segment's
    rows against the rows so far; since PR 67 the forward-only body, ONE
    result and no lse, a cell ``[1, 16, 128, 192]`` of q over 1,024 keys
    with the scores ``[1024, 2048]``, keys by the cell's rows) and
    ``flash_fwd_window`` in the five
    window layers (a cell a kv head's eight query heads, ``[1, 8, 128,
    192]`` of q over three ``[1, 1, 128, ...]`` blocks of k and of v, the
    band's at most; the scores ``[128, 1024]``, keys by the group's rows,
    and nothing ``1024 x 1024`` in the body; k and v taken as the scan
    carries them, never copied for the call),
    ``moe_gmm`` three times in either branch of an expert layer (the
    compact one and its fall-back, ``moe.moe``); no ``[32768, 32768]``
    scores, no whole ``[32768, 16384]`` gate or up of the dense layer in
    either type, no ``[P, vocabulary]`` logits; the donated state is
    updated in place; beside 32 slots the call fits the chip's 16 GiB
    (temporaries 3,317 MiB beside 12,084 MiB of arguments: the stream in
    and out of a layer, a layer's k and v so far, a segment's expert
    rows; on the chip the cell's peak reads 12.83 GB)."""
    from ray_tpu.models import mimo

    fam, m, cfg, eng, params, state, vec = _mimo_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert mimo.SLOTS.prefill_segments(cfg, 32768) == 16
    compiled, text = _widest_prefill(cfg, params, state, vec)
    calls = _kernel_calls(text)
    assert sum(bool(re.match(r"%flash_fwd_window\b", c)) for c in calls) \
        == cfg.window_layers == 5
    assert sum(bool(re.match(r"%flash_fwd(\.\d+)?$", c)) for c in calls) \
        == cfg.full_layers == 2
    # the forward-only body: ONE result each (no lse), one text; a cell
    # a kv head's 16 query heads over 128 rows, the scores keys by rows
    full = _flash_fwd_bodies(text)
    assert [n for n, _ in full] == [1, 1] and len(set(full)) == 1
    assert f"memref<1x16x128x{cfg.head_dim}xbf16" in full[0][1]
    assert "vector<1024x2048xf32>" in full[0][1]
    assert "vector<2048x1024xf32>" not in full[0][1]
    lines = text.splitlines()
    made_by = _made_by(lines)
    group = cfg.n_heads // cfg.kv_heads(True)
    bands = [ln for ln in lines
             if KERNEL in ln and "flash_fwd_window" in ln.split(" = ")[0]]
    assert len(bands) == cfg.window_layers
    for call in bands:
        operands = re.findall(
            r"%[\w.\-]+", re.search(r"custom-call\(([^)]*)\)", call).group(1))
        # offset, q, three blocks of k and of v, the sink
        assert len(operands) == 9 and len(set(operands)) == 5, operands
        moved = {o: made_by.get(o) for o in operands if made_by.get(o) in (
            "copy", "copy-done", "transpose")}
        assert not moved, moved
        body = _mosaic_text(MOSAIC_BODY.search(call).group(1))
        args = body[:body.index("\n", body.index("^bb0"))]
        assert group == 8 and (
            f"memref<1x{group}x128x{cfg.head_dim}xbf16" in args
            and f"memref<1x{group}x128x{cfg.v_head_dim}xbf16" in args
            and args.count(f"memref<1x1x128x{cfg.head_dim}xbf16") == 3
            and args.count(f"memref<1x1x128x{cfg.v_head_dim}xbf16") == 3
        ), args
        assert f"vector<128x{group * 128}xf32>" in body
        assert not re.search(r"1024x1024x", body)
    assert sum("moe_gmm" in c for c in calls) == 2 * 3 * cfg.moe_layers
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    assert "32768,32768" not in text and "2048,32768]" not in text
    assert not [a for a in arrays if 16384 in a[1] and 32768 in a[1]]
    assert not [a for a in arrays if cfg.vocab_size in a[1]
                and (32768 in a[1] or 2048 in a[1])]
    whole = [a for a in arrays if a[0] == "f32"
             and np.prod(a[1]) >= 32768 * 4096]
    assert not whole, whole[:4]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        mimo.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nmimo prefill 1 x 32768: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 3400 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.25 * 1024 * MIB), _mem(compiled)


def test_mimo_prefill_skips_the_segments_behind_the_prompts_last_live_one(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket (32,768 rows,
    sixteen segments; at eight the program is the same lines): a
    layer's one loop holds one ``conditional`` on a segment's first row
    against the prompt's rows, which the program reads from
    ``true_lens`` (``moe.in_segments`` with ``live``), and an expert
    layer's live branch one more (``moe.moe``'s). The dead branch
    hands the k and v rows it carries on (``[1, Hkv, 32768, 192 / 128]``,
    50 to 200 MB a layer) and makes zeros, nothing else: no kernel, no
    fusion, no copy, and no loop copies them either (written a segment
    at a time in place); each layer's flash kernel is called in the live
    branch; the branch lands nothing in ``unscoped`` (three instructions
    on the parent, ``PERF.md`` §6 PR 51)."""
    from ray_tpu.models import mimo

    fam, m, cfg, eng, params, state, vec = _mimo_cell(topo, monkeypatch)
    assert mimo.SLOTS.prefill_segments(cfg, 32768) == 16
    _, text = _widest_prefill(cfg, params, state, vec)
    branches = _segment_branches(text)
    assert len(branches) == text.count(" while(") == cfg.n_layers == 7
    assert text.count(" conditional(") == cfg.n_layers + cfg.moe_layers
    for loop, dead, live in branches:
        _dead_branch_hands_on_and_makes_zeros(dead)
        copied = [ln[:160] for ln in loop + live if re.search(
            r"= bf16\[1,[48],32768,(192|128)\]\S* (copy|copy-start)\(", ln)]
        assert not copied, copied
        assert sum(KERNEL in ln and "flash_fwd" in ln.split(" = ")[0]
                   for ln in live) == 1
    _loops_add_nothing_unscoped(text)


def test_mimo_segments_expert_layer_works_on_the_rows_its_experts_got(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket (segments of
    2,048 rows: N = 16,384 assignments a segment, C = 2,048, whatever
    the bucket): every expert
    layer's live segment holds ONE ``conditional`` of two branches that
    each call ``moe_gmm`` three times. The compact branch multiplies
    ``[2048, 4096]`` and ``[2048, 2048]`` operands and makes ONE array
    of 16,384 rows, y put back at every assignment's place for the sum
    over ``top_k`` (the lines that keep the sum's order and bits); the
    other N-row arrays of the expert layer (the gathered rows, gate, up,
    their product, y) live in the fall-back's computation and nowhere
    else in the program."""
    fam, m, cfg, eng, params, state, vec = _mimo_cell(topo, monkeypatch)
    _, text = _widest_prefill(cfg, params, state, vec)
    found = _expert_branches(text)
    assert len(found) == cfg.moe_layers == 6
    wide = re.compile(r"= \(?(?:bf16|f32)\[(?:\d+,)*16384,\d{3,}\]")

    def made(lines):  # (not a fusion's view of its operand)
        return [ln for ln in lines
                if wide.search(ln) and " parameter(" not in ln]

    in_branches = set()
    for compact, fallback in found:
        for branch in (compact, fallback):
            calls = [ln for ln in branch
                     if KERNEL in ln and "moe_gmm" in ln.split(" = ")[0]]
            assert len(calls) == 3, len(calls)
        assert any("bf16[2048,4096]" in ln.split("custom-call(")[1]
                   for ln in compact if KERNEL in ln)
        put_back = made(compact)  # (one fusion and what it holds)
        assert sum(" fusion(" in ln for ln in put_back) == 1 and all(
            "bf16[16384,4096]" in ln and "gather" in ln
            for ln in put_back), [ln[:160] for ln in put_back]
        assert len(made(fallback)) >= 5
        in_branches.update(compact, fallback)
    # (the dense layer's ``w_down`` is a ``[16384, 4096]`` array too:
    # the expert layer's are the ones its scope names)
    outside = [ln[:160] for ln in text.splitlines() if wide.search(ln)
               and "moe_experts" in ln and ln not in in_branches]
    assert not outside, outside[:4]
