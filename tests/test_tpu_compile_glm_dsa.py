"""The block whose every layer attends over rows an indexer chooses, the
choice made in some layers and read by those behind them
(``models/glm_dsa.py``) at the longreason cell's sizes, compiled for a
described v5e (``tests/_tpu_compile.py`` says how and why): the four
kernels of ``ops/dsa.py`` at this block's widths (64 heads of 192 + 64 /
256, 32 index heads), the 16-slot decode chunk and the 32,768-row
prefill, whose selection crosses four layers and is never ``[P, P]``.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, MIB, MOSAIC_BODY, _live_kv_products, _lower_prefill, _mem,
    _mosaic_text, _moved_operands, _on, topo)
from ray_tpu.models import decode_engine as de

GB = 10 ** 9
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
         "u32": 4, "f32": 4}


def _glm_cell(topo, monkeypatch):
    """``glm-5.2-ep16-1chip.longreason-saturated-16``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import glm_dsa
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated-16.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("glm-5.2-ep16-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: glm_dsa.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def _count(calls, name: str) -> int:
    return sum(bool(re.match(rf"%{name}(\.\d+)?$", c)) for c in calls)


def _largest_with(text: str, extent: int) -> tuple:
    """(bytes, shape) of the largest array in the text one of whose
    extents is ``extent``."""
    best = (0, "")
    for dt, dims in set(re.findall(r"\b(pred|s8|u8|bf16|f16|s32|u32|f32)"
                                   r"\[([\d,]+)\]", text)):
        shape = [int(d) for d in dims.split(",")]
        if extent in shape:
            size = _ITEM[dt]
            for d in shape:
                size *= d
            best = max(best, (size, f"{dt}[{dims}]"))
    return best


def test_glm_decode_chunk_selects_twice_and_attends_five_times(
        topo, monkeypatch):
    """The cell's decode program (5 layers, 16 of 256 experts held, 16
    slots: five stacks of 34,832 latent rows of 640, two of index keys
    of 128): a step calls ``dsa_kth`` once an INDEXER layer (two) and
    ``dsa_decode_attn`` once a layer (five: the three shared layers
    attend over a bias they did not make), ``moe_gmm`` three times an
    expert layer; the index scores are float32 of all 32 heads; no sort
    and no top-k of 2,048 stands in for the selection; the donated
    stacks are updated in place, never copied; no matrix exists in
    float32."""
    from ray_tpu.models import glm_dsa

    fam, m, cfg, eng, params, state, vec = _glm_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    assert (slots, max_len, cfg.n_layers, cfg.index_layers) \
        == (16, 34832, 5, 2)
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "dsa_kth") == cfg.index_layers
    assert _count(calls, "dsa_decode_attn") == cfg.n_layers
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers == 12
    assert len(calls) == cfg.index_layers + cfg.n_layers + 3 * cfg.moe_layers
    assert f"f32[{slots},32,{max_len}]" in text or \
        f"f32[{slots},1,32,{max_len}]" in text  # every head's scores
    assert "approx" not in text.lower()
    # (one branch a SELECTION, where a tie stands at the threshold:
    # ``dsa.select``; the expert layer's is a prompt's alone)
    assert text.count(" conditional(") == cfg.index_layers
    for line in text.splitlines():  # (the router's top-k is of 256)
        if re.search(r"topk|top_k|TopK| sort\(", line):
            assert str(max_len) not in line.split("metadata=")[0], line
    for dims in (f"bf16[5,{slots},{max_len},640]",
                 f"bf16[2,{slots},{max_len},128]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(glm_dsa.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 16 * 240_758_784
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert fam.num_params(m) == 3_881_517_056
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nglm decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 768 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 12.5 * GB), _mem(compiled)


def test_glm_32768_row_prefill_hands_a_segments_selection_to_four_layers(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048: TWO scans over segments, the
    dense indexer layer's and the group's (an indexer layer and the
    three shared layers behind it in one body), so ``dsa_index`` and
    ``dsa_kth`` stand twice in the text and ``dsa_attn`` five times
    (inside each layer's loop over eight groups of 8 heads); ``moe_gmm``
    three times in either branch of an expert layer. The selection that
    crosses the four layers is a segment's ``[2048, 32768]`` bfloat16
    bias: NO array of any type is ``[32768, 32768]``, and the largest
    array with an extent of 32,768 is the stream itself (384 MiB; a
    segment's float32 scores are 256). No float32
    scores a head, no whole ``[32768, 12288]`` of the dense layer, no 64
    heads' k or v of 32,768 rows. A group of 8 heads' k and v are a
    pair of ``[1, 8, 32768, 192]`` / ``[.., 256]`` buffers zeroed once a
    layer's segment, which ``dots._live_kv``'s loop writes a chunk of
    2,048 rows at a time and ``dsa_attn`` takes as they are (no copy,
    transpose, pad or concatenate before it): no product is made of the
    bucket's ``[32768, 512]`` latents. Arguments and temporaries under
    15.0 GB of the chip's 16 GiB."""
    from ray_tpu.models import glm_dsa
    from ray_tpu.ops import dsa

    fam, m, cfg, eng, params, state, vec = _glm_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert glm_dsa.SLOTS.prefill_segments(cfg, 32768) == 16
    assert cfg.share_groups == ((0,), (1, 2, 3, 4))
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "dsa_index") == cfg.index_layers == 2
    assert _count(calls, "dsa_kth") == cfg.index_layers
    assert _count(calls, "dsa_attn") == cfg.n_layers == 5
    assert sum("moe_gmm" in c for c in calls) == 2 * 3 * cfg.moe_layers
    for dims in ("[32768,32768]", "f32[32,2048,32768]",
                 "f32[1,32,2048,32768]", "[32768,12288]",
                 "bf16[1,64,32768,192]", "bf16[1,64,32768,256]",
                 "[32768,19360]", "[1,32768,512]"):
        assert dims not in text, dims
    assert "f32[1,2048,32768]" in text  # a segment's scores: they may
    assert "bf16[1,2048,32768]" in text  # and its bias, for four layers
    assert "bf16[1,8,32768,256]" in text  # a group of heads' v
    assert "approx" not in text.lower()
    lines = text.splitlines()
    # k_nope's and v's product a layer, each of one chunk's rows
    k = cfg.mla
    assert sorted(_live_kv_products(lines)) == cfg.n_layers * [
        [8, k.dn, 2048]] + cfg.n_layers * [[8, k.dv, 2048]], \
        _live_kv_products(lines)
    # nothing that spans the prompt is larger than the stream itself
    # (bf16 [32768, 6144], 384 MiB: one and a half times a segment's
    # float32 scores against the prompt, a fifth of a [P, P] bf16 bias)
    size, shape = _largest_with(text, 32768)
    assert size <= 32768 * cfg.d_model * 2, (size, shape)
    heads = k.heads // cfg.prefill_head_groups
    bq, bk, cell = dsa._ATTN_BLOCKS  # (a segment is 2,048 rows)
    assert bq != bk and heads % cell == 0
    masked = [ln for ln in lines
              if KERNEL in ln and re.match(r"\s*%dsa_attn\b", ln)]
    assert len(masked) == cfg.n_layers
    for call in masked:
        operands = re.findall(
            r"%[\w.\-]+", re.search(r"custom-call\(([^)]*)\)", call).group(1))
        # offset, q_n, q_r, k_n, the one rotated key, v, the bias
        assert len(operands) == 7, operands
        moved = _moved_operands(lines, operands[3:6])
        assert not moved, moved
        body = _mosaic_text(MOSAIC_BODY.search(call).group(1))
        args = body[:body.index("\n", body.index("^bb0"))]
        assert (f"memref<1x{cell}x{bq}x{k.dn}xbf16" in args
                and f"memref<1x{cell}x{bq}x{k.dr}xbf16" in args
                and f"memref<1x{cell}x{bk}x{k.dn}xbf16" in args
                and f"memref<1x{cell}x{bk}x{k.dv}xbf16" in args
                and f"memref<1x{bk}x{k.dr}xbf16" in args
                and f"memref<1x{bq}x{bk}xbf16" in args), args
        assert f"vector<{bk}x{bq}xf32>" in body
        assert f"memref<{cell}x{k.dv}x{bq}xf32" in args
        # ONE product a head's score tile (192 + 64: two lane tiles of
        # the contraction together, three apart: ``dsa._one_product``):
        # the scratch [q_n | q_r] is joined in, and two products a head
        assert dsa._one_product(k.dn, k.dr)
        assert f"memref<{cell}x{bq}x{k.dn + k.dr}xbf16" in args, args
        assert len(re.findall(r"\btpu\.matmul\b", body)) == 2 * cell
        assert f"vector<{bk}x{k.dn + k.dr}xbf16>" in body
    mem = compiled.memory_analysis()
    print(f"\nglm 32768-row prefill: {_mem(compiled)}; largest array "
          f"across the prompt {shape} = {size / MIB:.0f} MiB")
    assert mem.alias_size_in_bytes >= sum(
        glm_dsa.SLOTS.state_bytes(state).values()), _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.0 * GB), _mem(compiled)
