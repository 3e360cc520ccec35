"""The block whose full layers attend over rows an indexer chooses beside
window layers with a ring of latent rows (``models/dots.py``) at the
longreason cell's sizes, compiled for a described v5e
(``tests/_tpu_compile.py`` says how and why): the four kernels of
``ops/dsa.py`` at the published widths, the 24-slot decode chunk and the
32,768-row prefill.
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


def _dots_cell(topo, monkeypatch):
    """``dots3-note-prev-ep8-1chip.longreason-saturated``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import dots
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated-24.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("dots3-note-prev-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: dots.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def _count(calls, name: str) -> int:
    return sum(bool(re.match(rf"%{name}(\.\d+)?$", c)) for c in calls)


def test_dots_decode_chunk_selects_in_one_kernel_and_reads_in_place(
        topo, monkeypatch):
    """The cell's decode program (5 layers, 32 of 256 experts held, 24
    slots: two stacks of 34,832 latent rows of 640 and of index keys of
    128, three rings of 513 rows of 1,152): a step calls ``dsa_kth`` and
    ``dsa_decode_attn`` once a full layer (the selection's 32 passes and
    the masked read; never the XLA bodies, which would read the keys 32
    times and every row of every slot) and ``moe_gmm`` three times an
    expert layer; the index scores are float32 of all 64 heads; no sort
    and no top-k of 2,048 stands in for the selection; the donated
    stacks are updated in place, never copied; no matrix exists in
    float32; arguments and temporaries stay under 11 GiB of the chip's
    16."""
    from ray_tpu.models import dots

    fam, m, cfg, eng, params, state, vec = _dots_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "dsa_kth") == cfg.full_layers == 2
    assert _count(calls, "dsa_decode_attn") == cfg.full_layers
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers == 12
    assert len(calls) == 2 * cfg.full_layers + 3 * cfg.moe_layers
    assert f"f32[{slots},64,{max_len}]" in text or \
        f"f32[{slots},1,64,{max_len}]" in text  # every head's scores
    assert "approx" not in text.lower()
    for line in text.splitlines():  # (the router's top-k is of 256)
        if re.search(r"topk|top_k|TopK| sort\(", line):
            assert str(max_len) not in line.split("metadata=")[0], line
    for dims in (f"bf16[2,{slots},{max_len},640]",
                 f"bf16[2,{slots},{max_len},128]",
                 f"bf16[3,{slots},513,1152]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(dots.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 24 * 110_549_760
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\ndots decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 768 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 11 * 1024 * MIB), _mem(compiled)


def test_dots_32768_row_prefill_is_segments_and_four_kernels(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048, every layer one scan: in a full
    layer ``dsa_index`` (a segment's rows against every index key so
    far), ``dsa_kth`` (the selection) and ``dsa_attn`` (the masked flash
    kernel, inside the loop over four groups of 32 heads); in a window
    layer ``flash_fwd_window`` at blocks of 512; ``moe_gmm`` three times
    in either branch of an expert layer; no ``[32768, 32768]`` array, no
    float32 ``[64, 2048, 32768]`` scores a head, no whole ``[32768,
    13824]`` of the dense layer, no 128 heads' k or v of 32,768 rows. A
    group of 32 heads' k and v are a pair of ``[1, 32, 32768, 128]``
    buffers zeroed once a segment, which ``dots._live_kv``'s loop writes
    a chunk of 2,048 rows at a time (the product and the update one
    fusion, in place) and ``dsa_attn`` takes as they are: no product is
    made of the bucket's ``[32768, 512]`` latents. Beside 24 slots the
    call fits the chip (temporaries 2.1 GiB beside 10.1 GiB of
    arguments)."""
    from ray_tpu.models import dots
    from ray_tpu.ops import dsa

    fam, m, cfg, eng, params, state, vec = _dots_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert dots.SLOTS.prefill_segments(cfg, 32768) == 16
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "dsa_index") == cfg.full_layers == 2
    assert _count(calls, "dsa_kth") == cfg.full_layers
    assert _count(calls, "dsa_attn") == cfg.full_layers
    assert _count(calls, "flash_fwd_window") == cfg.window_layers == 3
    assert sum("moe_gmm" in c for c in calls) == 2 * 3 * cfg.moe_layers
    for dims in ("[32768,32768]", "f32[64,2048,32768]",
                 "f32[1,64,2048,32768]", "[32768,13824]",
                 "bf16[1,128,32768,192]", "bf16[1,128,32768,128]",
                 "[32768,19008]", "[1,32768,512]"):
        assert dims not in text, dims
    assert "f32[1,2048,32768]" in text  # a segment's scores: they may
    assert "bf16[1,32,32768,128]" in text  # a group of heads' k and v
    assert "approx" not in text.lower()
    lines = text.splitlines()
    # k_nope's and v's product a full layer, each of one chunk's rows
    assert _live_kv_products(lines) == 2 * cfg.full_layers * [
        [32, 128, 2048]], _live_kv_products(lines)
    k = cfg.kind(False)
    heads = k.heads // cfg.prefill_head_groups
    bq, bk, cell = dsa._ATTN_BLOCKS  # (a segment is 2,048 rows)
    assert bq != bk and heads % cell == 0  # (the tile's sides tell apart)
    masked = [ln for ln in lines
              if KERNEL in ln and re.match(r"\s*%dsa_attn\b", ln)]
    assert len(masked) == cfg.full_layers
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
                and args.count(f"memref<1x{cell}x{bk}x{k.dn}xbf16") >= 2
                and f"memref<1x{bk}x{k.dr}xbf16" in args
                and f"memref<1x{bq}x{bk}xbf16" in args), args
        # keys down the sublanes, the segment's rows along the lanes; a
        # row's statistics whole lanes, none a [block_q, 128] slab
        assert f"vector<{bk}x{bq}xf32>" in body
        assert args.count(f"memref<{cell}x1x{bq}xf32") == 2, args
        assert f"memref<{cell}x{k.dv}x{bq}xf32" in args
        assert not re.search(rf"memref<(\d+x)*{bq}x128xf32", body)
        # the parent's operands and body: at 128 + 64 one product would
        # spare the matrix unit nothing (``dsa._one_product``), so no
        # joined scratch and two score products and ``v^T @ p`` a head
        assert not dsa._one_product(k.dn, k.dr)
        assert f"x{k.dn + k.dr}xbf16" not in body
        assert len(re.findall(r"\btpu\.matmul\b", body)) == 3 * cell
    mem = compiled.memory_analysis()
    print(f"\ndots 32768-row prefill: {_mem(compiled)}")
    assert mem.alias_size_in_bytes >= sum(
        dots.SLOTS.state_bytes(state).values()), _mem(compiled)
    assert mem.temp_size_in_bytes < 2.6 * 1024 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13.2 * 1024 * MIB), _mem(compiled)
