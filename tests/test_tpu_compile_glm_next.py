"""The block of several residual streams round KDA and block-sparse
NoPE latent attention (``models/glm_next.py``) at the longreason cell's
sizes, compiled for a described v5e (``tests/_tpu_compile.py`` says how
and why): ``ops/dsa.py``'s kernels at heads WITHOUT a rotated part (256
+ 0 / 256: five operands, one product a tile) and over POOLED keys (a
quarter of the rows), the KDA kernels at Solar-Open2's shape, the
24-slot decode chunk and the 32,768-row prefill, whose streams exist
for a segment alone.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, MIB, MOSAIC_BODY, _lower_prefill, _mem, _mosaic_text,
    _moved_operands, _on, topo)
from ray_tpu.models import decode_engine as de

GB = 10 ** 9
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
         "u32": 4, "f32": 4}


def _cell(topo, monkeypatch):
    """``glm-5.3-flash-ep8-1chip.longreason-saturated-24``'s model, engine
    shape and arguments on one described chip, the kernels asked for by
    name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import glm_next, solar
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops import kda_chunk, kda_inputs, kda_step

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_step", functools.partial(
        kda_step.kda_step, use_kernel=True))
    monkeypatch.setattr(solar, "_kda_chunk", functools.partial(
        kda_chunk.kda_chunk, use_kernel=True))
    monkeypatch.setattr(glm_next, "_kda_qkvg", functools.partial(
        kda_inputs.kda_inputs, use_kernel=True))
    with open("benchmark/traffic/longreason-saturated-24.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("glm-5.3-flash-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: glm_next.SLOTS.init_state(
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


def test_glm_next_decode_chunk_steps_four_states_and_selects_blocks(
        topo, monkeypatch):
    """The cell's decode program (5 layers, 36 of 288 experts held, 24
    slots): a step calls ``kda_step`` once a KDA layer (four, ``S``
    aliased), ``dsa_kth`` once over the 8,708 POOLED keys and
    ``dsa_decode_attn`` once over latent rows of 512 (no rotated key),
    ``moe_gmm`` three times an expert layer; the donated state is
    updated in place; no matrix exists in float32; the slot's bytes are
    the family's count."""
    from ray_tpu.models import glm_next

    fam, m, cfg, eng, params, state, vec = _cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    assert (slots, max_len, cfg.n_layers, cfg.sparse_layers,
            cfg.kda_layers) == (24, 34832, 5, 1, 4)
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "kda_step") == cfg.kda_layers
    assert _count(calls, "dsa_kth") == cfg.sparse_layers
    assert _count(calls, "dsa_decode_attn") == cfg.sparse_layers
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers == 12
    assert "approx" not in text.lower()
    assert text.count(" conditional(") == cfg.sparse_layers  # (the tie's)
    pooled = -(-max_len // cfg.index_pool)
    for dims in (f"bf16[1,{slots},{max_len},512]",
                 f"bf16[1,{slots},{pooled},128]"):
        assert dims in text, dims
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    assert f"[1,{slots},{max_len},640]" not in text  # no rotated key
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(glm_next.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 24 * 55_265_024
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert fam.num_params(m) == 4_718_150_030
    # (the streams' 7.9 M numbers and the norm vectors are float32)
    assert 0 <= weights - 2 * fam.num_params(m) < 20 << 20
    print(f"\nglm_next decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 768 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 12.5 * GB), _mem(compiled)


def test_glm_next_32768_row_prefill_keeps_its_streams_to_a_segment(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    32,768 rows in 16 segments of 2,048: ONE scan over segments whose
    body runs all five layers, so ``kda_inputs`` and ``kda_chunk`` stand
    four times in the text, ``dsa_index`` and ``dsa_kth`` once (over
    8,192 pooled keys) and ``dsa_attn`` once, with FIVE operands (offset,
    q, k, v, the bias: no rotated part) and one product a head's tile.
    NO array is ``[32768, 32768]``; the four streams exist for a segment
    (``[1, 2048, 4, 4096]``) and never for the prompt: the largest array
    with an extent of 32,768 is the embedded prompt or the streams' sum
    (bf16 ``[32768, 4096]``, 256 MiB). Arguments and temporaries under
    15.0 GB of the chip's 16 GiB."""
    from ray_tpu.models import glm_next
    from ray_tpu.ops import dsa

    fam, m, cfg, eng, params, state, vec = _cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 32768
    assert glm_next.SLOTS.prefill_segments(cfg, 32768) == 16
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 32768,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert _count(calls, "kda_inputs") == cfg.kda_layers == 4
    assert _count(calls, "kda_chunk") == cfg.kda_layers
    assert _count(calls, "dsa_index") == 1
    assert _count(calls, "dsa_kth") == 1
    assert _count(calls, "dsa_attn") == 1
    assert sum("moe_gmm" in c for c in calls) == 2 * 3 * cfg.moe_layers
    for dims in ("[32768,32768]", "[32768,4,4096]", "[1,32768,4,4096]",
                 "[32768,12288]", "[32768,19360]",
                 "bf16[1,64,32768,256]"):
        assert dims not in text, dims
    assert "f32[1,2048,8192]" in text  # a segment's scores: pooled keys
    assert "bf16[1,2048,32768]" in text  # and its bias over the rows
    assert "bf16[1,8,32768,256]" in text  # a group of heads' k and v
    assert "approx" not in text.lower()
    size, shape = _largest_with(text, 32768)
    assert size <= 32768 * cfg.d_model * 2, (size, shape)
    lines = text.splitlines()
    k = cfg.mla
    bq, bk, cell = dsa._ATTN_BLOCKS
    masked = [ln for ln in lines
              if KERNEL in ln and re.match(r"\s*%dsa_attn\b", ln)]
    assert len(masked) == 1
    operands = re.findall(
        r"%[\w.\-]+", re.search(r"custom-call\(([^)]*)\)", masked[0]).group(1))
    assert len(operands) == 5, operands  # offset, q, k, v, the bias
    moved = _moved_operands(lines, operands[2:4])
    assert not moved, moved
    body = _mosaic_text(MOSAIC_BODY.search(masked[0]).group(1))
    args = body[:body.index("\n", body.index("^bb0"))]
    assert (f"memref<1x{cell}x{bq}x{k.dn}xbf16" in args
            and f"memref<1x{cell}x{bk}x{k.dn}xbf16" in args
            and f"memref<1x{bq}x{bk}xbf16" in args), args
    assert not dsa._one_product(k.dn, 0)  # (nothing to join)
    assert len(re.findall(r"\btpu\.matmul\b", body)) == 2 * cell
    mem = compiled.memory_analysis()
    print(f"\nglm_next 32768-row prefill: {_mem(compiled)}; largest array "
          f"across the prompt {shape} = {size / MIB:.0f} MiB")
    assert mem.alias_size_in_bytes >= sum(
        glm_next.SLOTS.state_bytes(state).values()), _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.0 * GB), _mem(compiled)
