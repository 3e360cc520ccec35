"""The block of gated short convolutions beside GQA layers of 64-wide
heads (``models/lfm2.py``) at the rag cell's sizes, compiled for a
described v5e (``tests/_tpu_compile.py`` says how and why): the 32-slot
decode chunk, whose attention is the ``decode_attn`` kernel over two
heads a lane tile, and the 8,192-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, MIB, _flash_fwd_bodies, _lower_prefill, _mem, _on, topo)
from ray_tpu.models import decode_engine as de

CONFIG, TRAFFIC = "lfm2-8b-a1b-ep2-1chip", "rag-saturated"


def _lfm2_cell(topo, monkeypatch):
    """``lfm2-8b-a1b-ep2-1chip.rag-saturated``'s model, engine shape and
    arguments on one described chip, the kernels asked for by name (the
    dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import lfm2
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    with open(f"benchmark/traffic/{TRAFFIC}.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model(CONFIG)
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: lfm2.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def test_lfm2_decode_chunk_attends_in_the_kernel_two_heads_a_tile(
        topo, monkeypatch):
    """The cell's decode program (24 layers, 16 of 32 experts held, 32
    slots: eighteen conv layers' two rows a slot in one array, one stack
    of 8,720 rows of 512 + 512 for six layers): a step calls
    ``decode_attn`` once a full layer (never the XLA body, which would
    read all 8,720 rows of every slot) with q laid out as FOUR heads of
    128 (a tile's two kv heads' eight query rows in a block of 16) and
    ``moe_gmm`` three times an expert layer, with no ``conditional``
    (half of the experts held gives the expert layer no capacity:
    ``moe.compact_rows``); neither stack is copied; no matrix exists in
    float32; arguments and temporaries stay under 13.5 GiB of the chip's
    16."""
    from ray_tpu.models import lfm2

    fam, m, cfg, eng, params, state, vec = _lfm2_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    assert (slots, max_len, eng["chunk_tokens"]) == (32, 8720, 16)
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum("decode_attn" in c for c in calls) == cfg.full_layers == 6
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers == 66
    assert len(calls) == 6 + 66
    assert " conditional(" not in text
    # a call's q block: [slots, 4 tiles, 16 rows, 128 lanes]
    assert f"bf16[{slots},4,16,128]" in text
    stack = f"bf16[6,{slots},{max_len},512]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)
    rows = f"bf16[18,{slots},2,2048]"
    assert rows in text
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(lfm2.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 32 * 107_298_816
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nlfm2 decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13.5 * 1024 * MIB), _mem(compiled)


def test_lfm2_8192_row_prefill_is_four_segments_and_six_flash_kernels(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    8,192 rows in four segments of 2,048, every layer one scan with ONE
    ``conditional`` (a dead segment hands the carry on): ``flash_fwd``
    once a full layer inside its scan (the forward-only call at a traced
    offset: one result, blocks whose last dimension is the 64 of a
    head), ``moe_gmm`` three times an expert layer; no ``[8192, 8192]``
    or ``[2048, 8192]`` scores, no ``[P, vocabulary]`` logits; the
    donated state is updated in place; beside 32 slots the call fits the
    chip's 16 GiB."""
    from ray_tpu.models import lfm2

    fam, m, cfg, eng, params, state, vec = _lfm2_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 8192
    assert lfm2.SLOTS.prefill_segments(cfg, 8192) == 4
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 8192,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum(bool(re.match(r"%flash_fwd(\.\d+)?$", c)) for c in calls) \
        == cfg.full_layers == 6
    bodies = _flash_fwd_bodies(text)
    assert [n for n, _ in bodies] == [1] * 6
    # a cell: a kv head's four query heads over 512 rows, 1,024 keys
    assert "memref<1x4x512x64xbf16" in bodies[0][1]
    assert "memref<1x1x1024x64xbf16" in bodies[0][1]
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers
    assert len(calls) == 6 + 3 * cfg.moe_layers
    assert text.count(" conditional(") == cfg.n_layers
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    assert not [a for a in arrays if a[1].count(8192) >= 2]
    assert not [a for a in arrays if a[1][-2:] == (2048, 8192)]
    # (logits are [.., vocabulary]; [vocabulary, 2048] is the embedding)
    assert not [a for a in arrays if a[1][-1] == cfg.vocab_size
                and np.prod(a[1][:-1]) >= 1024]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        lfm2.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nlfm2 prefill 1 x 8192: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2048 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.0 * 1024 * MIB), _mem(compiled)
