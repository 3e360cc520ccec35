"""The block of one sublayer a layer (``models/nemotron.py``) at the
reason cell's sizes, compiled for a described v5e (``tests/_tpu_compile.py``
says how and why): the 32-slot decode chunk, whose 23 recurrences are the
``ssd_step`` kernel with a block of four GROUPS of lane rows and whose 46
grouped products run at widths that are not whole lane tiles (1,856 = 14.5
x 128), and the 1,024-row prefill.
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

CONFIG, TRAFFIC = "nemotron-3-nano-30b-a3b-ep8-1chip", "reason-saturated"


def _nemotron_cell(topo, monkeypatch):
    """``nemotron-3-nano-30b-a3b-ep8-1chip.reason-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import granite, nemotron
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    # (the mixer's functions are Granite's: the step's dispatch is there)
    monkeypatch.setattr(granite, "_ssd_step", functools.partial(
        granite._ssd_step, use_kernel=True))
    with open(f"benchmark/traffic/{TRAFFIC}.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model(CONFIG)
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: nemotron.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def test_nemotron_decode_chunk_steps_23_grouped_states_in_place(
        topo, monkeypatch):
    """The cell's decode program (52 blocks, 16 of 128 experts held, 32
    slots: 23 float32 states of 32 x 32 x 128 x 128 (two heads a row of
    lanes, four lane rows a group), one stack of 3,088 rows of 256 + 256
    for six blocks): a step calls ``ssd_step`` once an M block with the
    state aliased to its output (never copied, never sliced into another
    memory) and each group's B and C as a ``[.., 8, 128, 2]`` operand,
    ``decode_attn`` once an attention block with sixteen query rows a kv
    head (never the XLA body) and ``moe_gmm`` TWICE an expert block (no
    gate), with no ``conditional`` (a decode step has no capacity); no
    matrix exists in float32; arguments and temporaries stay under 13.5
    GiB of the chip's 16."""
    from ray_tpu.models import nemotron

    fam, m, cfg, eng, params, state, vec = _nemotron_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    assert (slots, max_len, eng["chunk_tokens"]) == (32, 3088, 16)
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum("ssd_step" in c for c in calls) == cfg.ssm_layers == 23
    assert sum("decode_attn" in c for c in calls) == cfg.full_layers == 6
    assert sum("moe_gmm" in c for c in calls) == 2 * cfg.moe_layers == 46
    assert len(calls) == 23 + 6 + 46
    assert " conditional(" not in text
    h = f"f32[{slots},32,128,128]"
    assert h in text
    assert not re.search(re.escape(h) + r"\S* (copy|copy-start|slice-start)\(",
                         text)
    assert f"f32[{slots},8,128,2]" in text  # the groups' B and C columns
    assert f"bf16[{slots},2,16,128]" in text  # q: 16 rows a kv head
    stack = f"bf16[6,{slots},{max_len},256]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(nemotron.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 32 * 68_055_040
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\nnemotron decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13.5 * 1024 * MIB), _mem(compiled)


def test_nemotron_1024_row_prefill_has_six_flash_kernels_and_a_branch(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    1,024 rows in one segment: the chunked SSD scan in the 23 M blocks
    (the XLA body, a state a CHUNK of a group's heads and never one a
    row), the forward-only ``flash_fwd`` once an attention block (one
    result), ``moe_gmm`` twice a branch of every expert block's ONE
    ``conditional`` (6,144 assignments give a capacity of 1,536:
    ``moe.compact_rows``); no ``[heads, 1024, 1024]`` scores, no ``[P,
    vocabulary]`` logits; the donated state is
    updated in place; beside 32 slots the call fits the chip's 16 GiB."""
    from ray_tpu.models import moe, nemotron

    fam, m, cfg, eng, params, state, vec = _nemotron_cell(topo, monkeypatch)
    assert eng["prompt_buckets"] == [256, 512, 1024]
    assert nemotron.SLOTS.prefill_segments(cfg, 1024) == 1
    assert [moe.compact_rows(cfg, b * cfg.top_k)
            for b in eng["prompt_buckets"]] == [None, 768, 1536]
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 1024,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum(bool(re.match(r"%flash_fwd(\.\d+)?$", c)) for c in calls) \
        == cfg.full_layers == 6
    assert [n for n, _ in _flash_fwd_bodies(text)] == [1] * 6
    assert text.count(" conditional(") == cfg.moe_layers == 23
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    # (one float32 [1, 1024, 1024] is the flash call's own mask work; a
    # head's scores over the bucket would be 32 of them)
    assert not [a for a in arrays if a[1].count(1024) >= 2
                and np.prod(a[1]) > 1024 * 1024]
    chunks = 1024 // cfg.ssm_chunk
    per_row = [a for a in arrays if a[1][-2:] == (64, 128)
               and np.prod(a[1][:-2]) >= 1024 * 8]
    assert not per_row, per_row[:4]
    assert [a for a in arrays if a[1][-2:] == (64, 128)
            and np.prod(a[1][:-2]) == chunks * 64], "a state a chunk"
    # (logits are [.., vocabulary]; [2688, vocabulary] is the head)
    assert not [a for a in arrays if a[1][-1] == cfg.vocab_size
                and np.prod(a[1][:-1]) >= 1024
                and a[1] != (cfg.d_model, cfg.vocab_size)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        nemotron.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nnemotron prefill 1 x 1024: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2048 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.0 * 1024 * MIB), _mem(compiled)
