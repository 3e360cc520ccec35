"""The block of state-space layers beside one NoPE GQA layer
(``models/granite.py``) at the sessions cell's sizes, compiled for a
described v5e (``tests/_tpu_compile.py`` says how and why): the step
kernel at the published head, the 96-slot decode chunk and the 4,096-row
prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, MIB, _flash_fwd_bodies, _flash_fwd_calls, _lower_prefill, _mem,
    _on, topo)
from ray_tpu.models import decode_engine as de


def _granite_cell(topo, monkeypatch):
    """``granite-4.0-h-small-ep4-1chip.sessions-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import granite
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    monkeypatch.setattr(granite, "_ssd_step", functools.partial(
        granite._ssd_step, use_kernel=True))
    with open("benchmark/traffic/sessions-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("granite-4.0-h-small-ep4-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: granite.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def _kernel_calls(text: str) -> list:
    return [line.split(" = ")[0].strip() for line in text.splitlines()
            if KERNEL in line]


def test_granite_decode_chunk_updates_nine_states_in_place(topo,
                                                            monkeypatch):
    """The cell's decode program (ten layers, 18 of 72 experts held, 96
    slots: nine float32 states of 96 x 64 x 128 x 128 (two heads a row
    of lanes, ``ops.ssd_step.pack``), one stack of
    6,160 rows of 1,024 + 1,024): a step calls ``ssd_step`` once a Mamba
    layer with the state aliased to its output (never copied, never
    sliced into another memory), ``decode_attn`` once (never the XLA
    body, which would read all 6,160 rows of every slot) and ``moe_gmm``
    three times an expert layer, with no ``conditional`` (a quarter of
    the experts held gives the expert layer no capacity:
    ``moe.compact_rows``); no matrix exists in float32; arguments and
    temporaries stay under 13.5 GiB of the chip's 16."""
    from ray_tpu.models import granite

    fam, m, cfg, eng, params, state, vec = _granite_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    assert (slots, max_len, eng["chunk_tokens"]) == (96, 6160, 16)
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum("ssd_step" in c for c in calls) == cfg.ssm_layers == 9
    assert sum("decode_attn" in c for c in calls) == cfg.full_layers == 1
    assert len(calls) == cfg.n_layers + 3 * cfg.moe_layers == 40
    assert " conditional(" not in text
    h = f"f32[{slots},64,128,128]"
    assert h in text
    assert not re.search(re.escape(h) + r"\S* (copy|copy-start|slice-start)\(",
                         text)
    stack = f"bf16[1,{slots},{max_len},1024]"
    assert stack in text
    assert not re.search(re.escape(stack) + r"\S* copy\(", text)
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = sum(granite.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values()) == 96 * 63_436_288
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 leaves)
    print(f"\ngranite decode chunk: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 256 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13.5 * 1024 * MIB), _mem(compiled)


def test_granite_4096_row_prefill_is_two_segments_and_one_flash_kernel(
        topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    4,096 rows in two segments of 2,048, every layer one scan: the
    chunked SSD scan in the nine Mamba layers (the XLA body: no kernel
    of its own yet), ``flash_fwd`` once in the attention layer,
    ``moe_gmm`` three times an expert layer; no ``[4096, 4096]`` scores,
    no float32 state a ROW (``[rows, 128, 64, 128]`` for rows of a
    segment or the bucket: what there is is a state a CHUNK), no ``[P,
    vocabulary]`` logits; the donated state is updated in place; beside
    96 slots the call fits the chip's 16 GiB."""
    from ray_tpu.models import granite

    fam, m, cfg, eng, params, state, vec = _granite_cell(topo, monkeypatch)
    assert eng["prompt_buckets"][-1] == 4096
    assert granite.SLOTS.prefill_segments(cfg, 4096) == 2
    compiled = _lower_prefill(cfg, vec(jnp.int32).sharding, 4096,
                              (params, state, vec)).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert sum(bool(re.match(r"%flash_fwd(\.\d+)?$", c)) for c in calls) \
        == cfg.full_layers == 1
    # the SERVING call (PR 69): the forward-only body, ONE result (no
    # lse; the parent's program held the differentiable call's kernel,
    # ``(2, "b442ea24a3c6a115")``, which ``forward`` alone keeps): a
    # cell a kv head's 4 query heads over 512 rows
    assert _flash_fwd_calls(text) == [(1, "8659e3f2c62352a6")]
    body = _flash_fwd_bodies(text)[0][1]
    assert "memref<1x4x512x128xbf16" in body
    assert "vector<1024x2048xf32>" in body
    assert sum("moe_gmm" in c for c in calls) == 3 * cfg.moe_layers
    assert len(calls) == 1 + 3 * cfg.moe_layers
    arrays = {(dt, tuple(int(d) for d in dims.split(",")))
              for dt, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]+)\]",
                                         text)}
    # (the hidden size is 4,096 too: W_o and the stream are [4096, 4096];
    # scores would be 32 heads of it)
    assert not [a for a in arrays if a[1].count(4096) >= 2
                and np.prod(a[1]) > 4096 * 4096]
    assert "2048,2048]" not in text
    chunks = 2048 // cfg.ssm_chunk
    per_row = [a for a in arrays if a[1][-3:] == (128, 64, 128)
               and np.prod(a[1][:-3]) not in (1, chunks)]
    assert not per_row, per_row[:4]
    assert [a for a in arrays if a[1][-3:] == (128, 64, 128)
            and np.prod(a[1][:-3]) == chunks], "a state a chunk"
    # (logits are [.., vocabulary]; [vocabulary, 4096] is the embedding)
    assert not [a for a in arrays if a[1][-1] == cfg.vocab_size
                and np.prod(a[1][:-1]) >= 1024]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        granite.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\ngranite prefill 1 x 4096: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2048 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.0 * 1024 * MIB), _mem(compiled)
