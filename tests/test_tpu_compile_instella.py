"""The block with gated latent attention in every layer
(``models/instella.py``) at the longdoc cell's sizes, compiled for a
described v5e (``tests/_tpu_compile.py`` says how and why): its decode
chunk and its one-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, _flash_fwd_bodies, _flash_fwd_calls, _mem, MIB, _on, topo)
from ray_tpu.models import decode_engine as de


def _instella_cell(topo, monkeypatch):
    """``instella-moe-16b-a3b-pp4-1chip.longdoc-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    import dataclasses

    from benchmark import manifest
    from ray_tpu.models import instella
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention_latent", functools.partial(
        da.decode_attention_latent, use_kernel=True))
    with open("benchmark/traffic/longdoc-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("instella-moe-16b-a3b-pp4-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    cfg = dataclasses.replace(prog.cfg, use_flash=True)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: instella.SLOTS.init_state(
        cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, cfg, eng, params, state, vec


def test_instella_decode_chunk_reads_the_latent_stack_in_place(
        topo, monkeypatch):
    """The cell's decode program (7 layers, all 64 experts, 32 slots of
    16,912 rows of 640): a step calls ``decode_attn_latent`` once a
    layer on the one stack and ``moe_gmm`` three times an expert layer;
    the donated stack is updated in place, never copied nor sliced by
    layer; no matrix exists in float32; arguments and temporaries stay
    under 13 GiB of the chip's 16."""
    from ray_tpu.models import instella

    fam, m, cfg, eng, params, state, vec = _instella_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    # (a step's assignments give the expert layer no capacity,
    # ``moe.compact_rows``: its lines are the parent's, no branch)
    assert " conditional(" not in text
    assert text.count("decode_attn_latent") >= cfg.n_layers
    assert "decode_attn." not in text.replace("decode_attn_latent", "")
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers == 25
    dims = f"bf16[7,{slots},{max_len},640]"
    assert dims in text
    assert not re.search(re.escape(dims) + r"\S* copy\(", text)
    # no one layer of the stack is sliced out or written back whole
    assert f"bf16[1,{slots},{max_len},640]" not in text
    assert f"bf16[{slots},{max_len},640]" not in text
    for shape in {a.shape for a in jax.tree_util.tree_leaves(params)
                  if a.dtype == jnp.bfloat16 and a.size > 1 << 20}:
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape
    mem = compiled.memory_analysis()
    state_bytes = instella.SLOTS.state_bytes(state)["latent"]
    assert state_bytes == slots * fam.state_bytes_per_slot(
        m, max_len)["latent"]
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 norms)
    print(f"\ninstella decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 13 * 1024 * MIB), _mem(compiled)


def test_instella_16384_row_prefill_forms_no_scores(topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket, one prompt of
    16,384 rows: ``flash_fwd`` once a layer on 16 / 16 heads of 128 (k =
    nope ‖ the shared rotated key) and ``moe_gmm`` three times an expert
    layer; NO array of ``16384 x 16384`` exists (the scores would be 17
    GB in float32); the donated stack is updated in place; arguments and
    temporaries fit the chip's 16 GiB with room for the reference's
    probe."""
    from ray_tpu.models import instella

    fam, m, cfg, eng, params, state, vec = _instella_cell(topo, monkeypatch)
    prompt = jax.ShapeDtypeStruct((1, 16384), jnp.int32,
                                  sharding=vec(jnp.int32).sharding)
    lowered = de._prefill_batch_into_slots.lower(
        params, prompt, vec(jnp.int32, 1), vec(jnp.int32, 1),
        vec(jnp.uint32, 1), vec(jnp.float32, 1), vec(jnp.float32, 1),
        state, vec(jnp.int32), cfg=cfg)
    # the serving call is jitted by itself: the kernel is traced and
    # lowered ONCE a program, one private function the layers call (a
    # replica's start pays the trace, not the compile: PERF.md §6 PR 69)
    module = lowered.as_text()
    assert len(re.findall(r"func\.func private @attend_bucket\w*\(",
                          module)) == 1
    assert len(re.findall(r"call @attend_bucket\w*\(", module)) \
        == cfg.n_layers
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers
    assert text.count("flash_fwd") >= cfg.n_layers and "moe_gmm" in text
    # the SERVING call (PR 69): the forward-only body, ONE result a
    # layer (no lse; the parent's program held the differentiable
    # call's kernel, ``(2, "dc0d5840e825ba79")``, which ``forward``
    # alone keeps): a cell one head's 2,048 rows over 1,024 keys
    assert _flash_fwd_calls(text) == [(1, "b8e30ac4c7b1fb13")] * cfg.n_layers
    body = _flash_fwd_bodies(text)[0][1]
    assert "memref<1x1x2048x128xbf16" in body
    assert "vector<1024x2048xf32>" in body
    assert "16384,16384" not in text
    assert "bf16[1,16384,16,128]" in text or "bf16[1,16,16384,128]" in text
    # (no [P, vocabulary] logits either: the head sees the last real row)
    assert "16384,128896" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= instella.SLOTS.state_bytes(
        state)["latent"], _mem(compiled)
    print(f"\ninstella prefill 1 x 16384: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2304 * MIB, _mem(compiled)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 14.5 * 1024 * MIB), _mem(compiled)
