"""The block with window layers beside full ones (``models/exaone.py``) at
the reason-long cell's sizes, compiled for a described v5e
(``tests/_tpu_compile.py`` says how and why): its decode chunk and its
one-row prefill.
"""

import functools
import json
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, _mem, MIB, _on, topo, _whole_layer_ops)
from ray_tpu.models import decode_engine as de


def _exaone_cell(topo, monkeypatch):
    """``k-exaone-236b-a23b-ep8-1chip.reason-long-saturated``'s model,
    engine shape and arguments on one described chip, the kernels asked
    for by name (the dispatches would read the CPU backend here)."""
    from benchmark import manifest
    from ray_tpu.models import exaone
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", functools.partial(
        gm.grouped_matmul, use_kernel=True))
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, use_kernel=True))
    with open("benchmark/traffic/reason-long-saturated.json") as f:
        eng = json.load(f)["engine"]
    fam, m = manifest.model("k-exaone-236b-a23b-ep8-1chip")
    prog = fam.build(m, max_seq_len=eng["max_len"], remat=False)
    chip = SingleDeviceSharding(topo.devices[0])
    params = _on(chip, jax.eval_shape(prog.init_params,
                                      jax.random.PRNGKey(0)))
    state = _on(chip, jax.eval_shape(lambda: exaone.SLOTS.init_state(
        prog.cfg, eng["slots"], eng["max_len"])))
    vec = lambda dt, n=eng["slots"]: jax.ShapeDtypeStruct(  # noqa: E731
        (n,), dt, sharding=chip)
    return fam, m, prog.cfg, eng, params, state, vec


def _no_f32_matrix(text: str, params, but=()) -> None:
    matrices = {a.shape for a in jax.tree_util.tree_leaves(params)
                if a.dtype == jnp.bfloat16 and a.size > 1 << 20}
    assert (16, 6144, 2048) in matrices and (6144, 10240) in matrices
    for shape in matrices - set(but):
        assert f"f32[{','.join(map(str, shape))}]" not in text, shape


def test_exaone_decode_chunk_reads_both_stacks_in_place(topo, monkeypatch):
    """The cell's decode program (5 layers, 16 of 128 experts held, 64
    slots: four rings of 128 rows and one full stack of 5,136): a step
    calls ``decode_attn`` once a layer, on the ring or on the full stack
    (8 query rows a kv head), and ``moe_gmm`` three times an expert
    layer at 6144 x 2048 (``tiling``: 512 columns a block); the donated
    stacks are updated in place, never copied or sliced by layer; no
    matrix exists in float32; arguments and temporaries stay under
    11 GiB of the chip's 16."""
    from ray_tpu.models import exaone

    fam, m, cfg, eng, params, state, vec = _exaone_cell(topo, monkeypatch)
    slots, max_len = eng["slots"], eng["max_len"]
    compiled = de.decode_chunk.lower(
        params, state, vec(jnp.int32), vec(jnp.bool_), None, cfg=cfg,
        chunk=eng["chunk_tokens"]).compile()
    text = compiled.as_text()
    # (a step's assignments give the expert layer no capacity,
    # ``moe.compact_rows``: its lines are the parent's, no branch)
    assert " conditional(" not in text
    assert text.count("decode_attn") >= cfg.n_layers
    assert text.count(KERNEL) == cfg.n_layers + 3 * cfg.moe_layers == 17
    for dims in (f"bf16[1,{slots},{max_len},1024]",
                 f"bf16[4,{slots},128,1024]"):
        assert dims in text
        assert not re.search(re.escape(dims) + r"\S* copy\(", text), dims
    # no one layer of either stack is sliced out or written back whole
    assert _whole_layer_ops(text, cfg, slots, max_len) == []
    assert _whole_layer_ops(text, cfg, slots, 128) == []
    _no_f32_matrix(text, params)
    mem = compiled.memory_analysis()
    state_bytes = sum(exaone.SLOTS.state_bytes(state).values())
    assert state_bytes == slots * sum(
        fam.state_bytes_per_slot(m, max_len).values())
    assert mem.alias_size_in_bytes >= state_bytes, _mem(compiled)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert abs(weights - 2 * fam.num_params(m)) < 1 << 20  # (f32 norms)
    print(f"\nexaone decode chunk: {_mem(compiled)}")
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 11 * 1024 * MIB), _mem(compiled)


def test_exaone_one_row_prefill_compiles(topo, monkeypatch):
    """The cell's cold prefill call at its widest bucket: one prompt of
    1,024 rows, band-masked in the sliding layers, its last 128 rows
    gathered into the four rings and all of them into the full stack;
    the donated state is updated in place and no matrix is cast."""
    from ray_tpu.models import exaone

    fam, m, cfg, eng, params, state, vec = _exaone_cell(topo, monkeypatch)
    prompt = jax.ShapeDtypeStruct((1, 1024), jnp.int32,
                                  sharding=vec(jnp.int32).sharding)
    compiled = de._prefill_batch_into_slots.lower(
        params, prompt, vec(jnp.int32, 1), vec(jnp.int32, 1),
        vec(jnp.uint32, 1), vec(jnp.float32, 1), vec(jnp.float32, 1),
        state, vec(jnp.int32), cfg=cfg).compile()
    text = compiled.as_text()
    # (three in either branch of an expert layer, ``moe.moe``: the
    # compact one at C = 2,048 of 8,192 assignments, and its fall-back)
    assert text.count(KERNEL) == 2 * 3 * cfg.moe_layers and "moe_gmm" in text
    assert text.count(" conditional(") == cfg.moe_layers
    # (the one row's logits are a fused multiply and reduce over the
    # head, which converts it on the fly inside the fusion: no copy)
    _no_f32_matrix(text, params, but=[(6144, 19200)])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        exaone.SLOTS.state_bytes(state).values()), _mem(compiled)
    print(f"\nexaone prefill 1 x 1024: {_mem(compiled)}")
    assert mem.temp_size_in_bytes < 2 * 1024 * MIB, _mem(compiled)
