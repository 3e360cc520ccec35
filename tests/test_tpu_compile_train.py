"""The train step compiled for a described v5e, on one chip and sharded
over four (``tests/_tpu_compile.py`` says how and why), and, ``-m slow``,
the full 1B step.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from _tpu_compile import (  # noqa: F401 (topo: a fixture)
    KERNEL, _flash_fwd_calls, _mem, once, topo, _train_cfg, _train_step)
from ray_tpu.models import llama
from ray_tpu.parallel import AXES, MeshConfig, use_mesh


def _cell_step(topo) -> str:
    """2 layers at the four-chip cell's widths, batch and mesh
    (``internlm2-1.8b.pretrain-4k-fsdp2tp2``: fsdp=2 x tp=2), compiled
    once for the two tests that read its text."""
    return once("four-chip train step", lambda: _train_step(
        topo, _train_cfg(seq=4096, n_layers=2, d_ff=8192, vocab_size=92544,
                         rope_theta=1e6),
        MeshConfig(fsdp=2, tp=2), batch=6, seq=4096).as_text())


def test_sharded_train_step_compiles_with_kernel(topo):
    """The four-chip cell's step on fsdp=2 x tp=2 (``_cell_step``; 2
    layers at 1B widths on that mesh before PR 66, another program of
    the same parts): before the shard_map in ops/attention.py this
    failed with 'Mosaic kernels cannot be automatically partitioned'."""
    text = _cell_step(topo)
    assert KERNEL in text
    assert "all-reduce" in text and "all-gather" in text
    # the forward kernel with its lse, once a layer: PR 66's text
    assert set(_flash_fwd_calls(text)) == {(2, "c45ea4c48f353cb0")}


def _in_flight(text: str, shape: str) -> dict:
    """-> {collective-permute-start of ``shape``: the scheduled lines
    between it and its ``-done``} (a compiled module's text is in
    schedule order)."""
    lines = text.splitlines()
    out = {}
    for i, line in enumerate(lines):
        m = re.match(r"\s*(%[\w.\-]+) = \(" + re.escape(shape)
                     + r".* collective-permute-start\(", line)
        if m:
            done = next(j for j in range(i + 1, len(lines))
                        if f"collective-permute-done({m.group(1)})"
                        in lines[j])
            out[m.group(1)] = lines[i + 1:done]
    return out


def test_sharded_train_step_hides_its_tp_transfers(topo):
    """2 layers at the four-chip cell's widths, batch and mesh
    (``internlm2-1.8b.pretrain-4k-fsdp2tp2``): the residual stream's
    all-reduces over the tp pair ([3, 4096, 2048], five a layer over
    forward, recompute and backward, each synchronous) are gone from the
    layer loops; in their place asynchronous transfers of half the rows,
    products scheduled between their start and their done
    (``parallel/tp_products.py``). What is left of that shape is the
    head's input gradient, once a step. Says the mechanism engaged;
    only the chip says how much of a transfer its product hides."""
    text = _cell_step(topo)
    assert KERNEL in text
    whole = [ln for ln in text.splitlines()
             if re.search(r"= bf16\[3,4096,2048\]\S* all-reduce\(", ln)]
    assert all("lm_head" in ln for ln in whole) and len(whole) <= 1, whole
    flights = _in_flight(text, "bf16[3,2048,2048]")
    # a layer: 4 forward, 3 in the recompute (w_down's sum is not needed
    # again), 4 backward
    assert len(flights) == 11, list(flights)
    covered = [name for name, between in flights.items()
               if any("dot_general" in ln and " fusion(" in ln
                      for ln in between)]
    assert len(covered) >= 8, (covered, list(flights))


def test_sharded_flash_refuses_what_it_cannot_split(topo):
    from ray_tpu.ops.attention import attention

    q = jnp.zeros((2, 128, 4, 128), jnp.bfloat16)
    kv = jnp.zeros((2, 128, 1, 128), jnp.bfloat16)
    devs = np.asarray(topo.devices)
    with use_mesh(Mesh(devs.reshape(MeshConfig(fsdp=2, tp=2).shape), AXES)):
        with pytest.raises(ValueError, match="tp has to divide n_kv_heads"):
            jax.eval_shape(
                lambda: attention(q, kv, kv, use_flash=True))
    with use_mesh(Mesh(devs.reshape(MeshConfig(sp=2, tp=2).shape), AXES)):
        with pytest.raises(NotImplementedError, match="shards the sequence"):
            jax.eval_shape(
                lambda: attention(q, kv, kv, use_flash=True))
    # inside parallel/pipeline.py's pp stages: not brought up, said so
    cfg = _train_cfg(n_layers=2, pipeline_microbatches=2)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((4, 2048), jnp.int32)
    with use_mesh(Mesh(devs.reshape(MeshConfig(pp=2, tp=2).shape), AXES)):
        with pytest.raises(NotImplementedError, match="already manual"):
            jax.eval_shape(lambda p, t: llama.forward(p, t, cfg), params, tok)


# ---- the long programs: -m slow, run before a chip call ----

@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_full_1b_train_step_compiles(topo, chips):
    """All 22 layers, b2 x T2048, flash_qkv remat, bf16 grads and
    moments: the program the smoke's train phases run. Prints
    memory_analysis() — on one chip it sits at the edge of 16 GB."""
    compiled = _train_step(
        topo, _train_cfg(),
        MeshConfig(fsdp=2, tp=2) if chips == 4 else MeshConfig())
    text = compiled.as_text()
    print(f"\n1b train step, {chips} chip(s): {_mem(compiled)} "
          f"kernel calls={text.count(KERNEL)}")
    assert KERNEL in text
