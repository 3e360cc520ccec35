"""Which flash call a block's prefill takes (PR 69), for the three blocks
whose ``prefill`` is also their differentiable ``forward``
(``models/solar.py``, ``granite.py``, ``instella.py``): the SERVING call
(``_Slots.prefill``) attends a whole bucket through the forward-only
``flash_fwd`` (``ops.attention.attend_bucket``: one result, no lse),
``forward`` (what ``loss_fn`` differentiates) through
``ops.attention.attention`` and its ``(out, lse)``.

Both kernels run in the Pallas interpreter here: the configurations ask
``use_flash=True`` and the tests patch ``interpret=True`` into the two
calls (never inferred from the backend). float32, so the two routes
differ by the order of their sums alone.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import forget_programs, pallas_calls
from ray_tpu.models import granite, instella, moe, solar
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attend_bucket, attend_rows

BUCKET = 128
LENS = (90, 61)  # right-padded: the bucket's last segment of 32 is dead
# the tolerance each block's own tests hold float32 to
BLOCKS = {"solar": (solar, 1e-4), "granite": (granite, 1e-5),
          "instella": (instella, 1e-4)}


@pytest.fixture
def kernels_in_the_interpreter(monkeypatch):
    """Both flash calls interpreted, a bucket in four segments of 32."""
    for name in ("flash_fwd", "flash_attention"):
        monkeypatch.setattr(fa, name, functools.partial(
            getattr(fa, name), interpret=True))
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 32)
    forget_programs()
    yield
    forget_programs()


@functools.cache
def _model(block: str):
    mod = BLOCKS[block][0]
    cfg = {"solar": solar.SolarConfig, "granite": granite.GraniteConfig,
           "instella": instella.InstellaConfig}[block].tiny(use_flash=True)
    return mod, cfg, mod.init_params(cfg, jax.random.PRNGKey(11))


def _prompts():
    toks = jax.random.randint(jax.random.PRNGKey(3), (len(LENS), BUCKET),
                              1, 256)
    lens = jnp.array(LENS, jnp.int32)
    return jnp.where(jnp.arange(BUCKET)[None] < lens[:, None], toks, 0), lens


def _prefill(mod, params, toks, lens, cfg, **kw):
    """-> (h, the rows a slot keeps as one tree) by either route."""
    if mod is instella:
        h, rows = mod.prefill(params, toks, cfg, **kw)
        return h, rows
    h, state, _ = mod.prefill(params, toks, lens, cfg, **kw)
    return h, state


def _attention_layers(block: str, cfg) -> int:
    return cfg.n_layers if block == "instella" else cfg.full_layers


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_serving_prefill_is_forward_on_right_padded_prompts(
        block, kernels_in_the_interpreter):
    """``_Slots.prefill`` on right-padded prompts (a dead segment behind
    the longer one) against ``forward``'s route on the same rows: the
    stream's real rows, the cache rows of the real positions, every
    recurrent state and the first tokens' logprobs agree within the
    block's float32 tolerance; the serving program holds one-result
    ``flash_fwd`` calls alone, a layer each, and ``forward``'s the
    two-result ones."""
    mod, cfg, params = _model(block)
    toks, lens = _prompts()
    tol = BLOCKS[block][1]
    served = {} if mod is instella else {"live": jnp.max(lens)}
    h_s, rows_s = _prefill(mod, params, toks, lens, cfg, **served)
    h_f, rows_f = _prefill(mod, params, toks, lens, cfg,
                           differentiable=True)
    for i, n in enumerate(LENS):
        np.testing.assert_allclose(h_s[i, :n], h_f[i, :n], atol=tol)
    real = (jnp.arange(BUCKET)[None] < lens[:, None])[None, :, :, None]
    leaves_s, tree_s = jax.tree_util.tree_flatten(rows_s)
    leaves_f, tree_f = jax.tree_util.tree_flatten(rows_f)
    assert tree_s == tree_f
    for a, b in zip(leaves_s, leaves_f):
        if a.ndim == 4 and a.shape[1:3] == (len(LENS), BUCKET):  # rows
            a, b = jnp.where(real, a, 0), jnp.where(real, b, 0)
        np.testing.assert_allclose(a, b, atol=tol)
    # through the engine's half: the streams are the serving route's
    # own, the first token's logprob is ``forward``'s at the last row
    ones = jnp.ones((len(LENS),), jnp.float32)
    streams, _, tok0, logp0, *_ = mod.SLOTS.prefill(
        params, toks, lens, jnp.zeros((len(LENS),), jnp.uint32), 0 * ones,
        ones, cfg, BUCKET)
    for a, b in zip(jax.tree_util.tree_leaves(streams), leaves_s):
        np.testing.assert_array_equal(a, b)
    logits = mod.forward(params, toks, cfg)
    last = jax.nn.log_softmax(
        logits[jnp.arange(len(LENS)), lens - 1].astype(jnp.float32))
    np.testing.assert_array_equal(tok0, jnp.argmax(last, -1))
    np.testing.assert_allclose(
        logp0, jnp.max(last, -1), atol=100 * tol)  # (a 256-way softmax)
    # which kernel each program holds
    layers = _attention_layers(block, cfg)
    serving = pallas_calls(jax.make_jaxpr(lambda p: _prefill(
        mod, p, toks, lens, cfg, **served))(params).jaxpr)
    assert [c for c in serving if c[0] == "flash_fwd"] \
        == [("flash_fwd", 1)] * layers, serving
    whole = pallas_calls(jax.make_jaxpr(
        lambda p: mod.forward(p, toks, cfg))(params).jaxpr)
    assert [c for c in whole if c[0] == "flash_fwd"] \
        == [("flash_fwd", 2)] * layers, whole


@pytest.mark.parametrize("block", list(BLOCKS))
def test_grad_of_loss_fn_runs_and_reaches_the_differentiable_kernel(
        block, kernels_in_the_interpreter):
    """``jax.grad(loss_fn)`` runs (finite, not all zero, down to the
    attention's own matrices) and its program holds
    ``flash_attention``'s forward with its lse and the backward
    kernels, no forward-only call."""
    mod, cfg, params = _model(block)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(5), (1, 65), 1, 256)}

    def loss(p):
        return mod.loss_fn(p, batch, cfg)[0]

    calls = pallas_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert ("flash_fwd", 2) in calls and ("flash_fwd", 1) not in calls
    assert any(name.startswith("flash_bwd") for name, _ in calls), calls
    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
    attn = [i for i in range(cfg.n_layers)
            if block == "instella" or cfg.full(i)][0]
    wo = grads["layers"][attn]["attn"]["wo"]
    assert float(jnp.abs(wo).max()) > 0
    first = "wq" if block == "instella" else "w_qkv"
    assert float(jnp.abs(grads["layers"][attn]["attn"][first]).max()) > 0


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_differentiated_serving_prefill_raises_by_name(
        block, kernels_in_the_interpreter):
    """The serving route under ``jax.grad`` raises ``flash_fwd``'s
    ``NotImplementedError``: no gradient is dropped in silence."""
    mod, cfg, params = _model(block)
    toks, lens = _prompts()
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda p: _prefill(mod, p, toks, lens, cfg)[0].sum())(
            params)


@pytest.mark.parametrize("call, kw", [(attend_rows, {"offset": 0}),
                                      (attend_bucket, {})])
def test_the_forward_only_kernel_refuses_a_mesh_of_several_devices(call, kw):
    """``attention`` wraps its kernel in a ``shard_map`` under an
    ambient mesh; the forward-only calls do not (the serving engines
    run one device) and must refuse a larger mesh loudly, never hand a
    Mosaic call to GSPMD. The XLA body is GSPMD's to partition."""
    from ray_tpu.parallel import MeshConfig, use_mesh
    from ray_tpu.parallel.mesh import build_mesh

    q = jnp.ones((2, 2, 64, 16))
    with use_mesh(build_mesh(MeshConfig(dp=2), jax.devices()[:2])):
        with pytest.raises(NotImplementedError, match="mesh of 2"):
            call(q, q, q, use_flash=True, **kw)
        call(q, q, q, use_flash=False, **kw)
