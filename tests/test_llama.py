"""Llama model tests: shapes, causality, loss decreases, scan==unrolled."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.models import llama


def _cfg(**kw):
    return llama.LlamaConfig.tiny(**kw)


def test_forward_shapes(rng):
    cfg = _cfg()
    params = llama.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_param_axes_match_structure(rng):
    cfg = _cfg()
    params = llama.init_params(cfg, rng)
    axes = llama.param_logical_axes(cfg)
    ps = jax.tree_util.tree_structure(params)
    as_ = jax.tree_util.tree_structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x)
    )
    assert ps == as_
    # Every axes tuple rank matches param rank.
    flat_p = jax.tree_util.tree_leaves(params)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a), (p.shape, a)


def test_causality(rng):
    cfg = _cfg()
    params = llama.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (1, 16), 0, cfg.vocab_size)
    logits1 = llama.forward(params, tokens, cfg)
    tokens2 = tokens.at[0, 10:].set(0)
    logits2 = llama.forward(params, tokens2, cfg)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :10]), np.asarray(logits2[0, :10]), atol=1e-5
    )


def test_loss_decreases_under_sgd(rng):
    cfg = _cfg()
    params = llama.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        (loss, _), grads = jax.value_and_grad(
            llama.loss_fn, has_aux=True)(params, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses


def test_num_params_matches(rng):
    from ray_tpu.utils import tree_num_params

    cfg = _cfg()
    params = llama.init_params(cfg, rng)
    assert tree_num_params(params) == cfg.num_params()


def test_moe_forward_and_loss_decreases(rng):
    """MoE MLP (dense-dispatch, expert axis): forward shapes + learning."""
    cfg = llama.llama2_size("moe-tiny")
    params = llama.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        (loss, _), grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, cfg), has_aux=True
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(12):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8


def test_moe_top_k_masks_experts(rng):
    """top_k must zero all but k experts' gates, and rows renormalize."""
    cfg = llama.llama2_size("moe-tiny")
    for k in (1, 2):
        kcfg = llama.LlamaConfig(**{**cfg.__dict__, "top_k": k})
        params = llama.init_params(kcfg, rng)
        x = jax.random.normal(rng, (2, 16, kcfg.d_model), jnp.float32)
        gates = llama.moe_gates(kcfg, params["layers"]["router"][0], x)
        nonzero = (np.asarray(gates) > 0).sum(axis=-1)
        assert (nonzero == k).all()
        np.testing.assert_allclose(
            np.asarray(gates).sum(-1), 1.0, atol=1e-5
        )


def test_remat_policies_agree(rng, monkeypatch):
    """dots vs dots_flash vs nothing: same gradients, different remat."""
    import functools

    from ray_tpu.ops import flash_attention as fa

    # no chip here: the kernel runs in the Pallas interpreter, and that
    # is this test's choice (the op never infers it from the backend)
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    tokens = jax.random.randint(rng, (2, 33), 0, 256)
    batch = {"tokens": tokens}
    grads = {}
    for policy in ("dots", "dots_flash", "dots_flash_qkv",
                   "dots_flash_qkv_mlp", "nothing"):
        # use_flash=True: the flash kernel must be in the graph or the
        # flash_out/flash_lse plumbing goes untested
        cfg = llama.LlamaConfig.tiny(
            remat=True, remat_policy=policy, use_flash=True,
            max_seq_len=32,
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        g = jax.grad(lambda p: llama.loss_fn(p, batch, cfg)[0])(params)
        grads[policy] = g
    for policy in ("dots_flash", "nothing"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
            ),
            grads["dots"], grads[policy],
        )
