"""The KDA decode step's kernel (``ray_tpu/ops/kda_step.py``) in the
Pallas interpreter on the CPU against the XLA body it replaces on a TPU
(``kda_recurrence`` and a ``where``): state and output over several
steps at every block size, slot and head counts the block does not
divide, inactive slots bit for bit, the path off the TPU, and the whole
model through the slots with the kernel forced on. Never a timing."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ling
from ray_tpu.ops import kda_step as ks

REL = 1e-6


def _inputs(key, b, h, dk, dv):
    """A step's vectors as ``ling._kda_inputs`` makes them: q and k of
    unit length (q times dk^-1/2), a log decay in (-5, 0), beta in
    (0, 1)."""
    kq, kk, kv, kg, kb = jax.random.split(key, 5)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(kq, (b, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(kk, (b, h, dk)))
    v = jax.random.normal(kv, (b, h, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(kg, (b, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(kb, (b, h)))
    return q, k, v, g, beta


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _steps(b, h, dk, dv, heads, active, n=4, seed=0):
    """``n`` steps from a seeded state through the kernel (interpreted)
    and through the XLA body. -> [(s, o) kernel, (s, o) body] a step."""
    key = jax.random.PRNGKey(seed)
    s0 = jax.random.normal(key, (b, h, dk, dv), jnp.float32)
    kernel = jax.jit(functools.partial(ks.kda_step, interpret=True,
                                       heads=heads))
    body = jax.jit(functools.partial(ks.kda_step, use_kernel=False))
    got_s = want_s = s0
    out = []
    for i in range(n):
        xs = _inputs(jax.random.fold_in(key, i + 1), b, h, dk, dv)
        got_s, got_o = kernel(got_s, *xs, active)
        want_s, want_o = body(want_s, *xs, active)
        out.append(((got_s, got_o), (want_s, want_o)))
    return s0, out


@pytest.mark.parametrize("heads", [8, 16, 32])
@pytest.mark.parametrize("slots", [1, 3, 32])
def test_state_and_output_are_the_recurrences_over_steps(slots, heads):
    """32 heads in blocks of ``heads``, every slot active: after each of
    four steps the state and the output are ``kda_recurrence``'s within
    1e-6 of their largest number (sums in another order, nothing else)."""
    _, steps = _steps(slots, 32, 32, 128, heads, jnp.ones((slots,), bool))
    for (got_s, got_o), (want_s, want_o) in steps:
        assert got_s.dtype == got_o.dtype == jnp.float32
        assert got_o.shape == (slots, 32, 128)
        assert _rel(got_s, want_s) <= REL
        assert _rel(got_o, want_o) <= REL


def test_the_published_head_is_the_recurrences():
    """One slot at the published widths (32 heads of 128 x 128), the
    block the chip runs (16 heads)."""
    assert ks.block_heads(32) == ks.BLOCK_HEADS == 16
    _, steps = _steps(1, 32, 128, 128, None, jnp.ones((1,), bool), n=2)
    for (got_s, got_o), (want_s, want_o) in steps:
        assert _rel(got_s, want_s) <= REL
        assert _rel(got_o, want_o) <= REL


@pytest.mark.parametrize("slots, h, heads, block", [
    (5, 12, 8, 6), (3, 6, 4, 3), (2, 7, 16, 7), (7, 4, 16, 4)])
def test_counts_the_block_does_not_divide(slots, h, heads, block):
    """A head count that ``heads`` does not divide takes its largest
    divisor under it; any count of slots is a grid of that many rows."""
    assert ks.block_heads(h, heads) == block
    active = jnp.arange(slots) % 3 != 1
    s0, steps = _steps(slots, h, 16, 16, heads, active)
    for (got_s, got_o), (want_s, want_o) in steps:
        assert _rel(got_s, want_s) <= REL
        assert _rel(got_o, want_o) <= REL
        np.testing.assert_array_equal(got_s[~active], s0[~active])


@pytest.mark.parametrize("active", [
    [True, False, True, True], [False, True, False, False],
    [False, False, False, False]])
def test_an_inactive_slot_keeps_its_state_bit_for_bit(active):
    """Through ``ling.kda_step`` with the kernel in the interpreter: a
    slot that is not active gets back the S it had and its convolution
    rows, every bit; the active ones get the XLA body's."""
    cfg = ling.LingConfig.tiny()
    p = ling.init_params(cfg, jax.random.PRNGKey(3))["layers"][0]["attn"]
    b, h, dk = 4, cfg.n_heads, cfg.kda_head_dim
    key = jax.random.PRNGKey(4)
    state = {"s": jax.random.normal(key, (b, h, dk, dk), jnp.float32),
             "conv": jax.random.normal(
                 key, (b, cfg.conv_kernel - 1, 3 * h * dk))}
    x = jax.random.normal(jax.random.PRNGKey(5), (b, 1, cfg.d_model))
    active = jnp.asarray(active)
    want_y, want = jax.jit(functools.partial(ling.kda_step, cfg, p))(
        x, state, active)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ling, "_kda_step", functools.partial(
            ks.kda_step, interpret=True))
        got_y, got = jax.jit(functools.partial(ling.kda_step, cfg, p))(
            x, state, active)
    idle = ~np.asarray(active)
    for leaf in ("s", "conv"):
        np.testing.assert_array_equal(got[leaf][idle], state[leaf][idle])
        np.testing.assert_array_equal(want[leaf][idle], state[leaf][idle])
        np.testing.assert_allclose(got[leaf], want[leaf], rtol=0, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=1e-6)
    if active.any():
        assert not np.array_equal(got["s"][~idle], state["s"][~idle])


def test_off_the_tpu_the_path_is_the_recurrence_itself(monkeypatch):
    """On this backend nobody asked for the kernel: ``kda_step`` is
    ``kda_recurrence`` and a ``where``, bit for bit, and no
    ``pallas_call`` is made; a head that is not whole lanes takes that
    path on any backend."""
    assert jax.default_backend() == "cpu"
    assert ling.kda_recurrence is ks.kda_recurrence
    calls = []
    monkeypatch.setattr(ks, "_kda_step", lambda *a, **kw: calls.append(a))
    b, h, dk = 3, 4, 16
    s = jax.random.normal(jax.random.PRNGKey(0), (b, h, dk, dk))
    xs = _inputs(jax.random.PRNGKey(1), b, h, dk, dk)
    active = jnp.array([True, False, True])
    got_s, got_o = ks.kda_step(s, *xs, active)
    new, o = ks.kda_recurrence(s, *xs)
    np.testing.assert_array_equal(got_o, o)
    np.testing.assert_array_equal(
        got_s, jnp.where(active[:, None, None, None], new, s))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ks.kda_step(s, *xs, active)  # 16 lanes of 128: the body, there too
    assert calls == []
    wide = jnp.zeros((1, 2, 128, 128))
    ks.kda_step(wide, *_inputs(jax.random.PRNGKey(2), 1, 2, 128, 128),
                jnp.ones((1,), bool))
    assert len(calls) == 1


def test_the_models_logits_through_the_slots_with_the_kernel_on(monkeypatch):
    """``tests/test_ling_block.py``'s comparison of the whole model with
    its plain reference (prefill into slots, ten ragged steps, float32
    logits inside 1e-4, the control outside), unchanged, with every KDA
    step through the kernel in the interpreter."""
    import test_ling_block as block

    calls = []

    def kernel(*a, **kw):
        calls.append(a[0].shape)
        return ks.kda_step(*a, interpret=True, **kw)

    monkeypatch.setattr(ling, "_kda_step", kernel)
    block.test_prefill_then_ragged_decode_is_the_references_forward(
        "float32", block.F32_TOL, 8, np.max)
    # (traced once a KDA layer for each of the two jitted steps)
    assert len(calls) >= 6 and set(calls) == {(4, 4, 16, 16)}
