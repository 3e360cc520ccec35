"""The chunkwise delta rule's kernel (``ray_tpu/ops/kda_chunk.py``) in
the Pallas interpreter on the CPU against the recurrence a token at a
time (``kda_recurrence``) and against the XLA body it replaces on a TPU
(``kda_chunked``): head counts and heads a block, one chunk to 32, a
carried state, two segments against one call, padding rows, a decay
that underflows inside a chunk, keys nearly parallel under beta near 2,
the path off the TPU, the derivative, and the two blocks' own tests
with the kernel forced on. Never a timing."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ling, solar
from ray_tpu.ops import kda_chunk as kc
from ray_tpu.ops.kda_step import kda_recurrence

ATOL = 2e-5  # the blocks' tests' tolerance against the recurrence
C = 64


def _unit(a):
    return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)


def _inputs(seed, b, t, h, dk, dv=None, top=2.0):
    """A segment's arrays as the blocks' ``_kda_inputs`` make them: q
    and k of unit length (q times dk^-1/2), a log decay without a lower
    bound (most of it small, some under -6 a token), beta in (0, top),
    a state that is not empty."""
    dv = dv or dk
    key = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = _unit(jax.random.normal(key[0], (b, t, h, dk))) * dk ** -0.5
    k = _unit(jax.random.normal(key[1], (b, t, h, dk)))
    v = jax.random.normal(key[2], (b, t, h, dv))
    g = -8.0 * jax.random.uniform(key[3], (b, t, h, dk)) ** 4
    beta = top * jax.random.uniform(key[4], (b, t, h))
    s0 = 0.25 * jax.random.normal(key[5], (b, h, dk, dv))
    return q, k, v, g, beta, s0


def _recurrence(q, k, v, g, beta, s0):
    """The delta rule a token at a time. -> (o [B, T, H, dv], s)."""
    s, o = jax.lax.scan(
        lambda s, xs: kda_recurrence(s, *xs), s0,
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _exactly(q, k, v, g, beta, s0):
    """The same in float64 on the host: what both float32 forms stray
    from."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, s0))
    o = np.empty(v.shape)
    for t in range(q.shape[1]):
        s = s * np.exp(g[:, t])[..., None]
        pred = np.einsum("bhkv,bhk->bhv", s, k[:, t])
        s = s + (beta[:, t, :, None] * k[:, t])[..., None] \
            * (v[:, t] - pred)[..., None, :]
        o[:, t] = np.einsum("bhkv,bhk->bhv", s, q[:, t])
    return o, s


@functools.lru_cache(maxsize=None)
def _jitted(form, **kw):
    """(one trace and one compile a shape for the whole file: the
    kernel's graph in the interpreter costs six seconds)"""
    return jax.jit(functools.partial(form, **kw))


def _kernel(*args, chunk=C, heads=None):
    return _jitted(kc.kda_chunk, chunk=chunk, interpret=True,
                   heads=heads)(*args)


def _body(*args, chunk=C):
    return _jitted(kc.kda_chunked, chunk=chunk)(*args)


def _close(got, want, atol=ATOL):
    """Within ``atol`` of the largest number (of 1, if that is larger)."""
    for a, b in zip(got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape
        np.testing.assert_allclose(
            a, b, rtol=0, atol=atol * max(1.0, float(np.abs(b).max())))


@pytest.mark.parametrize("h, heads, chunks, dk", [
    (8, 8, 1, 128), (8, 4, 4, 16), (8, 8, 32, 16), (32, 8, 4, 16),
    (32, 32, 1, 16), (64, 8, 1, 16), (64, 16, 4, 16)])
def test_state_and_output_are_the_recurrences_and_the_xla_bodys(
        h, heads, chunks, dk):
    """``h`` heads in blocks of ``heads`` over ``chunks`` chunks of 64
    rows from a carried state: o and S are the recurrence's a token at a
    time within 2e-5 (sums in another order), and so the XLA body's
    within twice that (each strays from the recurrence on its own)."""
    args = _inputs(h + chunks, 1, chunks * C, h, dk)
    assert kc.block_heads(h, heads) == heads
    got = _kernel(*args, heads=heads)
    _close(got, _recurrence(*args))
    _close(got, _body(*args), 2 * ATOL)


def test_the_published_head_is_the_recurrences_at_two_rows_of_a_batch():
    """Two prompts at the published widths (heads of 128 x 128), the
    block the chip runs (16 heads)."""
    assert kc.block_heads(64, kc.BLOCK_HEADS) == kc.BLOCK_HEADS == 16
    assert kc.block_heads(32, kc.BLOCK_HEADS) == 16
    args = _inputs(0, 2, 2 * C, 16, 128)
    got = _kernel(*args)
    _close(got, _recurrence(*args))
    _close(got, _body(*args), 2 * ATOL)


def test_two_segments_in_a_row_are_one_call():
    """S handed from a call to the next (a layer's scan over a prompt's
    segments) is S carried inside one call: the same chunks in the same
    order, bit for bit."""
    q, k, v, g, beta, s0 = _inputs(1, 1, 4 * C, 8, 16)
    o, s = _kernel(q, k, v, g, beta, s0)
    half = 2 * C
    o1, s1 = _kernel(q[:, :half], k[:, :half], v[:, :half], g[:, :half],
                     beta[:, :half], s0)
    o2, s2 = _kernel(q[:, half:], k[:, half:], v[:, half:], g[:, half:],
                     beta[:, half:], s1)
    np.testing.assert_array_equal(jnp.concatenate([o1, o2], 1), o)
    np.testing.assert_array_equal(s2, s)


def _padded(args, pad):
    """``args`` with the rows ``pad`` (bool [T]) made padding: beta 0
    and g 0 there (q, k, v stay what they were: garbage is allowed)."""
    q, k, v, g, beta, s0 = args
    return (q, k, v, jnp.where(pad[None, :, None, None], 0.0, g),
            jnp.where(pad[None, :, None], 0.0, beta), s0)


@pytest.mark.parametrize("pad", [
    np.r_[0:20], np.r_[16:32], np.r_[23:41], np.r_[40:64], np.r_[0:64],
    np.r_[30:128]], ids=["start", "sub-block", "middle", "end", "chunk",
                         "tail-and-chunk"])
def test_padding_rows_leave_the_state_of_the_real_rows(pad):
    """Rows with beta 0 and g 0, wherever in a chunk they lie: S after
    the call is S after the REAL rows alone a token at a time, and a
    whole chunk of them hands S on bit for bit (the first chunk's S
    when the second is all padding; ``s0`` when everything is)."""
    t = 2 * C
    mask = np.zeros(t, bool)
    mask[pad] = True
    args = _padded(_inputs(2, 1, t, 8, 16), jnp.asarray(mask))
    o, s = _kernel(*args)
    real = np.flatnonzero(~mask)
    o_ref, s_ref = _recurrence(*(a[:, real] for a in args[:5]), args[5])
    np.testing.assert_allclose(s, s_ref, rtol=0, atol=ATOL)
    np.testing.assert_allclose(o[:, real], o_ref, rtol=0, atol=ATOL)
    assert np.isfinite(np.asarray(o)).all()
    if mask[C:].all():
        first = tuple(a[:, :C] for a in args[:5]) + (args[5],)
        np.testing.assert_array_equal(s, _kernel(*first)[1])
    if mask[:C].all():
        later = tuple(a[:, C:] for a in args[:5]) + (args[5],)
        np.testing.assert_array_equal(s, _kernel(*later)[1])


def test_a_call_of_padding_alone_returns_the_state_it_was_given():
    args = _padded(_inputs(3, 1, 2 * C, 8, 16), jnp.ones((2 * C,), bool))
    o, s = _kernel(*args)
    np.testing.assert_array_equal(s, args[5])
    assert np.isfinite(np.asarray(o)).all()


def test_a_decay_that_underflows_inside_a_chunk_stays_finite():
    """``tests/test_solar_block.py``'s case at the kernel's chunk: g near
    -20 a token sums to under -80 within five rows of a sub-block and
    under -1,000 within the chunk (float32's e^x ends at -87), beta 1.5
    to 2, a third of the channels hardly decaying. Every decay is taken
    pairwise and against a sub-block's first row, never as e^-G: all
    finite, and still the recurrence (in float64: the float32 one strays
    as far from it as the kernel does)."""
    b, t, h, d = 1, 2 * C, 8, 16
    key = jax.random.split(jax.random.PRNGKey(3), 6)
    q, k = (_unit(jax.random.normal(kk, (b, t, h, d))) for kk in key[:2])
    v = jax.random.normal(key[2], (b, t, h, d))
    g = -20.0 - 5.0 * jax.random.uniform(key[3], (b, t, h, d))
    g = jnp.where(jax.random.uniform(key[4], (b, t, h, d)) < 0.3, -0.01, g)
    beta = jax.random.uniform(key[5], (b, t, h), minval=1.5, maxval=2.0)
    s0 = jax.random.normal(key[0], (b, h, d, d))
    assert float(jnp.cumsum(g, 1)[:, 4].min()) < -80
    assert float(jnp.cumsum(g, 1)[:, C - 1].min()) < -1000
    o, s = _kernel(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(s)).all()
    _close((o, s), _exactly(q, k, v, g, beta, s0))
    _close((o, s), _body(q, k, v, g, beta, s0), 2 * ATOL)


def test_keys_nearly_parallel_under_beta_near_two():
    """The solve's worst case: inside every sub-block the keys are one
    direction plus a hundredth of noise, nothing decays and beta is
    1.9 to 2, so ``I + Diag(beta) A`` has 2 in nearly every place under
    its diagonal. The substitution is the recurrence's own order and
    stays with it (held to the recurrence in float64, and to no more
    than twice what the XLA body strays from it)."""
    b, t, h, d = 1, 2 * C, 8, 16
    key = jax.random.split(jax.random.PRNGKey(4), 6)
    base = jnp.repeat(jax.random.normal(key[0], (b, t // kc.SUB, h, d)),
                      kc.SUB, axis=1)
    k = _unit(base + 0.01 * jax.random.normal(key[1], (b, t, h, d)))
    q = _unit(jax.random.normal(key[2], (b, t, h, d))) * d ** -0.5
    v = jax.random.normal(key[3], (b, t, h, d))
    g = jnp.full((b, t, h, d), -1e-4)
    beta = jax.random.uniform(key[4], (b, t, h), minval=1.9, maxval=2.0)
    s0 = jax.random.normal(key[5], (b, h, d, d))
    assert float(jnp.sum(k[:, 0] * k[:, kc.SUB - 1], -1).min()) > 0.99
    got = _kernel(q, k, v, g, beta, s0)
    want = _exactly(q, k, v, g, beta, s0)
    _close(got, want)
    body = _body(q, k, v, g, beta, s0)
    for a, b, c in zip(got, body, want):
        assert np.abs(a - c).max() <= 2 * np.abs(b - c).max() + 1e-6


def test_off_the_tpu_and_at_a_narrow_head_the_path_is_the_xla_body(
        monkeypatch):
    """On this backend nobody asked for the kernel: ``kda_chunk`` is
    ``kda_chunked``, bit for bit, and no ``pallas_call`` is made; a head
    that is not whole lanes takes that path on any backend."""
    assert jax.default_backend() == "cpu"
    assert ling.kda_chunked is kc.kda_chunked
    calls = []
    monkeypatch.setattr(kc, "_kda_chunk",
                        lambda *a, **kw: calls.append(kw) or (a[2], a[5]))
    args = _inputs(5, 1, C, 4, 16)
    got = kc.kda_chunk(*args, chunk=C)
    for a, b in zip(got, kc.kda_chunked(*args, chunk=C)):
        np.testing.assert_array_equal(a, b)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kc.kda_chunk(*args, chunk=C)  # 16 lanes of 128: the body, there too
    assert calls == []
    kc.kda_chunk(*_inputs(6, 1, C, 32, 128), chunk=C)
    assert calls == [{"chunk": C, "hb": 16, "interpret": False}]
    with pytest.raises(ValueError, match="sub-blocks"):
        kc.kda_chunk(*_inputs(6, 1, 48, 4, 16), chunk=24, use_kernel=True)


def test_a_differentiated_call_takes_the_xla_bodys_derivative():
    args = _inputs(7, 1, 2 * C, 4, 16, top=1.0)
    w = jax.random.normal(jax.random.PRNGKey(8), (1, 2 * C, 4, 16))

    def loss(fn, *xs):
        o, s = fn(*xs, chunk=C)
        return jnp.sum(o * w) + jnp.sum(s * s)

    every = tuple(range(6))
    got = jax.grad(functools.partial(loss, functools.partial(
        kc.kda_chunk, interpret=True)), every)(*args)
    want = jax.grad(functools.partial(loss, kc.kda_chunked), every)(*args)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0
    _close(got, want)


# ------------------------------------------------ the blocks' own tests


@pytest.fixture
def forced(monkeypatch):
    """Every ``kda_chunk`` call of both blocks through the kernel in the
    interpreter; -> the shapes of q it was called with."""
    calls = []

    def kernel(*a, **kw):
        calls.append(a[0].shape)
        return kc.kda_chunk(*a, interpret=True, **kw)

    assert solar._kda_chunk is ling._kda_chunk is kc.kda_chunk
    monkeypatch.setattr(ling, "_kda_chunk", kernel)
    monkeypatch.setattr(solar, "_kda_chunk", kernel)
    return calls


def test_solars_kda_layer_is_the_recurrence_with_the_kernel_on(forced):
    """``tests/test_solar_block.py``'s one-layer comparison (chunkwise =
    stepping = the reference's recurrence, beta past 1), unchanged."""
    import test_solar_block as block

    block.test_kda_chunkwise_prefill_is_stepping_is_the_recurrence(200)
    assert forced == [(2, 4 * C, 4, 16)]


def test_solars_segments_and_padding_with_the_kernel_on(forced):
    import test_solar_block as block

    block.test_kda_padding_and_later_segments_leave_the_real_tokens_state()
    assert len(forced) == 8 and set(forced) == {(4, 8, 4, 16), (1, 16, 4, 16),
                                                (1, 24, 4, 16),
                                                (1, 32, 4, 16)}


def test_lings_kda_layer_is_the_recurrence_with_the_kernel_on(forced):
    import test_ling_block as block

    block.test_kda_chunkwise_prefill_is_stepping_is_the_recurrence(65)
    assert len(forced) == 1 and forced[0][1] == 2 * C


def test_the_models_logits_through_the_slots_with_the_kernel_on(forced):
    """The fifth block's whole model against its plain reference
    (prefill into slots, ragged steps, float32 logits inside 1e-4, the
    control outside), unchanged, with every KDA layer's prefill through
    the kernel."""
    import test_solar_block as block

    block.test_prefill_then_ragged_decode_is_the_references_forward(
        "float32", block.F32_TOL, 8, np.max)
    assert forced
