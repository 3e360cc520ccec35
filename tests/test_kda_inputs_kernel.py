"""The kernel that makes a KDA layer's q, k, v and log decay in one pass
(``ray_tpu/ops/kda_inputs.py``) in the Pallas interpreter on the CPU
against the XLA body it replaces on a TPU (``kda_inputs_xla``: the
blocks' old lines): both forms of the decay, rows before the segment
that are not zeros, several row blocks, padding rows, 32 and 64 heads,
the rows a slot keeps, the derivative, and the shapes that take the
body. Never a timing."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda_inputs as ki

ROWS = 16  # a row block of the tests (a bf16 tile; a cell's is 256)
LING = -5.0  # ``LingConfig.kda_lower_bound``; None: Solar-Open2's form


def _inputs(seed, b, t, h, dk=128, taps=4, before=1.0):
    """A layer-segment's arrays as the blocks hand them over: the
    projection's rows and the rows before them in bf16, taps around
    K^-1/2, a pre-activation wide enough for both tails of the softplus,
    ``a_log`` as ``init_params`` draws it."""
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    width = 3 * h * dk
    proj = jax.random.normal(key[0], (b, t, width)).astype(jnp.bfloat16)
    conv_rows = before * jax.random.normal(
        key[1], (b, taps - 1, width)).astype(jnp.bfloat16)
    conv = (taps ** -0.5 * jax.random.normal(
        key[2], (taps, width))).astype(jnp.bfloat16)
    f = 6.0 * jax.random.normal(key[3], (b, t, h * dk))
    a_log = jnp.log(jax.random.uniform(key[4], (h,), jnp.float32, 0.5, 4.0))
    return proj, conv_rows, conv, f, a_log


@functools.lru_cache(maxsize=None)
def _jitted(form, **kw):
    return jax.jit(functools.partial(form, **kw))


def _kernel(*args, real_rows=None, **kw):
    return _jitted(ki.kda_inputs, interpret=True, rows=ROWS, **kw)(
        *args, real_rows=real_rows)


def _body(*args, real_rows=None, **kw):
    return _jitted(ki.kda_inputs_xla, **kw)(*args, real_rows=real_rows)


def _same(got, want):
    """q, k, v, g of the kernel beside the body's: the same float32
    arithmetic in the same order, so equal to a rounding of each."""
    for name, a, b in zip("qkvg", got, want):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7, err_msg=name)


@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("lower_bound", [None, LING])
def test_the_kernel_makes_the_bodys_q_k_v_and_g(lower_bound, heads):
    """Both decay forms at both blocks' head counts: four row blocks,
    rows before the segment that are not zeros, a stream whose padding
    begins inside a block and one whose rows are all real."""
    args = _inputs(heads, 2, 4 * ROWS, heads)
    real = jnp.array([2 * ROWS + 5, 4 * ROWS], jnp.int32)
    got = _kernel(*args, real_rows=real, lower_bound=lower_bound)
    want = _body(*args, real_rows=real, lower_bound=lower_bound)
    _same(got, want)
    g = np.asarray(got[3])
    assert not g[0, 2 * ROWS + 5:].any()
    assert (g[0, :2 * ROWS + 5] < 0).mean() > 0.9 and (g[1] < 0).mean() > 0.9
    assert g.min() < (-20 if lower_bound is None else -4.9)
    if lower_bound is not None:
        assert g.min() >= lower_bound


@pytest.mark.parametrize("block_heads", [1, 2, 4])
def test_a_row_block_starts_from_the_rows_before_it(block_heads):
    """The convolution's three rows before a block's first come from the
    block before it, and before the first from ``conv_rows``: large
    rows there move the first three rows' q, k and v and no others, and
    one call over eight blocks equals two calls over four, the second
    handed the first's kept rows (the prefill's carry between
    segments)."""
    proj, conv_rows, conv, f, a_log = _inputs(3, 1, 8 * ROWS, 4, before=8.0)
    kw = dict(lower_bound=None, heads=block_heads)
    whole = _kernel(proj, conv_rows, conv, f, a_log, **kw)
    _same(whole, _body(proj, conv_rows, conv, f, a_log, lower_bound=None))
    quiet = _kernel(proj, 0 * conv_rows, conv, f, a_log, **kw)
    for a, b in zip(whole[:3], quiet[:3]):
        assert np.abs(np.asarray(a - b))[:, :3].max() > 1e-3
        np.testing.assert_array_equal(a[:, 3:], b[:, 3:])
    half = 4 * ROWS
    kept = ki.kept_rows(conv_rows, proj[:, :half],
                        jnp.array([half], jnp.int32))
    np.testing.assert_array_equal(kept, proj[:, half - 3:half])
    first = _kernel(proj[:, :half], conv_rows, conv, f[:, :half], a_log,
                    **kw)
    second = _kernel(proj[:, half:], kept, conv, f[:, half:], a_log, **kw)
    for a, b, c in zip(whole, first, second):
        np.testing.assert_array_equal(a, jnp.concatenate([b, c], axis=1))


@pytest.mark.parametrize("first", [0, 1, 2, 3, 7, 4 * ROWS])
def test_the_rows_a_slot_keeps_are_the_concatenations(first):
    """``kept_rows`` against the lines it replaces: rows ``first`` ..
    ``first + 2`` of the rows before and the product laid end to end,
    whether they lie in the one, across both or in the other."""
    proj, conv_rows, *_ = _inputs(5, 2, 4 * ROWS, 1)
    at = jnp.array([first, 4 * ROWS - first], jnp.int32)
    u = jnp.concatenate([conv_rows, proj], axis=1)
    want = jnp.take_along_axis(
        u, (at[:, None] + jnp.arange(3)[None, :])[..., None], axis=1)
    np.testing.assert_array_equal(ki.kept_rows(conv_rows, proj, at), want)


@pytest.mark.parametrize("lower_bound", [None, LING])
def test_a_differentiated_call_takes_the_bodys_derivative(lower_bound):
    args = _inputs(7, 1, 2 * ROWS, 2)
    real = jnp.array([ROWS + 3], jnp.int32)
    weights = [jax.random.normal(jax.random.PRNGKey(i), (1, 2 * ROWS, 2, 128))
               for i in range(4)]

    def loss(form, proj, conv_rows, conv, f, a_log):
        out = form(proj.astype(jnp.bfloat16), conv_rows, conv, f, a_log,
                   lower_bound=lower_bound, real_rows=real)
        return sum(jnp.sum(w * a) for w, a in zip(weights, out))

    kernel = functools.partial(ki.kda_inputs, interpret=True, rows=ROWS)
    proj32 = args[0].astype(jnp.float32)
    got = jax.grad(functools.partial(loss, kernel), argnums=(0, 3, 4))(
        proj32, *args[1:])
    want = jax.grad(functools.partial(loss, ki.kda_inputs_xla),
                    argnums=(0, 3, 4))(proj32, *args[1:])
    for a, b in zip(got, want):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("case", ["narrow_head", "one_row", "ragged_rows",
                                  "off_the_tpu"])
def test_what_the_kernel_does_not_fit_takes_the_body(case, monkeypatch):
    """A head that is not whole lanes, a decode step's one row and a row
    count that is not whole blocks run the XLA body even where the
    kernel is asked for by name (the compile tests' patch), and on the
    CPU nothing is asked for: no ``pallas_call`` is traced."""
    dk, t, kw = {"narrow_head": (64, 2 * ROWS, {"use_kernel": True}),
                 "one_row": (128, 1, {"use_kernel": True}),
                 "ragged_rows": (128, ROWS + 8, {"interpret": True}),
                 "off_the_tpu": (128, 2 * ROWS, {})}[case]
    args = _inputs(9, 2, t, 2, dk=dk)
    called = []
    monkeypatch.setattr(ki.pl, "pallas_call", lambda *a, **k: called.append(k))
    got = ki.kda_inputs(*args, lower_bound=LING, rows=ROWS, **kw)
    assert not called
    for a, b in zip(got, ki.kda_inputs_xla(*args, lower_bound=LING)):
        np.testing.assert_array_equal(a, b)
