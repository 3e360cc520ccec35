"""The SSD recurrence with B and C in GROUPS of heads (``ops/ssd_step.py``,
``ops/ssd_chunk.py``; ``models/nemotron.py``: eight groups of eight heads
of 64 with a state of 128), beside the one-group cases of
``tests/test_granite_block.py``: the step kernel in the Pallas
interpreter against the XLA body bit for bit and against the reference's
recurrence a token at a time (``benchmark/families/nemotron_h.
reference.py``), an inactive slot bit for bit; the chunked scan against
the same recurrence, a chunk of padding handing the state on; a group
that is not whole lane rows refused when the program is traced; and one
group giving the numbers the parent's one-group lines gave."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.ops import ssd_chunk as sc
from ray_tpu.ops import ssd_step as ss

REF = manifest.reference(manifest.family("nemotron_h"))
_HI = jax.lax.Precision.HIGHEST


@pytest.mark.parametrize("heads, p, groups, rows", [
    (64, 64, 8, None),   # the cell's: 32 lane rows, a block four groups
    (64, 64, 8, 8),      # a block two groups of four lane rows
    (64, 64, 2, 8),      # a group two blocks of eight lane rows
    (64, 64, 1, None),   # one group: Granite's call at 64 heads
    (16, 128, 4, None),  # a head a lane row, a group four lane rows
    (8, 8, 2, None)])    # the tests' size (no kernel on a chip)
def test_the_grouped_step_kernel_is_the_xla_body_bit_for_bit(
        heads, p, groups, rows):
    """``ssd_step`` in the interpreter: a lane row takes the B and C
    columns of ITS group (a block of 16 lane rows is four groups of four
    at the cell's size), the state and the output are the XLA body's
    (``ssd_recurrence`` and a ``where``) bit for bit and the reference's
    token-by-token recurrence to rounding; the inactive slot's ``H``
    comes back bit for bit."""
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    b, n = 3, 128 if p >= 64 else 16
    g = ss.lane_heads(heads, p)
    plain = jax.random.normal(key[0], (b, heads, p, n))
    h = ss.pack(plain)
    assert h.shape == (b, heads // g, n, g * p)
    assert ss.group_rows(heads // g, groups) == heads // g // groups
    dtx = jax.random.normal(key[1], (b, heads, p)) * 0.1
    da = jax.random.uniform(key[2], (b, heads)) ** 4
    bb, cc = (jax.random.normal(k, (b, groups, n)) for k in key[3:5])
    active = jnp.array([True, False, True])
    h_k, y_k = jax.jit(lambda *a: ss.ssd_step(
        *a, interpret=True, rows=rows))(h, dtx, da, bb, cc, active)
    h_x, y_x = jax.jit(lambda *a: ss.ssd_step(*a, use_kernel=False))(
        h, dtx, da, bb, cc, active)
    np.testing.assert_array_equal(h_k, h_x)
    np.testing.assert_allclose(y_k, y_x, atol=1e-5)
    np.testing.assert_array_equal(h_k[1], h[1])
    # one token of the reference's recurrence: x dt = dtx, e^(dt a) = da
    y_ref, h_ref = REF.ssm_recurrence(
        dtx[:, None], jnp.ones((b, 1, heads)), jnp.zeros((heads,)),
        bb[:, None], cc[:, None], plain * da[..., None, None])
    on = np.asarray(active)
    np.testing.assert_allclose(ss.unpack(h_k, p)[on], h_ref[on], atol=1e-5)
    np.testing.assert_allclose(y_k, y_ref[:, 0], atol=1e-4)
    if groups > 1:  # (another group's columns are another result)
        y_one = ss.ssd_step(h, dtx, da, bb[:, :1], cc[:, :1], active,
                            use_kernel=False)[1]
        assert float(jnp.abs(y_one - y_x).max()) > 0.1


def test_a_group_that_is_not_whole_lane_rows_is_refused_at_trace_time():
    """64 heads of 64 are 32 lane rows: three groups do not divide them,
    sixty-four would split a lane row's two heads, and a block of 12
    lane rows is neither whole groups of 8 nor a part of one."""
    h = jnp.zeros((2, 32, 128, 128))
    dtx, da = jnp.zeros((2, 64, 64)), jnp.ones((2, 64))
    active = jnp.ones((2,), bool)
    for groups, kw in ((3, {}), (64, {}), (3, {"use_kernel": False})):
        bc = jnp.zeros((2, groups, 128))
        with pytest.raises(ValueError, match="lane rows"):
            jax.eval_shape(lambda: ss.ssd_step(
                h, dtx, da, bc, bc, active, **{"interpret": True, **kw}))
    bc = jnp.zeros((2, 4, 128))
    with pytest.raises(ValueError, match="neither whole groups"):
        ss._ssd_step(h, dtx.reshape(2, 32, 128), dtx.reshape(2, 32, 128),
                     jnp.zeros((2, 4, 128, 2)), active.astype(jnp.int32),
                     rb=12, interpret=True)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_grouped_chunked_scan_is_the_recurrence(groups):
    """``ssd_chunked`` with ``groups`` groups over four chunks of 8 rows
    from a carried state, decays that underflow inside a chunk among
    them, is the reference's recurrence a token at a time (a head reads
    ITS group's ``C_g B_g^T``); a chunk of padding (``dt`` 0) hands the
    state on bit for bit."""
    key = jax.random.split(jax.random.PRNGKey(3), 7)
    b, t, h, p, n = 2, 32, 4, 8, 16
    x = jax.random.normal(key[0], (b, t, h, p))
    a = -jnp.array([1.0, 4.0, 16.0, 16.0])
    dt = 1.25 + 0.3 * jax.random.uniform(key[1], (b, t, h))
    dt = jnp.where(jax.random.uniform(key[2], (b, t, h)) < 0.3, 1e-3, dt)
    bb, cc = (jax.random.normal(k, (b, t, groups, n)) for k in key[3:5])
    h0 = jax.random.normal(key[5], (b, h, p, n))
    assert float(jnp.cumsum(dt * a, 1)[:, 7].min()) < -100
    # (one program a call: an eager call dispatches some forty)
    chunked = jax.jit(sc.ssd_chunked, static_argnames="chunk")
    y, last = chunked(x, dt, a, bb, cc, h0, chunk=8)
    y_ref, last_ref = REF.ssm_recurrence(x, dt, a, bb, cc, h0)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, y_ref, atol=3e-5, rtol=2e-5)
    np.testing.assert_allclose(last, last_ref, atol=3e-5, rtol=2e-5)
    _, kept = chunked(x[:, :8], jnp.zeros((b, 8, h)), a, bb[:, :8],
                      cc[:, :8], h0, chunk=8)
    np.testing.assert_array_equal(kept, h0)
    if groups > 1:
        y_one, _ = chunked(x, dt, a, bb[:, :, :1], cc[:, :, :1], h0,
                           chunk=8)
        assert float(jnp.abs(y_one - y).max()) > 0.1


def _ssd_chunked_before_pr_70(x, dt, a, b, c, h0, *, chunk: int):
    """``ops/ssd_chunk.py: ssd_chunked`` as PR 69 had it, letter for
    letter: b, c [B, T, N], one ``C B^T`` for every head."""
    import functools

    bsz, t, nh, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=jnp.float32)
    cs = jnp.cumsum((dt * a).reshape(bsz, nc, chunk, nh), axis=2)
    xdt = (x * dt[..., None]).reshape(bsz, nc, chunk, nh, p)
    bq, cq = b.reshape(bsz, nc, chunk, n), c.reshape(bsz, nc, chunk, n)
    by_head = jnp.moveaxis(cs, 3, 2)
    seen = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    decay = jnp.exp(jnp.where(
        seen, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    scores = mm("bctn,bcsn->bcts", cq, bq)[:, :, None] * decay
    y = mm("bchts,bcshp->bcthp", scores, xdt)
    to_end = jnp.exp(cs[:, :, -1:] - cs)
    added = mm("bcshp,bcsn->bchpn", xdt * to_end[..., None], bq)
    whole = jnp.exp(cs[:, :, -1])

    def over(h, chunk_):
        add, keep = chunk_
        return h * keep[..., None, None] + add, h

    last, starts = jax.lax.scan(
        over, h0, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    y = y + mm("bctn,bchpn->bcthp", cq, jnp.moveaxis(starts, 0, 1)) \
        * jnp.exp(cs)[..., None]
    return y.reshape(bsz, t, nh, p), last


def _ssd_recurrence_before_pr_70(h, x, da, b, c):
    """``ops/ssd_step.py: ssd_recurrence`` as PR 69 had it: b, c [B, N]."""
    h = h * da[:, :, None, :] + b[:, None, :, None] * x[:, :, None, :]
    return h, jnp.sum(h * c[:, None, :, None], axis=2)


def test_one_group_gives_the_parents_numbers():
    """Granite's call (one group under the group axis): the step's body
    gives the parent's state and output bit for bit; the chunked scan
    the parent's to float32 rounding (the same products under another
    einsum's labels: 2e-6 on values of 10 on this CPU)."""
    key = jax.random.split(jax.random.PRNGKey(9), 8)
    b, t, h, p, n = 2, 32, 8, 8, 16
    x = jax.random.normal(key[0], (b, t, h, p))
    a = -jnp.exp(jax.random.normal(key[1], (h,)))
    dt = jax.nn.softplus(jax.random.normal(key[2], (b, t, h)))
    bb, cc = (jax.random.normal(k, (b, t, n)) for k in key[3:5])
    h0 = jax.random.normal(key[5], (b, h, p, n))
    got = sc.ssd_chunked(x, dt, a, bb[:, :, None], cc[:, :, None], h0,
                         chunk=8)
    want = _ssd_chunked_before_pr_70(x, dt, a, bb, cc, h0, chunk=8)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)
    state = ss.pack(h0)
    xs = jax.random.normal(key[6], state.shape[:2] + state.shape[3:])
    da = jax.random.uniform(key[7], xs.shape)
    got = ss.ssd_recurrence(state, xs, da, bb[:, 0, None], cc[:, 0, None])
    want = _ssd_recurrence_before_pr_70(state, xs, da, bb[:, 0], cc[:, 0])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
