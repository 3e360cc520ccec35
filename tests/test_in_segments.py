"""``moe.in_segments``: a long prompt's tokenwise work over the segments
of its bucket, the segments behind the last one that holds a real row
left out (``live``). The body here counts its runs in the carry and
remembers the last segment it saw, so that a dead segment shows: not
run, the carry as the last live segment left it, its rows of the outputs
zeros; ``live=None`` runs the body on every segment.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe

SEG, T = 16, 128


def _body(carry, xs):
    runs, last, total = carry
    start, (x, y) = xs
    assert x.shape[:2] == (2, SEG) and y.shape == (2, SEG)
    return (runs + 1, start, total + x.sum((1, 2))), \
        {"twice": 2 * x + y[..., None], "at": start + jnp.zeros_like(y)}


def _run(live):
    x = jnp.arange(2 * T * 3, dtype=jnp.float32).reshape(2, T, 3) + 1
    y = jnp.arange(2 * T, dtype=jnp.int32).reshape(2, T)
    carry = (jnp.int32(0), jnp.int32(-1), jnp.zeros((2,), jnp.float32))
    if live is None:
        out = jax.jit(lambda: moe.in_segments(_body, carry, (x, y), SEG))()
    else:  # (traced, as the engine's prefill program has it)
        out = jax.jit(lambda n: moe.in_segments(
            _body, carry, (x, y), SEG, n))(jnp.int32(live))
    return x, y, out


@pytest.mark.parametrize("live", [1, SEG, SEG + 1, 70, T - SEG, T - 1, T])
def test_body_runs_on_the_segments_that_begin_under_live(live):
    x, y, ((runs, last, total), outs) = _run(live)
    n = -(-live // SEG)
    assert int(runs) == n and int(last) == (n - 1) * SEG
    rows = n * SEG
    np.testing.assert_array_equal(total, x[:, :rows].sum((1, 2)))
    np.testing.assert_array_equal(outs["twice"][:, :rows],
                                  2 * x[:, :rows] + y[:, :rows, None])
    np.testing.assert_array_equal(
        outs["at"][:, :rows], np.broadcast_to(np.arange(rows) // SEG * SEG,
                                              (2, rows)))
    # a dead segment's rows of the outputs: zeros, in the outputs' types
    assert outs["twice"].shape == (2, T, 3) and outs["at"].dtype == jnp.int32
    assert not outs["twice"][:, rows:].any() and not outs["at"][:, rows:].any()


def test_live_none_is_the_scan_over_every_segment_and_equals_live_t():
    _, _, scanned = _run(None)
    _, _, looped = _run(T)
    assert int(scanned[0][0]) == T // SEG
    for a, b in zip(jax.tree_util.tree_leaves(scanned),
                    jax.tree_util.tree_leaves(looped)):
        np.testing.assert_array_equal(a, b)


def test_live_past_the_bucket_runs_the_buckets_segments_once_each():
    _, _, ((runs, last, _), _) = _run(T + 5 * SEG)
    assert int(runs) == T // SEG and int(last) == T - SEG


def test_one_segment_is_one_plain_call_whatever_live_says():
    x = jnp.ones((2, SEG, 3))
    y = jnp.ones((2, SEG), jnp.int32)
    carry = (jnp.int32(0), jnp.int32(-1), jnp.zeros((2,), jnp.float32))
    for live in (None, jnp.int32(3)):
        (runs, last, _), outs = moe.in_segments(_body, carry, (x, y), SEG,
                                                live)
        assert int(runs) == 1 and int(last) == 0
        assert outs["twice"].shape == (2, SEG, 3)


def test_both_forms_differentiate_and_a_dead_segment_has_no_gradient():
    def loss(w, live):
        def body(c, xs):
            return c + (xs[1] * w).sum(), xs[1] * w
        c, out = moe.in_segments(body, jnp.float32(0),
                                 jnp.ones((1, T, 2)), SEG, live)
        return c + out.sum()

    grad = jax.jit(jax.grad(loss))
    assert float(jax.grad(loss)(jnp.float32(2.0), None)) == 4 * T
    assert float(grad(jnp.float32(2.0), jnp.int32(T))) == 4 * T
    # 40 rows live: three segments of 16, 2 x 2 x 48
    assert float(grad(jnp.float32(2.0), jnp.int32(40))) == 4 * 3 * SEG
