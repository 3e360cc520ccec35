"""``ops/dsa.py`` as the tenth block calls it (``models/glm_next.py``,
GLM-5.3-Flash: 64 heads of 256 + 0 beside values of 256, no rotated
part; 32 index heads over keys POOLED four rows a block; latent rows of
512), on the CPU: the kernels in the Pallas interpreter against their
XLA bodies at those widths, the pooled selection against a stable full
sort of whole blocks with the tail (the reference's), and what a call
without a rotated part hands its kernel.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import dots
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import dsa

REF = manifest.reference(manifest.family("glm5_next"))


@pytest.mark.parametrize("offset, heads, dtype", [
    pytest.param(256, 4, jnp.float32, id="offset-of-whole-blocks"),
    pytest.param(200, 2, jnp.float32, id="offset-200-cuts-the-last-block"),
    pytest.param(0, 4, jnp.float32, id="offset-0-every-causal-key-chosen"),
    pytest.param(256, 1, jnp.float32, id="one-head-a-cell"),
    pytest.param(256, 4, jnp.bfloat16, id="bfloat16-operands"),
    pytest.param(200, 8, jnp.bfloat16, id="bfloat16-eight-heads-offset-200"),
])
def test_the_masked_flash_kernel_without_a_rotated_part(offset, heads, dtype):
    """``dsa_attn`` at 256 + 0 / 256 (a group of 8 heads), 256 rows at
    ``offset`` over 512 keys with 48 chosen a row, in tiles of 128: the
    XLA body's output, from FIVE operands (no q_r, no k_r: a block of no
    width is nothing a ``pallas_call`` can be handed) and two products a
    head (the score tile's one, ``v^T @ p``). Row 7 attends its own
    position alone, row 9 key 3 alone (``tests/test_dsa_ops.py`` says
    what those hold)."""
    h, dn, dv = 8, 256, 256
    scale = dn ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, h, 256, dn), dtype)
    k = jax.random.normal(ks[1], (1, h, 512, dn), dtype)
    v = jax.random.normal(ks[2], (1, h, 512, dv), dtype)
    scores = jax.random.normal(ks[3], (1, 256, 512), jnp.float32)
    at = jnp.arange(256)[:, None] + offset
    valid = (jnp.arange(512)[None, :] <= at)[None]
    chosen = dsa.select(scores, valid, 48)
    chosen = chosen.at[0, 7].set(jnp.arange(512) == 7 + offset)
    chosen = chosen.at[0, 9].set(jnp.arange(512) == 3)
    bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    f32 = jnp.float32
    want = dsa.masked_attention_xla(q.astype(f32), None, k.astype(f32), None,
                                    v.astype(f32), bias, scale)
    kernel = functools.partial(
        dsa.masked_attention, scale=scale, interpret=True, block_q=128,
        block_k=128, heads=heads)
    got = kernel(q, None, k, None, v, bias, jnp.int32(offset))
    assert got.dtype == dtype and got.shape == (1, h, 256, dv)
    text = str(jax.make_jaxpr(kernel)(q, None, k, None, v, bias, offset))
    assert text.count("dot_general") == 2 * heads
    assert "Ref<vmem>{f32[1,128,256]}" not in text  # (nothing is joined)
    err = float(jnp.abs(got.astype(f32) - want).max())
    if dtype == jnp.float32:
        assert err < 1e-5
        np.testing.assert_allclose(got[0, :, 7], v[0, :, 7 + offset],
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, :, 9], v[0, :, 3], atol=1e-5)
    else:
        assert err < 2 ** -8 * float(jnp.abs(want).max()), err
        assert float(jnp.abs(got.astype(f32) - want).mean()) < 1e-3


def test_a_rotated_part_of_no_width_is_what_none_is():
    """The XLA body with ``q_r`` / ``k_r`` None is the body handed parts
    of no width (what ``models/dots.py`` hands it at ``dr`` 0 would be),
    and a kind without a rotated part keeps rows of the latent alone."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 16, 32))
    k = jax.random.normal(ks[1], (1, 2, 24, 32))
    v = jax.random.normal(ks[2], (1, 2, 24, 16))
    bias = jnp.where(jnp.arange(24)[None, :] <= jnp.arange(16)[:, None] + 8,
                     0.0, dsa.NEG).astype(jnp.bfloat16)[None]
    none = dsa.masked_attention_xla(q, None, k, None, v, bias, 0.2)
    empty = dsa.masked_attention_xla(
        q, jnp.zeros((1, 2, 16, 0)), k, jnp.zeros((1, 24, 0)), v, bias, 0.2)
    np.testing.assert_allclose(np.asarray(none), np.asarray(empty),
                               atol=1e-6)
    kind = dots.Kind(4, 32, 512, 256, 0, 256, 0.0, False, False)
    assert kind.row_width == 512 and kind.rotation(jnp.zeros((1, 3))) is None
    assert dots.Kind(4, 32, 512, 192, 64, 256, 1e4, False,
                     False).row_width == 640


@pytest.mark.parametrize("offset", [0, 256, 1024])
def test_the_index_kernel_over_pooled_keys_is_its_xla_body(offset):
    """``dsa_index`` with ``pool`` 4 at the published widths (32 index
    heads of 128): 256 rows at ``offset`` over 512 POOLED keys (2,048
    positions) in tiles of 128: every entry of a WHOLE block (``4 j + 3
    <= t``) is the XLA body's; the tiles wholly past the diagonal in
    POSITIONS (``4 j`` past the q block's last row) are not computed,
    which a kernel that compared key indices with rows would compute
    (offset 0: tile 1 begins at position 512, past row 255)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 32, 128), jnp.float32)
    w = jax.random.normal(ks[1], (1, 256, 32), jnp.float32)
    k = jax.random.normal(ks[2], (1, 512, 128), jnp.float32)
    want = dsa.index_scores_xla(q, w, k)
    got = dsa.index_scores(q, w, k, jnp.int32(offset), interpret=True,
                           block_q=128, block_k=128, pool=4)
    whole = 4 * jnp.arange(512)[None, :] + 3 <= jnp.arange(256)[:, None] \
        + offset
    assert float(jnp.abs(jnp.where(whole[None], got - want, 0)).max()) < 1e-3
    if offset == 0:
        # rows 0 .. 127 against keys 128 .. (positions 512 ..): dead
        assert not np.array_equal(np.asarray(got[0, :128, 128:256]),
                                  np.asarray(want[0, :128, 128:256]))
    one = dsa.index_scores(q, w, k, jnp.int32(offset), interpret=True,
                           block_q=128, block_k=128)
    causal = jnp.arange(512)[None, :] <= jnp.arange(256)[:, None] + offset
    assert float(jnp.abs(jnp.where(causal[None], one - want, 0)).max()) < 1e-3


@pytest.mark.parametrize("rows, start", [(43, 0), (40, 0), (24, 16)])
def test_the_pooled_selection_is_the_stable_sort_of_whole_blocks(rows, start):
    """``dots.pooled_keys`` + ``dsa.select`` over whole blocks +
    ``dots.pooled_bias`` against the reference's stable sort (its
    ``chosen_blocks`` / ``seen_rows``), with ties planted: the same rows
    are read, the tail (``4 ((t + 1) // 4) .. t``) among them, for a
    length that is whole blocks, one that is not, and a segment that
    starts behind position 0."""
    pool, blocks = 4, 3
    total = start + rows
    rng = np.random.RandomState(rows + start)
    k_i = jnp.asarray(rng.randn(1, total, 16).astype(np.float32))
    keys = dots.pooled_keys(k_i, pool)
    assert keys.shape == (1, -(-total // pool), 16)
    np.testing.assert_allclose(
        np.asarray(keys[:, :total // pool]),
        np.asarray(REF.pooled(k_i, pool)), atol=1e-6)
    scores = np.round(rng.randn(1, rows, keys.shape[1]), 1).astype(np.float32)
    at = start + jnp.arange(rows)
    whole = pool * jnp.arange(keys.shape[1])[None, :] + pool - 1 \
        <= at[:, None]
    chosen = dsa.select(jnp.asarray(scores), whole[None], blocks)
    bias, reads = dots.pooled_bias(chosen, at[:, None], pool, total)
    sets = REF.chosen_blocks(jnp.asarray(scores)[..., :total // pool], start,
                             pool, blocks)
    want = REF.seen_rows(sets, start, pool, total)
    np.testing.assert_array_equal(np.asarray(reads), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(bias == 0), np.asarray(want))
    t = rows - 1  # the last row reads its open block whole
    tail = np.arange(total) >= (start + t + 1) // pool * pool
    assert np.asarray(reads)[0, t][tail].all()
    assert int(reads[0, t].sum()) == pool * min(
        blocks, (start + t + 1) // pool) + (start + t + 1) % pool


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_masked_decode_kernel_over_rows_without_a_rotated_key(dtype):
    """``dsa_decode_attn`` over latent rows of 512 (key and value the
    whole row: ``dv`` = the row's width), 8 heads, three slots of
    lengths 300 / 0 / 77 in a stack of two layers, blocks chosen by
    fours: the XLA body's output; the inactive slot reads zeros."""
    b, h, w, s = 3, 8, 512, 384
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, h, w), dtype)
    rows = jax.random.normal(ks[1], (2, b, s, w), dtype)
    lengths = jnp.array([300, 0, 77], jnp.int32)
    chosen = jax.random.bernoulli(ks[2], 0.3, (b, s // 4))
    bias, _ = dots.pooled_bias(chosen & (
        4 * jnp.arange(s // 4)[None, :] + 3 < lengths[:, None]),
        (lengths - 1)[:, None], 4, s)
    want = dsa.attend_latent_masked(
        q.astype(jnp.float32), rows[1].astype(jnp.float32), lengths, bias, w,
        w ** -0.5)
    got = dsa.decode_attention_masked(
        q, rows, 1, lengths, bias, dv=w, scale=w ** -0.5, block=128,
        plan=da.visits(lengths, s, 128), interpret=True)
    assert got.shape == (b, h, w) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < tol * max(1.0, float(jnp.abs(want).max()))
    assert not np.asarray(got[1]).any()
