"""``ops/dsa.py``'s four kernels at the NINTH block's widths
(``models/glm_dsa.py``: heads of 192 + 64 beside values of 256, 32 index
heads of 128, 64 heads over latent rows of 640) in the Pallas
interpreter against their XLA bodies, on the CPU; the programs' text for
what must not be in it; the reference in blocks of rows against itself
in one, and its selection against ``dsa.select``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import glm_dsa
from ray_tpu.ops import dsa

FAM = manifest.family("glm_moe_dsa")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
TOPK = M["index_topk"]


def test_the_index_kernel_at_32_heads_is_its_xla_body():
    """``dsa_index`` at the published 32 index heads of 128: 256 rows at
    offset 128 over 512 keys in tiles of 128: every causal entry is the
    XLA body's."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 32, 128), jnp.float32)
    w = jax.random.normal(ks[1], (1, 256, 32), jnp.float32)
    k = jax.random.normal(ks[2], (1, 512, 128), jnp.float32)
    want = dsa.index_scores_xla(q, w, k)
    got = dsa.index_scores(q, w, k, jnp.int32(128), interpret=True,
                           block_q=128, block_k=128)
    causal = jnp.arange(512)[None, :] <= jnp.arange(256)[:, None] + 128
    assert float(jnp.abs(jnp.where(causal[None], got - want, 0)).max()) < 1e-3


@pytest.mark.parametrize("offset, heads, dtype", [
    pytest.param(256, 4, jnp.float32, id="four-heads-a-cell"),
    pytest.param(200, 2, jnp.float32, id="offset-200-cuts-the-last-block"),
    pytest.param(0, 4, jnp.float32, id="offset-0-every-causal-key-chosen"),
    pytest.param(256, 4, jnp.bfloat16, id="bfloat16-operands"),
])
def test_the_masked_flash_kernel_at_192_64_256_is_its_xla_body(
        offset, heads, dtype):
    """``dsa_attn`` at this block's head (the unrotated part 192, one
    and a half lane tiles; the rotated 64; values 256, two tiles), 8
    heads (a group), 256 rows at ``offset`` over 512 keys with 48 chosen
    a row, tiles of 128: the XLA body's output. Row 7 attends its own
    position alone, row 9 key 3 alone. bfloat16 operands are held to
    the float32 body of the same numbers as ``test_dsa_ops.py`` holds
    them."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q_n = jax.random.normal(ks[0], (1, 8, 256, 192), dtype)
    q_r = jax.random.normal(ks[1], (1, 8, 256, 64), dtype)
    k_n = jax.random.normal(ks[2], (1, 8, 512, 192), dtype)
    k_r = jax.random.normal(ks[3], (1, 512, 64), dtype)
    v = jax.random.normal(ks[4], (1, 8, 512, 256), dtype)
    scores = jax.random.normal(ks[5], (1, 256, 512), jnp.float32)
    at = jnp.arange(256)[:, None] + offset
    valid = (jnp.arange(512)[None, :] <= at)[None]
    chosen = dsa.select(scores, valid, 48)
    chosen = chosen.at[0, 7].set(jnp.arange(512) == 7 + offset)
    chosen = chosen.at[0, 9].set(jnp.arange(512) == 3)
    bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    args = (q_n, q_r, k_n, k_r, v)
    want = dsa.masked_attention_xla(
        *(a.astype(jnp.float32) for a in args), bias, 256 ** -0.5)
    got = dsa.masked_attention(*args, bias, jnp.int32(offset),
                               scale=256 ** -0.5, interpret=True,
                               block_q=128, block_k=128, heads=heads)
    assert got.dtype == dtype and got.shape == (1, 8, 256, 256)
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    if dtype == jnp.float32:
        assert err < 1e-5
        np.testing.assert_allclose(got[0, :, 7], v[0, :, 7 + offset],
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, :, 9], v[0, :, 3], atol=1e-5)
    else:
        assert err < 2 ** -8 * float(jnp.abs(want).max()), err
        assert float(jnp.abs(got.astype(jnp.float32) - want).mean()) < 1e-3


def test_the_decode_kernel_at_64_heads_is_its_xla_body_for_every_layer():
    """``dsa_decode_attn`` at this block's 64 heads over rows of 640
    (values the first 512), eight slots of 200 rows in blocks of 64 at
    ragged lengths (one 0), 24 rows chosen of each by the selection's
    kernel (a block of eight rows), ONE bias read by the
    calls of two layers of the stack (what a shared layer does): each
    is the XLA body's output over its own layer's rows; the inactive
    slot zeros."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (8, 64, 640), jnp.float32)
    rows = jax.random.normal(ks[1], (3, 8, 200, 640), jnp.float32)
    lengths = jnp.array([200, 0, 130, 1, 64, 200, 77, 199], jnp.int32)
    valid = jnp.arange(200)[None, :] < lengths[:, None]
    scores = jax.random.normal(ks[2], (8, 200), jnp.float32)
    bias = jnp.where(dsa.select(scores, valid, 24, interpret=True), 0.0,
                     dsa.NEG).astype(jnp.bfloat16)
    outs = []
    for layer in (0, 2):
        want = dsa.attend_latent_masked(q, rows[layer], lengths, bias, 512,
                                        256 ** -0.5)
        got = dsa.decode_attention_masked(
            q, rows, layer, lengths, bias, dv=512, scale=256 ** -0.5,
            block=64, interpret=True)
        assert float(jnp.abs(got - want).max()) < 1e-5
        assert not np.asarray(got[1]).any()
        outs.append(got)
    assert float(jnp.abs(outs[0] - outs[1]).max()) > 1e-2


def test_the_references_sets_are_selects():
    """The reference's sets as indices (a stable full argsort) against
    ``dsa.select``'s mask, scores with ties all over, 24 rows at offset
    40 over 64 keys: the same members, row for row, and an empty place
    names no key."""
    rng = np.random.RandomState(3)
    scores = rng.randn(1, 24, 64).astype(np.float32)
    scores[:, :, ::3] = np.round(scores[:, :, ::3], 1)
    valid = (np.arange(64)[None, :] <= np.arange(24)[:, None] + 40)[None]
    for k in (1, 8, 50, 100):
        sets = REF.selected(jnp.asarray(scores), 40, k)
        assert sets.shape == (1, 24, min(k, 64))
        np.testing.assert_array_equal(
            np.asarray(REF.members(sets, 64)),
            np.asarray(dsa.select(jnp.asarray(scores), jnp.asarray(valid),
                                  k)))


def test_the_selection_is_exact_and_made_twice_in_both_programs():
    """The programs' text at the tiny five-layer pattern: no approximate
    top-k and no top-k of ``index_topk`` at all (the router's own is of
    4), float32 index scores of every index head in the INDEXER layers
    alone (two sites for five layers), the mask handed to the attention
    as a bias a key."""
    cfg = FAM.build(M, max_seq_len=64, remat=False).cfg
    params = jax.eval_shape(lambda: glm_dsa.init_params(
        cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: glm_dsa.SLOTS.init_state(cfg, 2, 64))
    rest = {k: v for k, v in state.items() if k != "pos"}
    vec = lambda dt: jax.ShapeDtypeStruct((2,), dt)  # noqa: E731
    step = str(jax.make_jaxpr(functools.partial(
        glm_dsa.SLOTS.step, cfg))(params, None, vec(jnp.int32), rest,
                                  vec(jnp.int32), vec(jnp.bool_)))
    pre = str(jax.make_jaxpr(lambda p, t: glm_dsa.prefill(
        p, t, jnp.array([32], jnp.int32), cfg))(
            params, jax.ShapeDtypeStruct((1, 32), jnp.int32)))
    for text in (step, pre):
        assert "approx" not in text
        assert f"k={TOPK}]" not in text and " top_k[" in text  # (the router's)
    hi = cfg.index_heads
    assert step.count(f":f32[2,1,{hi},64] = dot_general") == cfg.index_layers
    assert "bf16[2,64]" in step  # a bias a key from the mask, no gather
    assert pre.count(f":f32[1,32,{hi},32] = dot_general") == cfg.index_layers


def test_blocks_of_rows_give_the_whole_sequences_forward(monkeypatch):
    """The reference in blocks of 16 rows and 8 query rows is the
    reference in one block (tiny widths, three layers as indexer,
    shared, shared; 40 positions: the handed-on sets are cut by query
    block, selection and both MLPs cross block boundaries)."""
    fam, ref = FAM, manifest.reference(FAM)  # (a module of its own)
    m = {**M, "n_layers": 3, "indexer_layers": [1, 0, 0]}
    params = fam.build(m, max_seq_len=64, remat=False).init_params(
        jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 256, (1, 40)))
    whole = ref.forward(params, toks, m)
    monkeypatch.setattr(ref, "ROWS", 16)
    monkeypatch.setattr(ref, "QUERY_ROWS", 8)
    blocks = ref.forward(params, toks, m)
    assert float(jnp.abs(blocks - whole).max()) < 1e-5
    assert float(jnp.abs(ref.forward(params, toks, m, last=5)
                         - whole[:, -5:]).max()) < 1e-5
    with pytest.raises(ValueError, match="no earlier layer"):
        ref.forward(params, toks, {**m, "indexer_layers": [0, 1, 0]})
