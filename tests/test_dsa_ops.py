"""Learned sparse attention's operations (``ops/dsa.py``) on the CPU, at
the widths of both blocks that call them (``dots3``: ``models/dots.py``'s
full layers, 128 heads of 128 + 64 beside values of 128, 64 index heads;
``glm``: ``models/glm_dsa.py``, 64 heads of 192 + 64 beside values of
256, 32 index heads; both over latent rows of 640): the four kernels in
the Pallas interpreter against their XLA bodies, the exact selection
against a stable full sort (the references'), the mask's positions, and
the programs' text for what must not be in it; and each reference in
blocks of rows against itself in one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sparse import sparse_block
from benchmark import manifest
from ray_tpu.ops import dsa


# sparse_layers: how many layers of the tiny model own an indexer;
# in_blocks: the fields of the reference's blocks-of-rows case; refused:
# what the reference refuses there
BLOCKS = {
    "dots3": sparse_block(
        "dots", sparse_layers=lambda cfg: cfg.full_layers,
        in_blocks={"n_layers": 2, "layer_pattern": [0, 1]}, refused=None),
    "glm": sparse_block(
        "glm_dsa", sparse_layers=lambda cfg: cfg.index_layers,
        in_blocks={"n_layers": 3, "indexer_layers": [1, 0, 0]},
        refused=({"indexer_layers": [0, 1, 0]}, "no earlier layer")),
}


@pytest.mark.parametrize("heads, offset, tol", [
    pytest.param(4, 256, 1e-4, id="dots3-4-of-64-heads"),
    pytest.param(32, 128, 1e-3, id="glm-32-heads")])
def test_the_index_kernel_in_the_interpreter_is_its_xla_body(heads, offset,
                                                             tol):
    """``dsa_index`` at the published index head width of 128 (4 heads
    for dots3's 64; GLM-5.2's 32): 256 rows at ``offset`` over 512 keys
    in tiles of 128: every causal entry is the XLA body's."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, heads, 128), jnp.float32)
    w = jax.random.normal(ks[1], (1, 256, heads), jnp.float32)
    k = jax.random.normal(ks[2], (1, 512, 128), jnp.float32)
    want = dsa.index_scores_xla(q, w, k)
    got = dsa.index_scores(q, w, k, jnp.int32(offset), interpret=True,
                           block_q=128, block_k=128)
    causal = jnp.arange(512)[None, :] <= jnp.arange(256)[:, None] + offset
    assert float(jnp.abs(jnp.where(causal[None], got - want, 0)).max()) < tol
    with pytest.raises(ValueError, match="multiples"):
        dsa.index_scores(q[:, :200], w[:, :200], k, 0, interpret=True,
                         block_q=128)


# (heads of a call, the unrotated part, the rotated part, values): dots3's
# full layer, 4 of its heads; GLM-5.2's, a group of 8 (192: one and a half
# lane tiles; 256: two); and widths no block has, which the rule must
# judge by themselves: both parts whole tiles, and both half a tile
_HEAD = {"dots3": (4, 128, 64, 128), "glm": (8, 192, 64, 256),
         "whole": (2, 128, 128, 128), "halves": (2, 64, 64, 128)}


@pytest.mark.parametrize("dn, dr, one", [
    pytest.param(192, 64, True, id="192+64-one-product"),
    pytest.param(128, 64, False, id="128+64-two-products"),
    pytest.param(128, 128, False, id="128+128-two-products"),
    pytest.param(64, 64, True, id="64+64-one-product"),
    pytest.param(320, 64, True, id="320+64-one-product"),
    pytest.param(256, 64, False, id="256+64-two-products")])
def test_the_rule_names_the_body_a_pair_of_widths_takes(dn, dr, one):
    """``dsa._one_product``: a head's score tile is one product where
    the two parts fill fewer 128-lane tiles together than apart (GLM-5.2
    192 + 64: two for three), two where joining them spares the matrix
    unit nothing (dots3 128 + 64, 128 + 128): the widths alone decide,
    and ``masked_attention``'s kernel holds the join's scratch exactly
    there."""
    assert dsa._one_product(dn, dr) is one
    text = str(jax.make_jaxpr(functools.partial(
        dsa.masked_attention, scale=1.0, interpret=True, block_q=128,
        block_k=128, heads=1))(
        jnp.zeros((1, 1, 128, dn)), jnp.zeros((1, 1, 128, dr)),
        jnp.zeros((1, 1, 128, dn)), jnp.zeros((1, 128, dr)),
        jnp.zeros((1, 1, 128, 256)), jnp.zeros((1, 128, 128), jnp.bfloat16),
        0))
    # the score tile's product(s) and ``v^T @ p``
    assert text.count("dot_general") == (2 if one else 3)
    assert (f"Ref<vmem>{{f32[1,128,{dn + dr}]}}" in text) is one, text


@pytest.mark.parametrize("widths, offset, heads, dtype", [
    pytest.param("dots3", 256, 2, jnp.float32,
                 id="dots3-offset-of-whole-blocks"),
    pytest.param("dots3", 200, 2, jnp.float32,
                 id="dots3-offset-200-cuts-the-last-block"),
    pytest.param("dots3", 0, 2, jnp.float32,
                 id="dots3-offset-0-every-causal-key-chosen"),
    pytest.param("dots3", 256, 1, jnp.float32, id="dots3-one-head-a-cell"),
    pytest.param("dots3", 256, 4, jnp.float32, id="dots3-four-heads-a-cell"),
    pytest.param("dots3", 256, 2, jnp.bfloat16, id="dots3-bfloat16-operands"),
    pytest.param("glm", 256, 4, jnp.float32, id="glm-four-heads-a-cell"),
    pytest.param("glm", 200, 2, jnp.float32,
                 id="glm-offset-200-cuts-the-last-block"),
    pytest.param("glm", 0, 4, jnp.float32,
                 id="glm-offset-0-every-causal-key-chosen"),
    pytest.param("glm", 256, 4, jnp.bfloat16, id="glm-bfloat16-operands"),
    pytest.param("glm", 200, 8, jnp.float32,
                 id="glm-eight-heads-offset-200-cuts-the-last-block"),
    pytest.param("glm", 0, 1, jnp.float32, id="glm-one-head-a-cell-offset-0"),
    pytest.param("glm", 200, 2, jnp.bfloat16,
                 id="glm-bfloat16-offset-200-cuts-the-last-block"),
    pytest.param("whole", 200, 2, jnp.float32,
                 id="whole-128+128-offset-200-cuts-the-last-block"),
    pytest.param("halves", 200, 2, jnp.float32,
                 id="halves-64+64-offset-200-cuts-the-last-block"),
    pytest.param("halves", 0, 1, jnp.float32, id="halves-64+64-offset-0"),
    pytest.param("halves", 256, 2, jnp.bfloat16,
                 id="halves-64+64-bfloat16-operands"),
])
def test_the_masked_flash_kernel_in_the_interpreter_is_its_xla_body(
        widths, offset, heads, dtype):
    """``dsa_attn`` at a block's published head widths (``_HEAD``; the
    rotated part 64, one for all heads), 256 rows at ``offset`` over 512
    keys with 48 chosen a row, in tiles of 128 (keys down the sublanes
    of a score tile, rows along its lanes): the XLA body's output. At
    256 the diagonal begins a block; at 200 the last live block is cut
    inside (rows 0 .. 55 see nothing of it); at 0 the first 48 rows see
    fewer keys than are chosen, so every causal key is, and blocks 2 and
    3 are dead for every row. Row 7 attends its own position alone (its
    LAST live tile: every tile before it all ``NEG``, where the floor
    must hold); row 9 key 3 alone (its FIRST tile: every later one all
    ``NEG``, and the running max must not move). bfloat16 operands are
    held to the float32 body of the same numbers: in the mean to the
    1e-3 that the chip showed at these widths (``PERF.md`` §6 PR 58, PR
    59; 3.4e-4 here), at the worst to a bfloat16's spacing at the
    largest output (the planted rows' are values of order 3, and the
    output is rounded to bfloat16). The body is the one the widths ask
    for (``dsa._one_product``): a ``glm`` or ``halves`` cell's score
    tile is ONE product a head over the joined operands (a float32
    accumulation over ``dn + dr`` for the XLA body's sum of two), a
    ``dots3`` or ``whole`` cell's two."""
    h, dn, dr, dv = _HEAD[widths]
    scale = (dn + dr) ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q_n = jax.random.normal(ks[0], (1, h, 256, dn), dtype)
    q_r = jax.random.normal(ks[1], (1, h, 256, dr), dtype)
    k_n = jax.random.normal(ks[2], (1, h, 512, dn), dtype)
    k_r = jax.random.normal(ks[3], (1, 512, dr), dtype)
    v = jax.random.normal(ks[4], (1, h, 512, dv), dtype)
    scores = jax.random.normal(ks[5], (1, 256, 512), jnp.float32)
    at = jnp.arange(256)[:, None] + offset
    valid = (jnp.arange(512)[None, :] <= at)[None]
    chosen = dsa.select(scores, valid, 48)
    if offset == 0:
        np.testing.assert_array_equal(np.asarray(chosen[0, :48]),
                                      np.asarray(valid[0, :48]))
    chosen = chosen.at[0, 7].set(jnp.arange(512) == 7 + offset)
    chosen = chosen.at[0, 9].set(jnp.arange(512) == 3)
    bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    args = (q_n, q_r, k_n, k_r, v)
    want = dsa.masked_attention_xla(
        *(a.astype(jnp.float32) for a in args), bias, scale)
    kernel = functools.partial(
        dsa.masked_attention, scale=scale, interpret=True, block_q=128,
        block_k=128, heads=heads)
    got = kernel(*args, bias, jnp.int32(offset))
    assert got.dtype == dtype and got.shape == (1, h, 256, dv)
    products = str(jax.make_jaxpr(kernel)(*args, bias, offset)).count(
        "dot_general")
    assert products == heads * (2 if widths in ("glm", "halves") else 3)
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    if dtype == jnp.float32:
        assert err < 1e-5
        # the planted rows are their one key's value
        np.testing.assert_allclose(got[0, :, 7], v[0, :, 7 + offset],
                                   atol=1e-5)
        np.testing.assert_allclose(got[0, :, 9], v[0, :, 3], atol=1e-5)
    else:
        assert err < 2 ** -8 * float(jnp.abs(want).max()), err
        assert float(jnp.abs(got.astype(jnp.float32) - want).mean()) < 1e-3


def test_the_kth_kernel_in_the_interpreter_is_its_xla_body():
    """``dsa_kth``: 16 rows of 384 keys, ragged validity (a row with no
    valid key, a row with one), k under and over what a row holds: the
    threshold is the XLA body's, and ``select`` through the kernel is
    ``select`` without it, ties and all."""
    rng = np.random.RandomState(2)
    scores = rng.randn(16, 384).astype(np.float32)
    scores[:, ::4] = np.round(scores[:, ::4], 1)
    lens = np.array([384, 0, 1, 40, 383, 200, 7, 300] * 2)
    valid = jnp.asarray(np.arange(384)[None, :] < lens[:, None])
    keys = dsa.ordered_keys(jnp.asarray(scores), valid)
    for k in (1, 48, 500):
        kk = jnp.minimum(k, jnp.asarray(lens, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(dsa.kth_largest(keys, kk, interpret=True)),
            np.asarray(dsa.kth_largest_xla(keys, kk)))
        np.testing.assert_array_equal(
            np.asarray(dsa.select(jnp.asarray(scores), valid, k,
                                  interpret=True)),
            np.asarray(dsa.select(jnp.asarray(scores), valid, k)))


def test_the_decode_kernel_in_the_interpreter_is_its_xla_body():
    """``dsa_decode_attn`` at a full layer's published row (640 = 512 +
    64 in whole lanes, values the first 512), 16 heads, four slots of
    200 rows in blocks of 64 at lengths 200, 0, 1 and 130, 24 rows chosen
    of each: the XLA body's output; the inactive slot zeros, bit for
    bit; a block without a chosen row changes nothing."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (4, 16, 640), jnp.float32)
    rows = jax.random.normal(ks[1], (2, 4, 200, 640), jnp.float32)
    lengths = jnp.array([200, 0, 1, 130], jnp.int32)
    valid = jnp.arange(200)[None, :] < lengths[:, None]
    scores = jax.random.normal(ks[2], (4, 200), jnp.float32)
    scores = scores.at[0, 64:128].set(-9.0)  # slot 0's second block: none
    chosen = dsa.select(scores, valid, 24)
    bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    want = dsa.attend_latent_masked(q, rows[1], lengths, bias, 512, 0.07)
    got = dsa.decode_attention_masked(q, rows, 1, lengths, bias, dv=512,
                                      scale=0.07, block=64, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert not np.asarray(got[1]).any() and not np.asarray(want[1]).any()
    # the chosen rows alone: the same attention over them gathered
    at = np.flatnonzero(np.asarray(chosen[3]))
    logits = (q[3] @ rows[1, 3, at].T) * 0.07
    alone = jax.nn.softmax(logits, -1) @ rows[1, 3, at, :512]
    assert float(jnp.abs(got[3] - alone).max()) < 1e-5


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


def test_a_tie_goes_to_the_earlier_position_in_both():
    """Two equal scores planted astride the threshold: of positions 3 and
    11, both worth the 4th place, only 3 is chosen by ``dsa.select`` and
    by the reference's sort; with -0.0 beside 0.0 too."""
    scores = jnp.asarray([[0.5, 9.0, 8.0, 1.0, 7.0, 0.1, 0.2, 0.3, 0.4,
                           0.45, 0.0, 1.0, -0.0, 0.0]], jnp.float32)
    valid = jnp.ones(scores.shape, bool)
    got = np.asarray(dsa.select(scores, valid, 4)[0])
    want = np.asarray(BLOCKS["dots3"].ref.selected(
        scores[None], scores.shape[1] - 1, 4)[0, 0])
    np.testing.assert_array_equal(got, want)
    assert got[3] and not got[11] and got.sum() == 4
    # zeros of either sign tie: the first of them wins
    lows = jnp.where(jnp.arange(14) < 10, -1.0, scores[0])[None]
    got = np.asarray(dsa.select(lows, valid, 2)[0])
    np.testing.assert_array_equal(np.flatnonzero(got), [11, 10][::-1])


@pytest.mark.parametrize("seed", [0, 1])
def test_select_is_a_stable_sorts_first_k_rows(seed):
    """Rows of 300 scores with every third one repeated (ties all over),
    ragged validity, k of 1, 17 and more than a row holds: ``select`` is
    the first ``min(k, valid)`` of a stable descending sort."""
    rng = np.random.RandomState(seed)
    scores = rng.randn(6, 300).astype(np.float32)
    scores[:, ::3] = np.round(scores[:, ::3], 1)
    valid = np.arange(300)[None, :] \
        < np.array([300, 1, 0, 17, 40, 299])[:, None]
    for k in (1, 17, 64):
        got = np.asarray(dsa.select(jnp.asarray(scores), jnp.asarray(valid),
                                    k))
        order = np.argsort(np.where(valid, -scores, np.inf), -1, kind="stable")
        want = np.zeros_like(valid)
        for r in range(6):
            want[r, order[r, :min(k, valid[r].sum())]] = True
        np.testing.assert_array_equal(got, want)


def test_the_references_sets_are_selects():
    """The reference's sets as indices (a stable full argsort) against
    ``dsa.select``'s mask, scores with ties all over, 24 rows at offset
    40 over 64 keys: the same members, row for row, and an empty place
    names no key."""
    ref = BLOCKS["glm"].ref
    rng = np.random.RandomState(3)
    scores = rng.randn(1, 24, 64).astype(np.float32)
    scores[:, :, ::3] = np.round(scores[:, :, ::3], 1)
    valid = (np.arange(64)[None, :] <= np.arange(24)[:, None] + 40)[None]
    for k in (1, 8, 50, 100):
        sets = ref.selected(jnp.asarray(scores), 40, k)
        assert sets.shape == (1, 24, min(k, 64))
        np.testing.assert_array_equal(
            np.asarray(ref.members(sets, 64)),
            np.asarray(dsa.select(jnp.asarray(scores), jnp.asarray(valid),
                                  k)))


@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_selection_is_exact_in_both_programs(block):
    """The programs' text at the tiny five-layer pattern: no approximate
    top-k and no top-k of ``index_topk`` at all (the router's own are of
    4, 2 and 1), float32 index scores of every index head in the layers
    that own an indexer alone (dots3's two full layers; GLM-5.2's two
    sites for five layers), the mask handed to the attention as a bias a
    key."""
    b = BLOCKS[block]
    cfg = b.fam.build(b.m, max_seq_len=64, remat=False).cfg
    params = jax.eval_shape(lambda: b.mod.init_params(
        cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: b.mod.SLOTS.init_state(cfg, 2, 64))
    rest = {k: v for k, v in state.items() if k != "pos"}
    vec = lambda dt: jax.ShapeDtypeStruct((2,), dt)  # noqa: E731
    step = str(jax.make_jaxpr(functools.partial(
        b.mod.SLOTS.step, cfg))(params, None, vec(jnp.int32), rest,
                                vec(jnp.int32), vec(jnp.bool_)))
    pre = str(jax.make_jaxpr(lambda p, t: b.mod.prefill(
        p, t, jnp.array([32], jnp.int32), cfg))(
            params, jax.ShapeDtypeStruct((1, 32), jnp.int32)))
    for text in (step, pre):
        assert "approx" not in text
        assert f"k={b.m['index_topk']}]" not in text
        assert " top_k[" in text  # (the router's)
    hi, sites = cfg.index_heads, b.sparse_layers(cfg)
    assert step.count(f":f32[2,1,{hi},64] = dot_general") == sites
    assert "bf16[2,64]" in step  # a bias a key from the mask, no gather
    assert pre.count(f":f32[1,32,{hi},32] = dot_general") == sites


@pytest.mark.parametrize("block", list(BLOCKS))
def test_blocks_of_rows_give_the_whole_sequences_forward(block, monkeypatch):
    """The reference in blocks of 16 rows and 8 query rows is the
    reference in one block (tiny widths, 40 positions; dots3: two
    layers, selection, the band and both MLPs cross block boundaries;
    GLM-5.2: three layers as indexer, shared, shared, the handed-on sets
    are cut by query block)."""
    b = BLOCKS[block]
    fam, ref = b.fam, manifest.reference(b.fam)  # (a module of its own)
    m = {**b.m, **b.in_blocks}
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
    if b.refused:
        fields, why = b.refused
        with pytest.raises(ValueError, match=why):
            ref.forward(params, toks, {**m, **fields})
