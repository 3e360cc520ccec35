"""Learned sparse attention's operations (``ops/dsa.py``) on the CPU: both
new kernels in the Pallas interpreter against their XLA bodies at the
published head widths, the exact selection against a stable full sort
(the reference's), the mask's positions, and the programs' text for
what must not be in it; and the reference in blocks of rows against
itself in one.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import dots
from ray_tpu.ops import dsa

FAM = manifest.family("dots3_note")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
TOPK = M["index_topk"]


def test_the_index_kernel_in_the_interpreter_is_its_xla_body():
    """``dsa_index`` at the published index head width (4 heads of 128
    for 64): 256 rows at offset 256 over 512 keys in tiles of 128: every
    causal entry is the XLA body's."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 128), jnp.float32)
    w = jax.random.normal(ks[1], (1, 256, 4), jnp.float32)
    k = jax.random.normal(ks[2], (1, 512, 128), jnp.float32)
    want = dsa.index_scores_xla(q, w, k)
    got = dsa.index_scores(q, w, k, jnp.int32(256), interpret=True,
                           block_q=128, block_k=128)
    causal = jnp.arange(512)[None, :] <= jnp.arange(256)[:, None] + 256
    assert float(jnp.abs(jnp.where(causal[None], got - want, 0)).max()) < 1e-4
    with pytest.raises(ValueError, match="multiples"):
        dsa.index_scores(q[:, :200], w[:, :200], k, 0, interpret=True,
                         block_q=128)


def test_the_masked_flash_kernel_in_the_interpreter_is_its_xla_body():
    """``dsa_attn`` at the published head widths (keys 192, values 128),
    4 heads two a grid cell, 256 rows at offset 256 over 512 keys with
    48 chosen a row, in tiles of 128: the XLA body's output; a row whose
    chosen keys all lie in its last tile too."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, 4, 256, 192), jnp.float32)
    k = jax.random.normal(ks[1], (1, 4, 512, 192), jnp.float32)
    v = jax.random.normal(ks[2], (1, 4, 512, 128), jnp.float32)
    scores = jax.random.normal(ks[3], (1, 256, 512), jnp.float32)
    at = jnp.arange(256)[:, None] + 256
    valid = (jnp.arange(512)[None, :] <= at)[None]
    chosen = dsa.select(scores, valid, 48)
    chosen = chosen.at[0, 7].set(jnp.arange(512) == 263)  # its own row alone
    bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    # (the one rotated key of all heads is an operand of its own)
    args = (q[..., :128], q[..., 128:], k[..., :128], k[0, 0, :, 128:][None],
            v, bias)
    want = dsa.masked_attention_xla(*args, 192 ** -0.5)
    got = dsa.masked_attention(*args, jnp.int32(256), scale=192 ** -0.5,
                               interpret=True, block_q=128, block_k=128,
                               heads=2)
    assert float(jnp.abs(got - want).max()) < 1e-5


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


def test_a_tie_goes_to_the_earlier_position_in_both():
    """Two equal scores planted astride the threshold: of positions 3 and
    11, both worth the 4th place, only 3 is chosen by ``dsa.select`` and
    by the reference's sort; with -0.0 beside 0.0 too."""
    scores = jnp.asarray([[0.5, 9.0, 8.0, 1.0, 7.0, 0.1, 0.2, 0.3, 0.4,
                           0.45, 0.0, 1.0, -0.0, 0.0]], jnp.float32)
    valid = jnp.ones(scores.shape, bool)
    got = np.asarray(dsa.select(scores, valid, 4)[0])
    want = np.asarray(REF.selected(scores[None], scores.shape[1] - 1, 4)[0, 0])
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


def test_the_selection_is_exact_in_both_programs():
    """The programs' text: no approximate top-k and no top-k of
    ``index_topk`` at all (the router's own are of 4, 2 and 1), the index
    scores float32 and made of every index head, the mask handed to
    the attention as a bias a key."""
    held = M["held_experts"]
    cfg = dots.DotsConfig(**{**M, "held_experts": tuple(held),
                             "layer_pattern": tuple(M["layer_pattern"])})
    params = jax.eval_shape(lambda: dots.init_params(
        cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: dots.SLOTS.init_state(cfg, 2, 64))
    rest = {k: v for k, v in state.items() if k != "pos"}
    vec = lambda dt: jax.ShapeDtypeStruct((2,), dt)  # noqa: E731
    step = str(jax.make_jaxpr(functools.partial(
        dots.SLOTS.step, cfg))(params, None, vec(jnp.int32), rest,
                               vec(jnp.int32), vec(jnp.bool_)))
    pre = str(jax.make_jaxpr(lambda p, t: dots.prefill(
        p, t, jnp.array([32], jnp.int32), cfg))(
            params, jax.ShapeDtypeStruct((1, 32), jnp.int32)))
    for text in (step, pre):
        assert "approx" not in text
        assert f"k={TOPK}]" not in text and " top_k[" in text  # (the router's)
    hi = cfg.index_heads
    assert f"f32[2,1,{hi},64]" in step  # every head's scores, float32
    assert "bf16[2,64]" in step  # a bias a key from the mask, no gather
    assert f"f32[1,32,{hi},32]" in pre


def test_blocks_of_rows_give_the_whole_sequences_forward(monkeypatch):
    """The reference in blocks of 16 rows and 8 query rows is the
    reference in one block (tiny widths, two layers, 40 positions:
    selection, the band and both MLPs cross block boundaries)."""
    fam, ref = FAM, manifest.reference(FAM)  # (a module of its own)
    m = {**M, "n_layers": 2, "layer_pattern": [0, 1]}
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
