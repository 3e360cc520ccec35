"""The decode step's attention and how its cache travels (ISSUEs 28, 33).

``_attend_ragged`` (the XLA body) contracts the query groups against the
cache as it is stored; the plain reference kept here repeats the kv
heads first (``ops.attention._repeat_kv``, the formulation the engine
had). The ``decode_attn`` kernel (``ops/decode_attention.py``), run in
the Pallas interpreter, reads each slot of the stacked cache up to its
own length and agrees with both. And the chunk program, whose layer loop
carries the stacked cache as state, returns the tokens of the uncached
forward (``tests/_oracle.py``), through the XLA body and through the
kernel.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from _oracle import greedy_tokens  # noqa: E402
from ray_tpu.models import decode_engine as de  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models import llama_slots  # noqa: E402
from ray_tpu.ops import decode_attention as da  # noqa: E402
from ray_tpu.ops.attention import _repeat_kv  # noqa: E402


def _attend_repeated(q, ck, cv, qpos):
    """The plain reference: repeat each kv head ``group`` times, then one
    contraction a query head (head h = kv * group + r)."""
    n_rep = q.shape[2] // ck.shape[2]
    kk, vv = _repeat_kv(ck, n_rep), _repeat_kv(cv, n_rep)
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, kk, preferred_element_type=jnp.float32
    ) * (q.shape[-1] ** -0.5)
    k_pos = jnp.arange(ck.shape[1], dtype=jnp.int32)[None, None, :]
    live = k_pos <= qpos[:, :, None]  # [B, T, S]
    logits = jnp.where(live[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(
        "bhts,bshd->bthd", probs, vv, preferred_element_type=jnp.float32
    ).astype(q.dtype)


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_grouped_contraction_matches_repeated_kv(group, t):
    b, s, hkv, hd = 3, 24, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7 * group + t), 3)
    q = jax.random.normal(kq, (b, t, hkv * group, hd), jnp.float32)
    ck = jax.random.normal(kk, (b, s, hkv, hd), jnp.float32)
    cv = jax.random.normal(kv, (b, s, hkv, hd), jnp.float32)
    # ragged: each slot at its own base position, the last one at the
    # cache's edge
    pos = jnp.array([0, 7, s - t], jnp.int32)
    qpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    got = da.attend_ragged(q, ck, cv, qpos)
    want = _attend_repeated(q, ck, cv, qpos)
    assert got.shape == (b, t, hkv * group, hd)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # rows past a query's position do not reach its output
    junk = ck.at[0, t:].set(1e3), cv.at[0, t:].set(1e3)
    np.testing.assert_array_equal(
        da.attend_ragged(q, *junk, qpos)[0], got[0])


@pytest.mark.parametrize("group", [1, 2, 4])
def test_decode_chunk_returns_the_greedy_tokens(group):
    """Three slots prefilled at ragged lengths, two chunks of 6: every
    token is ``greedy_generate``'s for that prompt alone."""
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4,
        n_kv_heads=4 // group, d_ff=128, max_seq_len=48, dtype="float32",
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(group))
    slots, max_len, bucket, chunk = 3, 48, 16, 6
    rng = np.random.RandomState(28)
    lens = [5, 16, 11]
    prompts = np.zeros((slots, bucket), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.randint(1, 250, n)
    cache = llama_slots.init_ragged_cache(cfg, slots, max_len)
    cache, tok, toks0, _ = de._prefill_batch_into_slots(
        params, prompts, np.array(lens, np.int32),
        np.arange(slots, dtype=np.int32), np.zeros(slots, np.uint32),
        np.zeros(slots, np.float32), np.ones(slots, np.float32),
        cache, jnp.zeros((slots,), jnp.int32), cfg)
    got = [np.asarray(toks0)[:, None]]
    active = np.ones(slots, bool)
    for _ in range(2):
        toks, _, cache, tok = de.decode_chunk(
            params, cache, tok, active, None, cfg, chunk)
        got.append(np.asarray(toks))
    got = np.concatenate(got, axis=1)  # [slots, 1 + 2 * chunk]
    assert list(np.asarray(cache["pos"])) == [n + 2 * chunk for n in lens]
    for i, n in enumerate(lens):
        np.testing.assert_array_equal(got[i], greedy_tokens(
            params, prompts[i, :n], cfg, 1 + 2 * chunk))


# ---- the kernel (ISSUE 33), in the Pallas interpreter ----

BLOCK, ROWS = 16, 40  # the cache ends inside its third block


@pytest.mark.parametrize("length", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, ROWS])
@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("group", [1, 2])
def test_kernel_reads_each_slot_up_to_its_own_length(group, t, length):
    """Slot 0 holds ``length`` rows (0: it is inactive), its neighbours
    a block and a half and nothing; layer 1 of a stack of two. The
    kernel agrees with the XLA body and with the repeated-kv oracle,
    junk beyond a slot's length (in the block it ends in, and in blocks
    it never visits) does not reach the output, and a slot without a row
    returns zeros."""
    layers, b, hkv, hd = 2, 3, 2, 16
    length = max(length, t) if length else 0  # an active slot wrote t rows
    lengths = jnp.array([length, BLOCK + BLOCK // 2, 0], jnp.int32)
    kq, kk, kv, kj = jax.random.split(
        jax.random.PRNGKey(100 * group + 10 * t + length), 4)
    q = jax.random.normal(kq, (b, t, hkv * group, hd), jnp.float32)
    k, v = (jax.random.normal(key, (layers, b, ROWS, hkv * hd), jnp.float32)
            for key in (kk, kv))
    layer = jnp.int32(1)
    attend = functools.partial(da.decode_attention, interpret=True,
                               rows=BLOCK)
    got = attend(q, k, v, layer, lengths)
    assert got.shape == q.shape and np.isfinite(got).all()
    live = np.asarray(lengths) > 0
    assert not np.asarray(got)[~live].any()
    # the XLA body and the oracle, over every row of the layer
    qpos = (lengths - t)[:, None] + jnp.arange(t, dtype=jnp.int32)
    heads = (b, ROWS, hkv, hd)
    for reference in (da.attend_ragged, _attend_repeated):
        want = reference(q, k[1].reshape(heads), v[1].reshape(heads),
                         jnp.maximum(qpos, 0))
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live],
                                   atol=2e-5, rtol=2e-5)
    # the dispatch off the TPU is the XLA body, zeros where it is told to
    np.testing.assert_allclose(
        da.decode_attention(q, k, v, layer, lengths), got,
        atol=2e-5, rtol=2e-5)
    beyond = jnp.arange(ROWS)[None, :, None] >= lengths[:, None, None]
    junk = 1e3 * jax.random.normal(kj, k.shape[1:], jnp.float32)
    kj_, vj_ = (jnp.where(beyond[None], junk[None], a) for a in (k, v))
    np.testing.assert_array_equal(attend(q, kj_, vj_, layer, lengths), got)


def test_the_visits_are_the_blocks_that_hold_a_row():
    (slots, blocks), steps = da.visits(
        jnp.array([0, 17, 0, 40, 16], jnp.int32), ROWS, BLOCK)
    assert int(steps) == 2 + 3 + 1
    assert list(np.asarray(slots)[:6]) == [1, 1, 3, 3, 3, 4]
    assert list(np.asarray(blocks)[:6]) == [0, 1, 0, 1, 2, 0]
    # nothing live: one step, of a slot that stores nothing
    (slots, blocks), steps = da.visits(jnp.zeros(3, jnp.int32), ROWS, BLOCK)
    assert int(steps) == 1 and int(blocks[0]) == 0
    assert da.block_rows(1296) == 432 and da.block_rows(512) == 512
    assert da.block_rows(288) == 288 and da.block_rows(40) == 48


@pytest.mark.parametrize("group", [1, 2])
def test_decode_chunk_through_the_kernel_returns_the_greedy_tokens(
        group, monkeypatch):
    """The chunk program with the kernel interpreted in its layer loop:
    two slots at different positions decode the oracle's tokens, the
    third is inactive and keeps its token and its position."""
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    cfg = llama.LlamaConfig(  # (a size of its own: decode_chunk is cached)
        vocab_size=251, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4 // group, d_ff=128, max_seq_len=40, dtype="float32",
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(33 + group))
    slots, max_len, bucket, chunk = 3, 40, 16, 5
    rng = np.random.RandomState(33)
    lens = [4, 15, 9]
    prompts = np.zeros((slots, bucket), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.randint(1, 250, n)
    cache = llama_slots.init_ragged_cache(cfg, slots, max_len)
    cache, tok, toks0, _ = de._prefill_batch_into_slots(
        params, prompts, np.array(lens, np.int32),
        np.arange(slots, dtype=np.int32), np.zeros(slots, np.uint32),
        np.zeros(slots, np.float32), np.ones(slots, np.float32),
        cache, jnp.zeros((slots,), jnp.int32), cfg)
    first = np.asarray(toks0)
    active = np.array([True, True, False])
    got = [first[:, None]]
    for _ in range(2):  # (40 rows are one block of 48: it ends past them)
        toks, _, cache, tok = de.decode_chunk(
            params, cache, tok, active, None, cfg, chunk)
        got.append(np.asarray(toks))
    got = np.concatenate(got, axis=1)
    assert list(np.asarray(cache["pos"])) == [4 + 10, 15 + 10, 9]
    assert int(tok[2]) == int(first[2])
    for i in (0, 1):
        np.testing.assert_array_equal(got[i], greedy_tokens(
            params, prompts[i, :lens[i]], cfg, 1 + 2 * chunk))


# ---- a head that is a part of a lane tile (ISSUE 68) ----


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hd, hkv, group, t", [
    (64, 8, 4, 1),   # LFM2's step: two heads a tile, 8 of a tile's 16 rows
    (64, 2, 2, 3),   # a verify's three rows a head
    (32, 4, 1, 2),   # four heads a tile
    (16, 8, 2, 1),   # eight heads a tile: all 16 rows
])
def test_a_head_that_divides_a_tile_is_a_part_of_a_head_of_128(
        hd, hkv, group, t, dtype):
    """``decode_attention`` where ``128 // hd`` kv heads share a lane
    tile: the kernel in the interpreter, handed each tile as ONE head of
    128 (a head's query rows in a zeroed tile at the head's own lanes),
    agrees with ``attend_ragged`` at ragged lengths with an inactive
    slot, with a sink and without; junk beyond a slot's length does not
    reach the output; and the lay-out is what the docstring says: four
    tiles of 16 rows for LFM2's 8 x 64."""
    dt = jnp.dtype(dtype)
    layers, b = 2, 3
    assert da._heads_a_tile(hd, hd, hkv) == 128 // hd
    lengths = jnp.array([BLOCK + t, ROWS, 0], jnp.int32)
    kq, kk, kv, ks, kj = jax.random.split(jax.random.PRNGKey(hd + t), 5)
    q = jax.random.normal(kq, (b, t, hkv * group, hd), jnp.float32).astype(dt)
    k, v = (jax.random.normal(key, (layers, b, ROWS, hkv * hd),
                              jnp.float32).astype(dt) for key in (kk, kv))
    sink = jax.random.normal(ks, (hkv * group,), jnp.float32)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for s in (None, sink):
        attend = functools.partial(da.decode_attention, interpret=True,
                                   rows=BLOCK, sink=s)
        got = attend(q, k, v, jnp.int32(1), lengths)
        assert got.shape == q.shape and got.dtype == dt
        assert not np.asarray(got[2], np.float32).any()
        want = da.decode_attention(q, k, v, jnp.int32(1), lengths,
                                   use_kernel=False, sink=s)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        beyond = jnp.arange(ROWS)[None, :, None] >= lengths[:, None, None]
        junk = (1e3 * jax.random.normal(kj, k.shape[1:], jnp.float32)
                ).astype(dt)
        kj_, vj_ = (jnp.where(beyond[None], junk[None], a) for a in (k, v))
        np.testing.assert_array_equal(
            attend(q, kj_, vj_, jnp.int32(1), lengths), got)
    if (hd, hkv, group, t) == (64, 8, 4, 1):
        text = jax.jit(functools.partial(
            da.decode_attention, interpret=True, rows=BLOCK)).lower(
                q, k, v, jnp.int32(1), lengths).as_text()
        assert f"tensor<{b}x4x16x128x" in text  # q: four tiles of 16 rows


def test_the_dispatch_takes_the_kernel_for_a_part_of_a_tile(monkeypatch):
    """On a TPU backend the choice is the shapes': whole tiles (128,
    256), whole tiles and a packed remainder (192 beside values of 128)
    and a part of a tile (64, 32: LFM2's heads) take the kernel; a head
    that divides no tile (48, 96), values of another width than such
    keys, a row that is not whole tiles of heads and more query rows
    than a tile's 16 take the XLA body."""
    asked = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "_decode_attn", lambda q, *a, **kw: asked.append(
        q.shape) or jnp.zeros(q.shape[:3] + (a[1].shape[3] // (
            a[0].shape[3] // q.shape[3]),), q.dtype))

    def kernel(hd, hkv, group, t=1, dv=None):
        dv = dv or hd
        asked.clear()
        da.decode_attention(
            jnp.zeros((2, t, hkv * group, hd)), jnp.zeros((1, 2, 32, hkv * hd)),
            jnp.zeros((1, 2, 32, hkv * dv)), 0, jnp.array([5, 0]))
        return bool(asked)

    assert kernel(128, 8, 2) and kernel(256, 2, 8) and kernel(192, 4, 4, dv=128)
    assert kernel(64, 8, 4) and kernel(32, 4, 1) and kernel(64, 8, 4, t=2)
    assert not kernel(48, 8, 2) and not kernel(96, 4, 2)
    assert not kernel(64, 8, 4, dv=128)  # keys and values alike there
    assert not kernel(64, 3, 4)  # three heads are a tile and a half
    assert not kernel(64, 8, 4, t=3)  # 3 x 4 x 2 rows > 16
    assert not kernel(128, 8, 4, t=5)


def test_the_tile_of_heads_code_stands_behind_both_kernels():
    """Both kernels' Mosaic modules carry the lines and columns they
    were traced from, their callers' among them, so what PR 68 added
    stands at the END of the module and the kernel's call in
    ``decode_attention`` starts where it started: a line added above
    either moves the compile-cache key of every older cell's programs.
    And the other head's lanes of a tile's ``p v`` are NOT the head's:
    the control the cell's limits are read against."""
    import inspect

    src = inspect.getsource(da)
    behind = src.index("def decode_attention_latent(")
    for name in ("_heads_a_tile", "_kernel_call", "_tiles_of_heads"):
        assert src.index(f"def {name}(") > behind, name
    assert "\n    return _kernel_call(hd, k, v)(q, k, v, layer, lengths,\n" \
        "                        plan or visits(lengths, s, bs), bs=bs,\n" in src
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 8, 64), jnp.float32)
    k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 32, 4 * 64),
                              jnp.float32) for i in (1, 2))
    lengths = jnp.array([20, 7], jnp.int32)
    want = da.decode_attention(q, k, v, 0, lengths, use_kernel=False)
    got = da.decode_attention(q, k, v, 0, lengths, interpret=True, rows=16)
    np.testing.assert_allclose(got, want, atol=2e-5)

    def other_lanes(per, q, k, v, layer, lengths, plan, **kw):
        b, t, hq, hd = q.shape
        hkv = k.shape[3] // hd
        lanes = jnp.eye(per, dtype=q.dtype)[:, None, :, None]
        tiles = q.reshape(b, t, hkv // per, per, hq // hkv, 1, hd) * lanes
        out = da._decode_attn(tiles.reshape(b, t, hq, 128), k, v, layer,
                              lengths, plan, scale=hd ** -0.5, **kw)
        out = out.reshape(b, t, hkv // per, per, hq // hkv, per, hd)
        return jnp.stack([out[:, :, :, i, :, per - 1 - i]
                          for i in range(per)], axis=3).reshape(b, t, hq, hd)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(da, "_tiles_of_heads", other_lanes)
        wrong = da.decode_attention(q, k, v, 0, lengths, interpret=True,
                                    rows=16)
    assert float(jnp.abs(wrong - want).max()) > 0.5
