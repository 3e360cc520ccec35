"""The decode step's attention and how its cache travels (ISSUE 28).

``_attend_ragged`` contracts the query groups against the cache as it is
stored; the plain reference kept here repeats the kv heads first
(``ops.attention._repeat_kv``, the formulation the engine had). And the
chunk program, whose layer loop carries the stacked cache as state,
returns the tokens of the uncached forward (``tests/_oracle.py``).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from _oracle import greedy_tokens  # noqa: E402
from ray_tpu.models import decode_engine as de  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
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
    got = de._attend_ragged(q, ck, cv, qpos)
    want = _attend_repeated(q, ck, cv, qpos)
    assert got.shape == (b, t, hkv * group, hd)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # rows past a query's position do not reach its output
    junk = ck.at[0, t:].set(1e3), cv.at[0, t:].set(1e3)
    np.testing.assert_array_equal(
        de._attend_ragged(q, *junk, qpos)[0], got[0])


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
    cache = de.init_ragged_cache(cfg, slots, max_len)
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
