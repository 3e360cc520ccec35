"""Ops unit tests: norms, rope, attention (reference vs flash-interpret)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention_reference, rms_norm, softmax_cross_entropy
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.rope import apply_rotary, rotary_embedding


def test_rms_norm_matches_manual(rng):
    x = jax.random.normal(rng, (4, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16,), jnp.float32)
    got = rms_norm(x, w, eps=1e-6)
    want = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_rope_norm_preserving(rng):
    x = jax.random.normal(rng, (2, 8, 4, 32), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None, :]
    sin, cos = rotary_embedding(pos, 32)
    y = apply_rotary(x, sin, cos)
    # Rotation preserves per-pair norms.
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )
    # Position 0 is identity.
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-6)


def test_attention_reference_causality(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (1, 8, 2, 16), jnp.float32)
    k = jax.random.normal(k2, (1, 8, 2, 16), jnp.float32)
    v = jax.random.normal(k3, (1, 8, 2, 16), jnp.float32)
    out1 = attention_reference(q, k, v, causal=True)
    # Perturbing future keys/values must not change earlier outputs.
    k_mod = k.at[:, 4:].set(0.0)
    v_mod = v.at[:, 4:].set(9.0)
    out2 = attention_reference(q, k_mod, v_mod, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :4]), np.asarray(out2[:, :4]), rtol=1e-5)
    assert not np.allclose(np.asarray(out1[:, 4:]), np.asarray(out2[:, 4:]))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [1, 2])
def test_flash_matches_reference(rng, causal, gqa):
    b, t, hq, d = 2, 256, 4, 64
    hkv = hq // gqa
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, hq, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, hkv, d), jnp.float32)
    want = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_flash_gradients_match_reference(rng):
    b, t, h, d = 1, 128, 2, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_reference_gqa(rng, causal):
    b, t, hq, hkv, d = 1, 128, 4, 2, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, hq, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, hkv, d), jnp.float32)

    def f_ref(q, k, v):
        return (attention_reference(q, k, v, causal=causal) ** 2).sum()

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                interpret=True) ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3)


def test_flash_gradients_decode_shape(rng):
    """T != S gradients (end-aligned causal mask in the backward)."""
    b, t, s, h, d = 1, 64, 128, 2, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


def test_flash_empty_rows_t_gt_s(rng):
    """T > S causal: leading rows attend nothing -> output 0, gradients 0,
    including rows straddling a live block."""
    b, t, s, h, d = 1, 256, 128, 2, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)
    want = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    # offset = -128: rows 0..127 attend nothing (blocks 0..1 of 64 are dead).
    np.testing.assert_allclose(np.asarray(got[:, :128]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[:, 128:]),
                               np.asarray(want[:, 128:]), atol=2e-5, rtol=1e-4)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True).sum()

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4,
                                   rtol=1e-3)


def test_flash_gradients_non_pow2_seq(rng):
    """Seq len where naive bwd tile widening would go ragged (1536 % 1024)."""
    b, t, h, d = 1, 1536, 1, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512, block_k=512,
                               interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-4,
                                   rtol=1e-3)


def test_flash_decode_shape_matches_reference(rng):
    """T != S (decode against a cache): mask must be end-aligned."""
    b, t, s, h, d = 1, 64, 256, 2, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)
    want = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


def test_flash_rejects_ragged_lengths(rng):
    q = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, q, q, causal=True, block_q=64, block_k=64,
                        interpret=True)


def test_cross_entropy_uniform(rng):
    logits = jnp.zeros((4, 7, 10))
    labels = jnp.zeros((4, 7), jnp.int32)
    loss, n = softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(10), rtol=1e-6)
    assert int(n) == 28


def test_flash_long_seq_multiblock_fwd(rng):
    """S > _FULL_INNER_MAX forces the tiled online-softmax forward kernel
    (log2-domain running max/corr) — unreachable at short S, where the
    single-pass kernel runs instead."""
    b, t, h, d = 1, 4096, 1, 32
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)
    want = attention_reference(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=1024, block_k=1024,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=1024,
                               block_k=1024, interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=1e-3)


def test_flash_legacy_bwd_path_very_long_kv(rng):
    """S large enough that the fused backward's dq-partial array is
    ineligible (> _MAX_DQ_PARTIALS) — exercises the legacy two-kernel
    backward, which otherwise has no reachable configuration."""
    from ray_tpu.ops.flash_attention import _fused_blocks

    b, t, s, h, d = 1, 256, 16384, 1, 32
    assert _fused_blocks(t, s, 256, 1024) is None  # really the legacy path
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, s, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, s, h, d), jnp.float32)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=256,
                               block_k=1024, interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("hb", [2, 4])
def test_flash_heads_per_block_matches_reference(rng, hb):
    """flash_heads_per_block > 1 (multi-head grid cells, MHA only) must
    be numerically identical to the per-head layout."""
    from ray_tpu._private import config as _cfg

    b, t, h, d = 2, 256, 4, 64
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)
    want = attention_reference(q, k, v, causal=True)
    old = _cfg.get("flash_heads_per_block")
    try:
        _cfg.set_system_config({"flash_heads_per_block": hb})
        got = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=256, interpret=True)
    finally:
        _cfg.set_system_config({"flash_heads_per_block": old})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("hb", [2, 4])
def test_flash_bwd_heads_per_block_matches_reference(rng, hb):
    """flash_bwd_heads_per_block > 1 (multi-head fused-backward cells,
    MHA only) must produce the same gradients as the per-head layout."""
    from ray_tpu._private import config as _cfg

    b, t, h, d = 2, 512, 4, 64
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, t, h, d), jnp.float32)
    k = jax.random.normal(k2, (b, t, h, d), jnp.float32)
    v = jax.random.normal(k3, (b, t, h, d), jnp.float32)

    def f_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=256,
                               block_k=512, interpret=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    old = _cfg.get("flash_bwd_heads_per_block")
    try:
        _cfg.set_system_config({"flash_bwd_heads_per_block": hb})
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        _cfg.set_system_config({"flash_bwd_heads_per_block": old})
    for a, b_ in zip(g_ref, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=1e-3)


def test_attention_shards_flash_over_the_mesh(rng, monkeypatch):
    """Under a multi-device mesh `attention` runs the kernel inside a
    shard_map (batch over fsdp, heads and kv heads over tp): outputs and
    gradients must equal the unsharded reference."""
    import functools

    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import attention
    from ray_tpu.parallel import MeshConfig, build_mesh, use_mesh

    # no chip here: the interpreter is this test's explicit choice
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (2, 128, 4, 64), jnp.float32)
    k = jax.random.normal(k2, (2, 128, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (2, 128, 2, 64), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref = functools.partial(attention_reference, causal=True)
    want = jax.value_and_grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    with use_mesh(mesh):
        got = jax.jit(jax.value_and_grad(
            loss(functools.partial(attention, use_flash=True)),
            argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4)


def test_attention_never_falls_back_once_flash_is_chosen(rng):
    """Ragged lengths raise instead of quietly taking the O(T*S)
    reference; use_flash=False is the explicit way to the reference,
    and on a CPU backend use_flash=None means the reference."""
    from ray_tpu._private import config as _cfg
    from ray_tpu.ops.attention import attention

    block = _cfg.get("flash_block_q")
    q = jnp.zeros((1, block + 64, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiples of the flash blocks"):
        jax.eval_shape(lambda: attention(q, q, q, use_flash=True))
    assert jax.eval_shape(
        lambda: attention(q, q, q, use_flash=False)).shape == q.shape
    assert jax.eval_shape(
        lambda: attention(q, q, q, use_flash=None)).shape == q.shape
