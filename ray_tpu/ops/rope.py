"""Rotary position embeddings (RoPE), Llama convention, and YaRN's
frequencies for a context stretched past the trained one (DeepSeek-V3's
form: arXiv:2309.00071 as ``deepseek_v3`` configurations apply it).

Sin/cos tables are computed in f32 once per call site; under jit XLA constant-
folds them for static position ranges.
"""

import math

import jax.numpy as jnp


def rotary_embedding(positions, head_dim: int, theta: float = 10000.0,
                     inv_freq=None):
    """Return (sin, cos) tables of shape positions.shape + (head_dim // 2,).
    ``inv_freq`` [head_dim // 2] replaces the plain frequencies
    (:func:`yarn_inv_freq`)."""
    half = head_dim // 2
    if inv_freq is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.sin(angles), jnp.cos(angles)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's frequencies [head_dim // 2], float32: pair i keeps its plain
    frequency ``theta^(-2i / head_dim)`` where it turns more than
    ``beta_fast`` times over the ``original_max`` trained positions,
    takes that over ``factor`` where it turns fewer than ``beta_slow``
    times, and between the two pairs where that happens (the first
    rounded down, the second up) a linear blend of both. ``factor`` <= 1
    is the plain rotary, bit for bit."""
    half = head_dim // 2
    plain = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if factor <= 1:
        return plain

    def pair_turning(rotations: float) -> float:
        return head_dim * math.log(
            original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # (no pair between: a step, not a division by 0)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1`` (1
    for ``factor`` <= 1): cos and sin carry ``yarn_mscale(factor, mscale)
    / yarn_mscale(factor, mscale_all_dim)``, the softmax scale
    ``yarn_mscale(factor, mscale_all_dim) ** 2``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rotary(x, sin, cos):
    """Rotate pairs (x1, x2) = (x[..., :half], x[..., half:]).

    x: [..., T, n_heads, head_dim]; sin/cos: [..., T, half] (broadcast over heads).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[..., None, :]  # broadcast over the heads axis
    cos = cos[..., None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def apply_rotary_leading(x, sin, cos):
    """Rotate the LEADING ``2 * sin.shape[-1]`` numbers of every head
    (rotate-half among themselves) and pass the rest through
    (``partial_rotary_factor``). x, sin, cos as :func:`apply_rotary`."""
    r = 2 * sin.shape[-1]
    return jnp.concatenate(
        [apply_rotary(x[..., :r], sin, cos), x[..., r:]], axis=-1)


def apply_rotary_interleaved(x, sin, cos):
    """Rotate the INTERLEAVED pairs (x[..., 2i], x[..., 2i + 1]) by pair
    i's angle (``rope_interleave``). The rotated pairs come back apart,
    [every y_2i, then every y_2i+1]: a fixed permutation of the rotated
    vector, the same for a query and a key, so their product is that of
    the interleaved result. x, sin, cos as :func:`apply_rotary`."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
