"""``ops/rope.py``'s YaRN frequencies, temperature and interleaved pairs
against a NumPy transcription of the published form (DeepSeek-V3's
``yarn`` rotary), at the positions where it matters: 0, the last trained
position, the first past it, and the last row of the longest slot."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.rope import (apply_rotary, apply_rotary_interleaved,
                              rotary_embedding, yarn_inv_freq, yarn_mscale)

# the rotated part of the configuration that uses it (32 of a head's 128)
DIM, THETA, FACTOR, TRAINED, FAST, SLOW = 32, 8e6, 40.0, 4096, 32.0, 1.0
POSITIONS = (0, 4095, 4096, 16911)


def _numpy_yarn():
    """The published form, written out in float64."""
    i = np.arange(0, DIM, 2, dtype=np.float64)
    extra = 1.0 / THETA ** (i / DIM)
    inter = 1.0 / (FACTOR * THETA ** (i / DIM))

    def correction_dim(rotations):
        return DIM * math.log(TRAINED / (rotations * 2 * math.pi)) \
            / (2 * math.log(THETA))

    low = max(math.floor(correction_dim(FAST)), 0)
    high = min(math.ceil(correction_dim(SLOW)), DIM - 1)
    ramp = np.clip((np.arange(DIM // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def test_yarn_frequencies_blend_between_the_two_turning_pairs():
    got = np.asarray(yarn_inv_freq(DIM, THETA, FACTOR, TRAINED, FAST, SLOW))
    want = _numpy_yarn()
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 1.0 / THETA ** (np.arange(0, DIM, 2) / DIM)
    # the fastest pairs keep their frequency, the slowest are stretched
    # by the whole factor, and some pair lies strictly between
    assert got[0] == pytest.approx(plain[0], rel=1e-6)
    assert got[-1] == pytest.approx(plain[-1] / FACTOR, rel=1e-6)
    ratio = got / plain
    assert ((ratio < 0.999) & (ratio > 1.001 / FACTOR)).any()


@pytest.mark.parametrize("position", POSITIONS)
def test_yarn_tables_against_numpy(position):
    inv = yarn_inv_freq(DIM, THETA, FACTOR, TRAINED, FAST, SLOW)
    sin, cos = rotary_embedding(jnp.asarray([position]), DIM, THETA,
                                inv_freq=inv)
    # the angle in float32 as the program forms it, the functions exact
    angle = (np.float32(position) * np.asarray(inv)).astype(np.float64)
    np.testing.assert_allclose(np.asarray(sin)[0], np.sin(angle), atol=2e-6)
    np.testing.assert_allclose(np.asarray(cos)[0], np.cos(angle), atol=2e-6)
    # and the transcription's own float64 angle, to what a float32
    # product of a position of five digits allows
    want = position * _numpy_yarn()
    np.testing.assert_allclose(np.asarray(sin)[0], np.sin(want),
                               atol=2e-3 if position else 0)


def test_yarn_temperature():
    assert yarn_mscale(FACTOR, 1.0) == pytest.approx(
        0.1 * math.log(40.0) + 1.0)
    assert yarn_mscale(1.0, 1.0) == 1.0
    # cos and sin carry mscale / mscale_all_dim = 1 for this model; the
    # softmax scale carries the square
    assert yarn_mscale(FACTOR, 1.0) ** 2 == pytest.approx(1.87387, rel=1e-5)


@pytest.mark.parametrize("position", POSITIONS)
def test_factor_one_is_the_plain_rotary_bit_for_bit(position):
    pos = jnp.asarray([position])
    inv = yarn_inv_freq(DIM, THETA, 1.0, TRAINED, FAST, SLOW)
    for got, want in zip(rotary_embedding(pos, DIM, THETA, inv_freq=inv),
                         rotary_embedding(pos, DIM, THETA)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_interleaved_pairs_rotate_as_complex_numbers():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 4, 2, DIM)).astype(np.float32)
    pos = jnp.asarray([[0, 4095, 4096, 16911]])
    inv = yarn_inv_freq(DIM, THETA, FACTOR, TRAINED, FAST, SLOW)
    sin, cos = rotary_embedding(pos, DIM, THETA, inv_freq=inv)
    got = np.asarray(apply_rotary_interleaved(jnp.asarray(x), sin, cos))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * np.asarray(pos, np.float64)[..., None, None]
        * np.asarray(inv, np.float64))
    np.testing.assert_allclose(got[..., :DIM // 2], z.real, atol=3e-3)
    np.testing.assert_allclose(got[..., DIM // 2:], z.imag, atol=3e-3)
    # position 0 only takes the pairs apart
    np.testing.assert_array_equal(got[0, 0, :, :DIM // 2], x[0, 0, :, 0::2])
    # the same products as the rotate-half convention gives on the
    # vector taken apart first: a permutation shared by q and k
    apart = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    np.testing.assert_array_equal(
        got, np.asarray(apply_rotary(jnp.asarray(apart), sin, cos)))
