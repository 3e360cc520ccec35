"""What the tests of the two segmented blocks (``test_solar_block.py``,
``test_mimo_block.py``) share: buckets cut into segments of 16 rows, one
prompt through the engine's prefill program into a slot, greedy steps of
that slot alone, and the comparison of a short prompt in a long bucket
(dead segments behind it, ``moe.in_segments``) with the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode_engine as de
from ray_tpu.models import moe


@pytest.fixture
def segments_of_16(monkeypatch):
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    jax.clear_caches()  # (the engine's programs are cached by cfg alone)
    yield
    jax.clear_caches()


def prefill_slot(cfg, params, state, cur, slot, prompt, bucket=128):
    row = np.zeros((1, bucket), np.int32)
    row[0, :len(prompt)] = prompt
    state, cur, *_ = de._prefill_batch_into_slots(
        params, row, np.array([len(prompt)], np.int32),
        np.array([slot], np.int32), np.array([0], np.uint32),
        np.array([0.0], np.float32), np.array([1.0], np.float32),
        state, cur, cfg)
    return state, cur


def decode_from(slots, cfg, params, state, cur, slot, steps):
    """``steps`` greedy steps of ``slot`` alone -> (the tokens fed,
    float32 logits [steps, V])."""
    active = jnp.arange(state["pos"].shape[0]) == slot
    step = jax.jit(functools.partial(slots.step, cfg, params, None))
    tok, fed, rows = cur, [], []
    for _ in range(steps):
        fed.append(int(tok[slot]))
        rest = {k: v for k, v in state.items() if k != "pos"}
        logits, rest, *_ = step(tok, rest, state["pos"], active)
        state = {**rest, "pos": state["pos"] + active}
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        rows.append(np.asarray(logits[slot]))
    return fed, np.stack(rows)


def short_prompt_in_a_reused_slot(slots, cfg, params, forward, tol):
    """Through the engine's prefill program: a prompt of 40 tokens in a
    128-row bucket (three of eight segments run) then 8 greedy steps are
    the reference's ``forward(tokens) -> logits [T, V]`` over prompt +
    tokens, in a fresh slot and in one that a 100-token prompt (seven
    segments) filled before: the second stream's logits are the first's
    bit for bit, so it read no row and no state of the longer one (the
    full layers' rows 48 to 127 are zeros over the longer prompt's)."""
    assert slots.prefill_segments(cfg, 128) == 8
    rng = np.random.RandomState(3)
    long_, short = (rng.randint(1, 256, n).astype(np.int32)
                    for n in (100, 40))

    def empty():  # (the program is donated its state and tokens)
        return slots.init_state(cfg, 2, 160), jnp.zeros((2,), jnp.int32)

    used, cur = prefill_slot(cfg, params, *empty(), 1, long_)
    assert np.asarray(used["k_full"][0, 1, 48:100]).any()
    got = []
    for state, cur in (empty(), (used, cur)):
        state, cur = prefill_slot(cfg, params, state, cur, 1, short)
        assert int(state["pos"][1]) == 40
        assert not np.asarray(state["k_full"][:, 1, 48:128]).any()
        got.append(decode_from(slots, cfg, params, state, cur, 1, 8))
    (fed, logits), (fed_reused, logits_reused) = got
    want = np.asarray(forward(list(short) + fed))
    assert fed[0] == int(want[39].argmax())  # (the prefill's own token)
    assert np.abs(logits - want[40:]).max() < tol
    assert fed_reused == fed
    np.testing.assert_array_equal(logits_reused, logits)
