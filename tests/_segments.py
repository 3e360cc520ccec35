"""What the tests of the segmented blocks (``test_solar_block.py``,
``test_mimo_block.py``, ``test_sparse_blocks.py``) share: buckets cut
into segments of 16 rows, one prompt through the engine's prefill
program into a slot, greedy steps of that slot alone,
the comparison of a short prompt in a long bucket (dead segments behind
it, ``moe.in_segments``) with the reference, what holds a sparse
layer's k and v to the rows its segment can see (:func:`live_kv_case`),
and which kernels a traced program calls (:func:`pallas_calls`).

A test that changes what a program reads when it is traced (a module's
constant, a function of a block) calls :func:`forget_programs` and not
``jax.clear_caches()``: the latter also throws away every operation
the reference has compiled, and the rest of the file pays for them
again.
"""

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode_engine as de
from ray_tpu.models import moe


def forget_programs():
    """Drops the traces of this repo's own jitted functions (the
    engine's programs, cached by cfg alone, and the few of
    ``ray_tpu.models`` and ``ray_tpu.ops``): the next call of each
    reads the modules as they now are. A ``jax.jit`` a test makes is
    new each time and has nothing to forget."""
    for name, module in list(sys.modules.items()):
        if name.startswith("ray_tpu."):
            for fn in list(vars(module).values()):
                if callable(getattr(fn, "clear_cache", None)):
                    fn.clear_cache()


def pallas_calls(jaxpr) -> list:
    """(name, results) of every ``pallas_call`` in a jaxpr, the ones
    inside its equations' own jaxprs among them."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], len(eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += pallas_calls(sub)
    return out


@pytest.fixture
def segments_of_16(monkeypatch):
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    forget_programs()
    yield
    forget_programs()


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


# ------------------------------------ a sparse layer's k and v (PR 61)

def whole_bucket_kv(k, lat_all, w_g, start, seg, bufs):
    """``dots._live_kv``'s place as the blocks filled it before PR 61:
    a group's k_nope and v of EVERY row of the bucket, each segment."""
    lat = lat_all[..., :k.kv_lora]
    return tuple(jnp.einsum(
        "bsr,rhd->bhsd", lat, w, preferred_element_type=jnp.float32
    ).astype(lat.dtype) for w in (w_g[..., :k.dn], w_g[..., k.dn:]))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def live_kv_case(case: str, monkeypatch, block, kind, layers: int, cfg,
                 params, tokens):
    """One prompt of 40 rows in a 64-row bucket of 16-row segments (four
    segments, ``live`` ends inside the third) through ``block.prefill``,
    ``layers`` of whose layers attend through ``kind``'s sparse MLA:

    - ``whole_bucket``: h and every layer's rows are what the parent's
      form gives (:func:`whole_bucket_kv` in the helper's place) to
      float32 rounding: XLA's CPU backend picks a product's loop by its
      shape, so a chunk's 16 rows of k are not the bits of the same rows
      of a 64-row product (2e-6 apart, read here; h then by 2.4e-6 on
      values of 3; a chunk left out moves h by 1e-2 and more);
    - ``stale_1e4``: bit for bit what it gives with the pair full of 1e4
      before each segment instead of zeros: nothing behind ``start +
      seg`` is read with weight;
    - ``lowered``: every product with a group's ``w_kvb`` (k_nope's
      half or v's) in the traced program has one segment's 16 rows of
      latents as its other operand, none the bucket's 64."""
    from ray_tpu.models import dots

    lens, live = jnp.array([40], jnp.int32), jnp.int32(40)

    def run(p, t):
        return block.prefill(p, t, lens, cfg, live=live)[:2]

    def prefill():  # (traced anew: the patches are read at trace time)
        return jax.jit(run)(params, tokens)

    if case == "lowered":
        hg = kind.heads // math.gcd(cfg.prefill_head_groups, kind.heads)
        halves = {(kind.kv_lora, hg, kind.dn), (kind.kv_lora, hg, kind.dv)}
        others = [
            tuple(v.aval.shape for v in eqn.invars if v.aval.shape not in
                  halves)
            for eqn in _equations(jax.make_jaxpr(run)(params, tokens).jaxpr)
            if eqn.primitive.name == "dot_general"
            and halves & {v.aval.shape for v in eqn.invars}]
        assert len(others) == 2 * layers, others  # (k_nope's and v's)
        assert set(others) == {((1, 16, kind.kv_lora),)}, others
        return
    want = prefill()
    assert np.asarray(want[0][:, :40]).any()
    assert not np.asarray(want[0][:, 48:]).any()  # (the dead segment)
    if case == "stale_1e4":
        monkeypatch.setattr(dots, "_kv_buffers", lambda k, b, h, t, dt: (
            jnp.full((b, h, t, k.dn), 1e4, dt),
            jnp.full((b, h, t, k.dv), 1e4, dt)))
        same = np.testing.assert_array_equal
    else:
        monkeypatch.setattr(dots, "_live_kv", whole_bucket_kv)
        same = functools.partial(np.testing.assert_allclose, rtol=0,
                                 atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(prefill()),
                    jax.tree_util.tree_leaves(want), strict=True):
        same(np.asarray(a), np.asarray(b))
