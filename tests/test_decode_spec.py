"""Speculative decoding: draft/verify in one engine step (ISSUE 19).

The contract under test: speculation is a LATENCY optimization, never a
distribution change —

- greedy (temperature 0) through the spec kernel is BIT-IDENTICAL to
  the non-speculative path (accept-until-mismatch against the target's
  own argmax token reconstructs exactly the plain sequence);
- sampled streams are a pure function of (weights, prompt, seed)
  REGARDLESS of spec depth, because acceptance is judged against the
  target's own (seed, position) RNG-lane token — the same token the
  plain kernel would emit.  That is what keeps failover seed-replay
  exact with speculation enabled;
- the `serve.spec_verify` chaos site degrades a "drop" pump to the
  plain kernel (retryable by construction: same tokens either way);
- serve_spec_enabled / serve_spec_depth flip speculation live, per
  pump, without rebuilding the engine;
- the zero-init draft head is an exact identity at init.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu._private import config as _cfg  # noqa: E402
from ray_tpu._private import fault_injection as fi  # noqa: E402
from ray_tpu.models import llama, mlp  # noqa: E402
from ray_tpu.models.decode_engine import RaggedDecoder  # noqa: E402

TINY = llama.LlamaConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=64, dtype="float32", remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def _run(params, prompt, n, *, temperature, seed, spec_depth=0,
         draft_layers=1, draft_head=None, extra_streams=0, slots=4,
         chunk=4, rng_seed=99):
    """Decode one stream (optionally amid unrelated concurrent sampled
    streams) and return (tokens, logprobs, engine-stats)."""
    eng = RaggedDecoder(params, TINY, slots=slots, max_len=64,
                        chunk_tokens=chunk, prompt_buckets=(8, 16),
                        spec_depth=spec_depth,
                        spec_draft_layers=draft_layers,
                        spec_draft_head=draft_head)
    rng = np.random.RandomState(rng_seed)
    others = [eng.submit(rng.randint(1, 250, 6).astype(np.int32), n,
                         temperature=0.7, seed=int(rng.randint(2**31)))
              for _ in range(extra_streams)]
    sid = eng.submit(np.asarray(prompt, np.int32), n,
                     temperature=temperature, seed=seed)
    eng.drain()
    s = eng.pop_finished(sid)
    for o in others:
        eng.purge(o)
    return (np.asarray(s.tokens[:n]),
            np.asarray(s.logprobs[:n], np.float32), eng.stats())


@pytest.mark.parametrize("depth", [2, 4])
def test_spec_greedy_bit_identical_to_plain(params, depth):
    """Temperature 0 must reproduce the plain engine's tokens exactly —
    rejected drafts roll back by truncating the slot's cache length,
    and the verify's own argmax fills the first mismatch, so no
    speculative state ever leaks into output."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    base, base_lp, _ = _run(params, prompt, 16, temperature=0.0, seed=5)
    toks, lps, st = _run(params, prompt, 16, temperature=0.0,
                         seed=5, spec_depth=depth)
    np.testing.assert_array_equal(toks, base)
    np.testing.assert_array_equal(lps, base_lp)
    assert st["spec"]["pumps"] > 0


def test_spec_sampled_seed_replay_across_depths(params):
    """The failover contract with speculation ON: one (prompt, seed)
    yields identical tokens whether decoded plain, at depth 2, at
    depth 4, or at depth 4 amid unrelated concurrent streams.  The
    accepted-draft prefix length varies run to run; the emitted
    sequence must not."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    base, base_lp, _ = _run(params, prompt, 14, temperature=0.9,
                            seed=777)
    d2, d2_lp, _ = _run(params, prompt, 14, temperature=0.9, seed=777,
                        spec_depth=2)
    d4, d4_lp, st = _run(params, prompt, 14, temperature=0.9, seed=777,
                         spec_depth=4)
    crowd, crowd_lp, _ = _run(params, prompt, 14, temperature=0.9,
                              seed=777, spec_depth=4, extra_streams=3,
                              rng_seed=41)
    np.testing.assert_array_equal(d2, base)
    np.testing.assert_array_equal(d4, base)
    np.testing.assert_array_equal(crowd, base)
    np.testing.assert_allclose(d4_lp, base_lp, atol=1e-5)
    np.testing.assert_allclose(crowd_lp, base_lp, atol=1e-5)
    # with a real draft trunk some drafts must actually be accepted —
    # otherwise this test exercises nothing
    assert st["spec"]["accepted"] > 0


def test_spec_stats_block(params):
    """stats()["spec"] reports the acceptance telemetry the dashboard
    aggregates: proposed/accepted counters and the per-pump
    accepted-length histogram."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    _, _, st = _run(params, prompt, 16, temperature=0.8, seed=11,
                    spec_depth=4)
    sp = st["spec"]
    assert sp["depth"] == 4 and sp["draft_layers"] == 1
    assert sp["pumps"] > 0
    assert 0 <= sp["accepted"] <= sp["proposed"]
    assert 0.0 <= sp["acceptance_rate"] <= 1.0
    hist = sp["accept_hist"]
    assert hist and all(isinstance(k, str) for k in hist)
    assert sum(hist.values()) > 0


def test_spec_live_flip_via_config(params):
    """serve_spec_enabled gates speculation and serve_spec_depth
    overrides the constructor depth — consulted at every pump, so an
    operator can flip speculation on a live engine."""
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    base, _, _ = _run(params, prompt, 12, temperature=0.0, seed=5)
    try:
        _cfg.set_system_config({"serve_spec_enabled": False})
        toks, _, st = _run(params, prompt, 12, temperature=0.0, seed=5,
                           spec_depth=4)
        np.testing.assert_array_equal(toks, base)
        assert st["spec"]["pumps"] == 0  # gated off: plain path ran
        _cfg.set_system_config({"serve_spec_enabled": True,
                                "serve_spec_depth": 2})
        toks, _, st = _run(params, prompt, 12, temperature=0.0, seed=5,
                           spec_depth=0)  # ctor says off; config wins
        np.testing.assert_array_equal(toks, base)
        assert st["spec"]["pumps"] > 0
    finally:
        _cfg.set_system_config({"serve_spec_enabled": True,
                                "serve_spec_depth": 0})


def test_spec_verify_chaos_drop_falls_back_exact(params):
    """A "drop" at serve.spec_verify degrades that pump to the plain
    kernel — retryable by construction, because the plain path emits
    the exact same tokens.  A bounded "delay" only adds latency."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    base, _, _ = _run(params, prompt, 16, temperature=0.9, seed=31)
    try:
        fi.configure([
            {"site": "serve.spec_verify", "action": "drop", "count": 2},
            {"site": "serve.spec_verify", "action": "delay",
             "delay_s": 0.02, "after": 2, "count": 1},
        ])
        toks, _, st = _run(params, prompt, 16, temperature=0.9,
                           seed=31, spec_depth=4)
        drops = [h for h in fi.hits() if h["action"] == "drop"]
        assert len(drops) == 2
        np.testing.assert_array_equal(toks, base)
        # the dropped pumps ran plain; later pumps speculated again
        assert st["spec"]["pumps"] > 0
    finally:
        fi.clear()


def test_draft_head_zero_init_is_identity(params):
    """mlp.init_draft_head zero-inits the out-projection, so the
    residual adapter is an exact identity at init — an engine built
    with the head stays bit-identical to one without it."""
    head = mlp.init_draft_head(TINY.d_model, jax.random.PRNGKey(7))
    h = jax.random.normal(jax.random.PRNGKey(8), (3, TINY.d_model))
    np.testing.assert_array_equal(
        np.asarray(mlp.apply_draft_head(head, h)), np.asarray(h))
    rng = np.random.RandomState(9)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    base, _, _ = _run(params, prompt, 12, temperature=0.0, seed=5)
    toks, _, _ = _run(params, prompt, 12, temperature=0.0, seed=5,
                      spec_depth=2, draft_head=head)
    np.testing.assert_array_equal(toks, base)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_spec_and_plain_widths_agree_bit_for_bit_on_the_cpu(dtype):
    """At the "1b" WIDTHS with one layer, the depth+1-wide verify and
    the 1-wide step give the same tokens bit for bit: in f32 because
    that is what speculation promises, and on a CPU in bf16 too — the
    dtype every named size serves in — because both widths are one
    helper (``llama_slots._step_logits``) and a CPU rounds a bf16
    product once whatever its width. (While the plain step's head was a
    2-D product and the verify's a 3-D one, the CPU's bf16 parted as the
    chip's does, PR 21.) The chip's MXU rounds the two widths
    differently, and with random weights the top two of 32,128 logits
    are often closer than that rounding: that they part at such
    near-ties ONLY is asserted where it happens, on the chip, by
    ``chip_smoke.serve_phase`` with this same ``spec_parting``;
    ``tests/test_chip_smoke.py`` checks the margins it judges by."""
    import chip_smoke

    got = chip_smoke.spec_parting(
        chip_smoke.model_fields("1b", 288, n_layers=1, dtype=dtype,
                                remat=False, use_flash=False),
        seed=0, slots=8, max_len=288, chunk_tokens=8, bucket=128,
        max_tokens=24)
    assert got["partings"] == [None] * 4
