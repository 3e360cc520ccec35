"""What each mechanism of the eighth block (``models/dots.py``) is worth,
on the CPU at a tiny size against the plain reference
(``benchmark/families/dots3_note.reference.py``): whole sequences of a
two-layer cut (a full layer with the dense MLP, a window layer with
experts), the comparison that FAILS with bf16 index scores,
``index_topk - 1`` rows, the gate, the latent's rescale or one row of
the window left out; a ring that reads exactly its window; the eight
shares of an expert layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import dots, moe
from ray_tpu.ops import dsa

# float32 on both sides, the same products in another order (absorbed
# against unabsorbed, a head at a time against all at once): readings of
# 2e-6 to 2e-5 on logits that spread by 0.8
F32_TOL = 1e-4

FAM = manifest.family("dots3_note")
REF = manifest.reference(FAM)
# a full layer (dense MLP) and a window layer (experts): a program of
# two layers compiles in a third of the five layers' time
M = {**FAM.TINY_FIELDS, "n_layers": 2, "layer_pattern": [0, 1]}
W, TOPK = M["sliding_window"], M["index_topk"]


def _cfg(**kw):
    m = {**M, **kw}
    held = m.pop("held_experts")
    return dots.DotsConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"])}, max_seq_len=256,
        prefill_head_groups=2)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, dots.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


@pytest.fixture(scope="module")
def reference(model):
    """(tokens [1, 120], the reference's logits over them)."""
    toks = jnp.asarray(_tokens(4, 1, 120))
    return toks, REF.forward(model[1], toks, M)


def _forward(cfg, params, toks):
    # (a function of its own a call: a patched operation must be traced)
    return jax.jit(lambda p, t: dots.forward(p, t, cfg))(params, toks)


def test_forward_is_the_references_logits(model, reference):
    """120 rows: every full-layer row past the 8th reads a chosen 8, every
    window row past the 9th a band; and a sequence no longer than
    ``index_topk`` is plain causal MLA, bit for bit in the reference
    (the indexer asked for every row gives the same logits)."""
    cfg, params = model
    toks, want = reference
    assert float(jnp.abs(_forward(cfg, params, toks) - want).max()) < F32_TOL
    short = toks[:, :TOPK]
    np.testing.assert_array_equal(
        np.asarray(REF.forward(params, short, M)),
        np.asarray(REF.forward(params, short, {**M, "index_topk": 10**6})))


def _without(what: str, cfg, params, monkeypatch):
    """The program with one mechanism left out or weakened."""
    if what == "gate":
        return dataclasses.replace(cfg, gated_attention=False), params
    if what == "topk_less_one":
        return dataclasses.replace(cfg, index_topk=cfg.index_topk - 1), params
    if what == "window_less_one":
        return dataclasses.replace(
            cfg, sliding_window=cfg.sliding_window - 1), params
    if what == "bf16_index_scores":
        def rounded(fn):
            return lambda *a, **kw: fn(*a, **kw).astype(
                jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(dsa, "index_scores", rounded(dsa.index_scores))
        monkeypatch.setattr(dsa, "index_scores_xla",
                            rounded(dsa.index_scores_xla))
        return cfg, params
    assert what == "r_kv"  # the latent's rescale undone in its norm
    layers = []
    for i, p in enumerate(params["layers"]):
        k = cfg.kind(cfg.windowed(i))
        layers.append({**p, "attn": {**p["attn"], "kv_norm": p["attn"][
            "kv_norm"] / (cfg.d_model / k.kv_lora) ** 0.5}})
    return cfg, {**params, "layers": layers}


@pytest.mark.parametrize("what", ["bf16_index_scores", "topk_less_one",
                                  "gate", "r_kv", "window_less_one"])
def test_the_comparison_fails_without(what, model, reference, monkeypatch):
    """Each moves the logits of the 120-token sequence by far more than
    the tolerance the whole program meets (measured, this seed, two
    layers, logits that spread by 0.8: bf16 index scores 0.34, one row
    fewer 1.36, no gate 1.58, no rescale 0.89, a window of 8 for 9
    1.35; the program itself 2e-6)."""
    cfg, params = model
    toks, want = reference
    cfg_off, params_off = _without(what, cfg, params, monkeypatch)
    off = float(jnp.abs(_forward(cfg_off, params_off, toks) - want).max())
    print(f"\n{what}: logits off by {off:.3g}")
    assert off > 30 * F32_TOL, what


def test_a_ring_reads_exactly_its_window(model):
    """A window layer's step at position 30 with a large key planted
    ``window`` rows back (position 21, whose ring row the step
    overwrites) and ``window - 1`` back (position 22): the first is not
    seen, the second is; and the prefill's banded call agrees with the
    step at the same row."""
    cfg, params = model
    i = 1
    assert cfg.windowed(i)
    k, p = cfg.kind(True), params["layers"][i]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 31, cfg.d_model))
    lat = jnp.zeros((1, 31, k.row_width))
    y, lat = dots._window_segment(cfg, p, x, jnp.int32(0), lat)

    def step_at_30(rows):
        """The step's attention at row 30 over a ring cut from ``rows``
        (positions 0..29), row 30 written by the step itself."""
        ring = dots.ring_rows(rows[:, :30], jnp.array([30]), W)
        q_row, w_v, row, gate, _ = dots._step_inputs(
            cfg, k, p, x[:, 30:], k.rotation(jnp.array([[30]])))
        ring = ring.at[0, 30 % W].set(row[0])
        o = dots._da.attend_latent(q_row, ring, jnp.array([W]), k.kv_lora,
                                   (k.dn + k.dr) ** -0.5)
        return dots._step_out(cfg, p, o, w_v, gate)[0, 0]

    base = step_at_30(lat)
    assert float(jnp.abs(base - y[0, 30]).max()) < F32_TOL
    unseen = step_at_30(lat.at[0, 30 - W].mul(50.0))
    np.testing.assert_array_equal(np.asarray(unseen), np.asarray(base))
    seen = step_at_30(lat.at[0, 30 - W + 1].mul(50.0))
    assert float(jnp.abs(seen - base).max()) > 100 * F32_TOL


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Layer 1's expert layer by the reference: the eight shares of two
    experts each, the shared expert counted once, sum to the layer with
    every expert held; and the program's share is the reference's."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    whole = dataclasses.replace(cfg, held_experts=None)
    p = dots.init_params(whole, jax.random.PRNGKey(7))["layers"][1]["mlp"]
    with jax.default_matmul_precision("highest"):
        uncut = REF.moe_layer({**M, "held_experts": None}, p, x)
        parts = 0
        for first in range(0, 16, 2):
            held = {**p, **{name: p[name][first:first + 2]
                            for name in ("w_gate", "w_up", "w_down")}}
            parts = parts + REF.moe_layer(M, held, x, held=(first, 2),
                                          shared=first == 0)
        assert float(jnp.abs(parts - uncut).max()) < 1e-5
        share = dataclasses.replace(cfg, held_experts=(2, 2))
        held = {**p, **{name: p[name][2:4]
                        for name in ("w_gate", "w_up", "w_down")}}
        got = moe.moe(share, held, x)
        want = REF.moe_layer(M, held, x, held=(2, 2))
    assert float(jnp.abs(got - want).max()) < 1e-5
