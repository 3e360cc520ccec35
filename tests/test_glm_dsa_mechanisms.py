"""What each mechanism of the ninth block (``models/glm_dsa.py``) is
worth and WHO READS WHOSE SELECTION, on the CPU at a tiny size against
the plain reference (``benchmark/families/glm_moe_dsa.reference.py``):
whole sequences of the cell's five-layer pattern, the comparison that
FAILS with bf16 index scores, ``index_topk - 1`` rows, a shared layer
that reads a stale selection or none, the experts' scaling left out; a
selection put in an indexer layer's place moves the layers that read it
and no other; the sixteen shares of an expert layer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import glm_dsa, moe
from ray_tpu.ops import dsa

# float32 on both sides, the same products in another order: readings of
# 2e-6 to 3e-5 on logits that spread by 0.8
F32_TOL = 1e-4

FAM = manifest.family("glm_moe_dsa")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
TOPK, ROWS = M["index_topk"], 72


def _cfg(**kw):
    """The family's own way to the program's configuration."""
    return FAM.build({**M, **kw}, max_seq_len=256, remat=False).cfg


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, glm_dsa.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


@pytest.fixture(scope="module")
def reference(model):
    """(tokens [1, 72], the reference's logits over them)."""
    toks = jnp.asarray(_tokens(4, 1, ROWS))
    return toks, REF.forward(model[1], toks, M)


def _forward(cfg, params, toks):
    # (a function of its own a call: a patched operation must be traced)
    return jax.jit(lambda p, t: glm_dsa.forward(p, t, cfg))(params, toks)


def _recent(scores, valid, k, **_):
    """Another selection: a row's ``k`` LATEST valid positions."""
    valid = jnp.broadcast_to(valid, scores.shape)
    n = jnp.sum(valid, axis=-1, keepdims=True)
    return valid & (jnp.arange(scores.shape[-1]) >= n - k)


def _recent_sets(rows: int, k: int):
    """The same selection as the reference takes one: indices [1, T,
    k], ``rows`` in an empty place."""
    at = np.arange(rows)[:, None] - np.arange(k)[None, :]
    return jnp.asarray(np.where(at >= 0, at, rows)[None], jnp.int32)


def _replace_select(monkeypatch, which: int):
    """``dsa.select``'s ``which``-th call of a trace (one an indexer
    layer of a one-segment forward, in the layers' order) gives
    :func:`_recent`."""
    real, calls = dsa.select, []

    def select(scores, valid, k, **kw):
        calls.append(None)
        return (_recent if len(calls) - 1 == which else real)(
            scores, valid, k, **kw)

    monkeypatch.setattr(dsa, "select", select)
    return calls


def _shared_layers_read(monkeypatch, what: str):
    """``dsa.masked_attention``'s calls of a one-segment forward, one a
    layer: the SHARED layers' (calls 2, 3, 4) handed layer 0's bias
    (``stale``) or a causal one (``none``)."""
    real, seen = dsa.masked_attention, []

    def attend(q_n, q_r, k_n, k_r, v, bias, offset, **kw):
        seen.append(bias)
        if len(seen) > 2:
            t, s = bias.shape[1:]
            causal = jnp.arange(s)[None, :] <= jnp.arange(t)[:, None]
            bias = seen[0] if what == "stale" else jnp.where(
                causal, 0.0, dsa.NEG).astype(bias.dtype)[None]
        return real(q_n, q_r, k_n, k_r, v, bias, offset, **kw)

    monkeypatch.setattr(dsa, "masked_attention", attend)
    return seen


def test_forward_is_the_references_logits(model, reference):
    cfg, params = model
    toks, want = reference
    assert float(jnp.abs(_forward(cfg, params, toks) - want).max()) < F32_TOL


def test_with_index_topk_over_the_length_every_layer_is_plain_causal_mla(
        model, reference, monkeypatch):
    """``index_topk`` past the sequence's length: the program's logits
    are the reference's with every causal row read, and they are the
    program's own with every layer's bias replaced by a causal one (no
    selection is left in them)."""
    cfg, params = model
    toks, _ = reference
    every = dataclasses.replace(cfg, index_topk=10**6)
    got = _forward(every, params, toks)
    want = REF.forward(params, toks, {**M, "index_topk": 10**6})
    assert float(jnp.abs(got - want).max()) < F32_TOL
    real = dsa.masked_attention

    def causal(q_n, q_r, k_n, k_r, v, bias, offset, **kw):
        t, s = bias.shape[1:]
        seen = jnp.arange(s)[None, :] <= jnp.arange(t)[:, None]
        return real(q_n, q_r, k_n, k_r, v, jnp.where(
            seen, 0.0, dsa.NEG).astype(bias.dtype)[None], offset, **kw)

    monkeypatch.setattr(dsa, "masked_attention", causal)
    np.testing.assert_array_equal(np.asarray(_forward(every, params, toks)),
                                  np.asarray(got))


def _without(what: str, cfg, params, monkeypatch):
    """The program with one mechanism left out or weakened."""
    if what == "topk_less_one":
        return dataclasses.replace(cfg, index_topk=cfg.index_topk - 1)
    if what == "experts_scaling":
        return dataclasses.replace(cfg, routed_scaling_factor=1.0)
    if what == "bf16_index_scores":
        def rounded(fn):
            return lambda *a, **kw: fn(*a, **kw).astype(
                jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(dsa, "index_scores", rounded(dsa.index_scores))
        monkeypatch.setattr(dsa, "index_scores_xla",
                            rounded(dsa.index_scores_xla))
        return cfg
    assert what in ("shared_read_stale", "shared_read_none")
    _shared_layers_read(monkeypatch, what.rsplit("_", 1)[1])
    return cfg


@pytest.mark.parametrize("what", ["bf16_index_scores", "topk_less_one",
                                  "shared_read_stale", "shared_read_none",
                                  "experts_scaling"])
def test_the_comparison_fails_without(what, model, reference, monkeypatch):
    """Each moves the logits of the 72-token sequence by far more than
    the tolerance the whole program meets: index scores rounded to
    bfloat16 before the selection, one row fewer chosen, the three
    shared layers reading the FIRST indexer layer's selection (a stale
    one) or every causal row (none), the routed experts' 2.5 left
    out."""
    cfg, params = model
    toks, want = reference
    cfg_off = _without(what, cfg, params, monkeypatch)
    off = float(jnp.abs(_forward(cfg_off, params, toks) - want).max())
    print(f"\n{what}: logits off by {off:.3g}")
    assert off > 30 * F32_TOL, what


def test_the_shared_layers_are_handed_their_indexer_layers_bias(
        model, reference, monkeypatch):
    """One segment, five layers, five calls of the masked attention: the
    bias of calls 2, 3 and 4 IS the array call 1 was handed (the second
    indexer layer's), and call 0's is another."""
    cfg, params = model
    seen = _shared_layers_read(monkeypatch, "stale")
    jax.make_jaxpr(lambda p, t: glm_dsa.forward(p, t, cfg))(
        params, reference[0])
    assert len(seen) == cfg.n_layers
    assert seen[2] is seen[1] and seen[3] is seen[1] and seen[4] is seen[1]
    assert seen[0] is not seen[1]


@pytest.mark.parametrize("layer", [0, 1])
def test_a_replaced_selection_is_read_by_the_layers_behind_it(
        layer, model, reference, monkeypatch):
    """Another selection (a row's 8 latest rows) in indexer layer
    ``layer``'s place, in the program (its ``select`` call replaced) and
    in the reference (the same sets handed over as indices): the logits
    agree as closely as the unreplaced ones do, and both have moved.
    For layer 1 that holds only if the program's three shared layers
    read what layer 1 chose; for layer 0 only if they do NOT."""
    cfg, params = model
    toks, plain = reference
    want = REF._head(
        REF.hidden(params, toks, M,
                   replace={layer: _recent_sets(ROWS, TOPK)}),
        params["final_norm"], params["lm_head"], M["rms_eps"])
    calls = _replace_select(monkeypatch, layer)
    got = _forward(cfg, params, toks)
    assert len(calls) == cfg.index_layers
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert float(jnp.abs(want - plain).max()) > 30 * F32_TOL


def test_a_later_indexers_selection_leaves_the_earlier_layers_alone(
        monkeypatch):
    """Five layers as (indexer, shared, shared, indexer, shared), the
    rows every layer leaves in the cache (a layer's rows are made of its
    INPUT): with the second indexer's selection replaced, layers 0 to 3
    leave the same rows bit for bit and layer 4's, behind the replaced
    attention, differ; with the first's replaced, layer 0's stay and
    layers 1 to 4's all differ."""
    cfg = _cfg(indexer_layers=[1, 0, 0, 1, 0])
    assert cfg.share_groups == ((0, 1, 2), (3, 4))
    params = glm_dsa.init_params(cfg, jax.random.PRNGKey(3))
    toks = jnp.asarray(_tokens(6, 1, 40))
    lens = jnp.array([40], jnp.int32)

    def rows():
        _, kept, _ = jax.jit(lambda p, t: glm_dsa.prefill(p, t, lens, cfg))(
            params, toks)
        return [np.asarray(r[0]) for r in kept]

    plain = rows()
    assert [len(r) for r in jax.eval_shape(
        lambda: glm_dsa.prefill(params, toks, lens, cfg))[1]] \
        == [2, 1, 1, 2, 1]
    _replace_select(monkeypatch, 1)
    later = rows()
    for i in range(4):
        np.testing.assert_array_equal(later[i], plain[i])
    assert np.abs(later[4] - plain[4]).max() > 30 * F32_TOL
    monkeypatch.undo()
    _replace_select(monkeypatch, 0)
    first = rows()
    np.testing.assert_array_equal(first[0], plain[0])
    for i in range(1, 5):
        assert np.abs(first[i] - plain[i]).max() > 30 * F32_TOL, i


def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Layer 1's expert layer by the reference: the sixteen shares of one
    expert each, the shared expert counted once, sum to the layer with
    every expert held; and the program's share is the reference's."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    whole = dataclasses.replace(cfg, held_experts=None)
    p = glm_dsa.init_params(whole, jax.random.PRNGKey(7))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 16
    with jax.default_matmul_precision("highest"):
        uncut = REF.moe_layer({**M, "held_experts": None}, p, x)
        parts = 0
        for first in range(16):
            held = {**p, **{name: p[name][first:first + 1]
                            for name in ("w_gate", "w_up", "w_down")}}
            parts = parts + REF.moe_layer(M, held, x, held=(first, 1),
                                          shared=first == 0)
        assert float(jnp.abs(parts - uncut).max()) < 1e-5
        share = dataclasses.replace(cfg, held_experts=(2, 2))
        held = {**p, **{name: p[name][2:4]
                        for name in ("w_gate", "w_up", "w_down")}}
        got = moe.moe(share, held, x)
        want = REF.moe_layer(M, held, x, held=(2, 2))
    assert float(jnp.abs(got - want).max()) < 1e-5
