"""The compact branch of the shared expert layer (``moe.moe``): a device
that holds a small part of the experts gathers and multiplies the first
C rows of the sort when its held assignments fit there, every
assignment's row when they do not, and C comes from shapes alone
(``moe.compact_rows``).

A made-up router sends each token to a number of held experts the case
chooses, so that the count of held assignments is known to the row; the
branch's result is held against today's lines (``moe._held_part``
without a capacity, which is also its fall-back) on the CPU,
``ragged_dot`` for the grouped matmul.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import mimo, moe
from ray_tpu.ops.grouped_matmul import TILE_M, grouped_matmul

F32_TOL = 1e-4  # the blocks' tolerance against their references
TOKENS, TOP_K, EXPERTS, COUNT, D = 128, 8, 64, 8, 32
N = TOKENS * TOP_K
C = 256  # 2 * N * COUNT / EXPERTS, a whole tile; 4 * C = N


def _cfg(first=0, dtype="float32"):
    return mimo.MimoConfig(
        vocab_size=64, d_model=D, n_layers=1, n_heads=2, n_kv_heads=1,
        window_kv_heads=1, head_dim=24, v_head_dim=16, rotary_dim=8,
        dense_d_ff=64, d_ff=16, n_experts=EXPERTS, top_k=TOP_K,
        held_experts=(first, COUNT), routed_scaling_factor=2.5, dtype=dtype)


def _layer(cfg, held_of_token, seed=0):
    """Leaves and tokens such that token i chooses ``held_of_token[i]``
    held experts and foreign ones for the rest of its ``top_k``: the
    first 9 numbers of a token say which kind it is (how many held it
    takes), the router reads those alone and likes the kind's experts by
    6 to 9 logits; the other numbers are noise the experts multiply."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    mat, _ = moe.makers(cfg, keys)
    p = moe.init_experts(cfg, mat, keys)
    first, count = cfg.held
    rng = np.random.default_rng(seed)
    foreign = [e for e in range(EXPERTS) if not first <= e < first + count]
    router = np.full((D, EXPERTS), 0.0, np.float32)
    for kind in range(TOP_K + 1):
        chosen = list(rng.permutation(count)[:kind] + first) + list(
            rng.permutation(foreign)[:TOP_K - kind])
        router[kind] = -6.0
        router[kind, chosen] = rng.uniform(0.0, 3.0, TOP_K)
    p["router"] = jnp.asarray(router, cfg.compute_dtype)
    p["router_bias"] = jnp.zeros((EXPERTS,), jnp.float32)
    x = rng.normal(size=(TOKENS, D)).astype(np.float32)
    x[:, :TOP_K + 1] = np.eye(TOP_K + 1)[np.asarray(held_of_token)]
    return p, jnp.asarray(x[None], cfg.compute_dtype)


def _todays_lines(cfg, p, x):
    """``moe._held_part`` at every assignment's row behind the router
    and the sort: what ``moe`` is where the rule gives no capacity, and
    the branch's fall-back."""
    xf = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(jnp.dot(xf, p["router"],
                                    preferred_element_type=jnp.float32))
    weights, ids = moe.route(cfg, scores, p["router_bias"])
    held, order, group_sizes = moe.held_first(cfg, ids)
    return moe._held_part(cfg, grouped_matmul, None, p["w_gate"], p["w_up"],
                          p["w_down"], xf, weights, held, order,
                          group_sizes).reshape(x.shape), group_sizes


def _with_aux(cfg, p, x):
    aux = {}
    return moe.moe(cfg, p, x, aux), aux


def _spread(total):
    """``total`` held assignments over the tokens, at most COUNT each."""
    each = np.full(TOKENS, total // TOKENS)
    each[:total % TOKENS] += 1
    return each


CASES = {
    "no_held_assignment": (np.zeros(TOKENS, int), 0, True),
    "some": (np.arange(TOKENS) % 4, 192, True),
    "exactly_c": (_spread(C), C, True),
    "c_plus_1_falls_back": (_spread(C + 1), C + 1, False),
    "every_assignment_held": (np.full(TOKENS, TOP_K), N, False),
    "three_and_more_a_token": (np.where(np.arange(TOKENS) < 48, 5, 0), 240,
                               True),
}


@pytest.mark.parametrize("first", [0, 24], ids=["held_from_0", "held_from_24"])
@pytest.mark.parametrize("case", CASES)
def test_the_branch_gives_todays_lines_result(case, first):
    held_of_token, n_held, compact = CASES[case]
    cfg = _cfg(first)
    assert moe.compact_rows(cfg, N) == C
    p, x = _layer(cfg, held_of_token)
    out, aux = jax.jit(lambda p, x: _with_aux(cfg, p, x))(p, x)
    ref, group_sizes = jax.jit(
        lambda p, x: _todays_lines(cfg, p, x))(p, x)
    assert int(group_sizes.sum()) == n_held
    held_ids = (aux["expert_ids"] >= first) & (aux["expert_ids"]
                                               < first + COUNT)
    np.testing.assert_array_equal(held_ids.sum(-1)[0], held_of_token)
    assert int(aux["compact"]) == compact
    out, ref = np.asarray(out[0]), np.asarray(ref[0])
    # the fall-back IS today's lines, and the compact branch puts the
    # same rows back at the same places before the same sum: the bits
    np.testing.assert_array_equal(out, ref)
    assert not out[held_of_token == 0].any()
    if n_held:
        assert np.abs(ref).max() > 0.01


def test_the_branch_in_the_serving_type_gives_todays_bits():
    """bfloat16: a held row's three products are today's bits (the same
    groups at the same offsets) and so is the float32 sum of a token's
    rows, put back in ``top_k``'s order."""
    cfg = _cfg(8, "bfloat16")
    p, x = _layer(cfg, np.arange(TOKENS) % 5, seed=3)
    out = jax.jit(lambda p, x: moe.moe(cfg, p, x))(p, x)
    ref, _ = jax.jit(lambda p, x: _todays_lines(cfg, p, x))(p, x)
    out, ref = (np.asarray(a[0], np.float32) for a in (out, ref))
    assert np.abs(ref).max() > 0.01
    np.testing.assert_array_equal(out, ref)


def test_where_the_rule_gives_no_capacity_there_is_no_branch():
    """A decode step's 8 rows: ``moe`` is today's lines, no ``cond`` in
    the program and nothing in ``aux`` but the ids."""
    cfg = _cfg()
    p, x = _layer(cfg, np.arange(TOKENS) % 4)
    x = x[:, :8]
    assert moe.compact_rows(cfg, 8 * TOP_K) is None
    jaxpr, (_, aux) = jax.make_jaxpr(
        lambda p, x: _with_aux(cfg, p, x), return_shape=True)(p, x)
    assert "cond" not in str(jaxpr) and set(aux) == {"expert_ids"}
    np.testing.assert_array_equal(
        jax.jit(lambda p, x: moe.moe(cfg, p, x))(p, x),
        jax.jit(lambda p, x: _todays_lines(cfg, p, x)[0])(p, x))


def test_the_branch_differentiates_as_todays_lines_do():
    cfg = _cfg()
    p, x = _layer(cfg, np.arange(TOKENS) % 3)

    def loss(fn, w_down, x):
        return jnp.sum(fn(cfg, {**p, "w_down": w_down}, x) ** 2)

    got = jax.grad(lambda *a: loss(moe.moe, *a), (0, 1))(p["w_down"], x)
    want = jax.grad(lambda *a: loss(
        lambda *b: _todays_lines(*b)[0], *a), (0, 1))(p["w_down"], x)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=F32_TOL)


def _cell_cfg(config):
    fam, m = manifest.model(config)
    return fam.build(m, max_seq_len=4096, remat=False).cfg


# (configuration, tokens of the call, C or None): ISSUE 53's table
RULE = [
    ("mimo-v2.5-ep16-1chip", 2048, 2048),
    ("solar-open2-250b-ep8-1chip", 2048, 4096),
    ("k-exaone-236b-a23b-ep8-1chip", 1024, 2048),
    ("k-exaone-236b-a23b-ep8-1chip", 512, 1024),
    ("k-exaone-236b-a23b-ep8-1chip", 256, 512),
    ("mimo-v2.5-ep16-1chip", 32, None),  # decode steps: 32 or 64 slots
    ("solar-open2-250b-ep8-1chip", 32, None),
    ("k-exaone-236b-a23b-ep8-1chip", 64, None),
    ("ling-3.0-flash-vl-ep4-1chip", 1024, None),
    ("ling-3.0-flash-vl-ep4-1chip", 64, None),
    ("instella-moe-16b-a3b-pp4-1chip", 16384, None),
    ("instella-moe-16b-a3b-pp4-1chip", 32, None),
]


@pytest.mark.parametrize("config,tokens,c", RULE)
def test_the_capacity_the_shapes_give(config, tokens, c):
    cfg = _cell_cfg(config)
    assert moe.compact_rows(cfg, tokens * cfg.top_k) == c


def test_the_capacity_is_twice_the_uniform_share_in_whole_tiles():
    cfg = _cfg()
    sixteenth = dataclasses.replace(cfg, held_experts=(8, 4))
    for n in (1024, 5000, 16384, 100_000):
        c = moe.compact_rows(sixteenth, n)
        assert c % TILE_M == 0 and 0 <= c - 2 * n * 4 / EXPERTS < TILE_M
    # an eighth held: C is a quarter of n where that is whole tiles
    assert moe.compact_rows(cfg, 1024) == 256
    assert moe.compact_rows(cfg, 1016) is None
    assert moe.compact_rows(cfg, 1032) is None
    # a device that holds a quarter of the experts, or all, has none
    for count in (16, 64):
        assert moe.compact_rows(dataclasses.replace(
            cfg, held_experts=(0, count)), 1 << 20) is None


@pytest.mark.parametrize("family", ["mimo_v2", "solar_open2", "exaone_moe"])
def test_an_engines_prefills_count_their_compact_calls(family, monkeypatch):
    """A block at its family's tiny fields but 8 of 64 experts held and
    ``top_k`` 8, so that a 128-row segment (or prompt) gives the expert
    layer a capacity: the engine serves the reference's tokens through
    the compact branch, and the read-back's span carries the prefills'
    expert-layer calls (one a sparse layer and LIVE segment) and how
    many of them took the branch."""
    from ray_tpu._private import flight_recorder as fr
    from ray_tpu.models.decode_engine import RaggedDecoder, slot_model

    fam = manifest.family(family)
    ref = manifest.reference(fam)
    m = {**fam.TINY_FIELDS, "n_experts": 64, "top_k": 8,
         "held_experts": [8, 8]}
    prog = fam.build(m, max_seq_len=320, remat=False)
    cfg = prog.cfg
    assert moe.compact_rows(cfg, 128 * 8) == 256
    params = prog.init_params(jax.random.PRNGKey(5))
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 128)
    eng = RaggedDecoder(params, cfg, slots=2, max_len=288, chunk_tokens=4,
                        prompt_buckets=(128, 256, 512),
                        name=f"compact-{family}")
    rng = np.random.RandomState(2)
    asked = [rng.randint(1, 256, n).astype(np.int32) for n in (100, 200)]
    mark = fr._get().recorded  # (the ring is bounded: count, not place)
    sids = [eng.submit(p, 6) for p in asked]
    eng.drain()
    for sid, p in zip(sids, asked):
        check = ref.check_served_tokens(
            params, list(p), list(eng.finished[sid].tokens), m)
        assert check["wrong"] == 0 and check["agree"] == 6, check
    backs = [s["attrs"] for s in list(fr._get().ring)[mark - fr._get().recorded:]
             if s["name"] == "engine.readback"
             and "moe_expert_calls" in s["attrs"]]
    # 100 rows: one segment of its 128-row bucket; 200: both of 256's
    # (an unsegmented block: one call a prompt at its bucket's rows)
    live = 1 + slot_model(cfg).prefill_segments(cfg, 256)
    assert live == (2 if family == "exaone_moe" else 3)
    calls = sum(b["moe_expert_calls"] for b in backs)
    assert calls == cfg.moe_layers * live
    assert sum(b["moe_compact_calls"] for b in backs) == calls
    assert all("expert_load_max" in b for b in backs)
