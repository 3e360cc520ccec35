"""A decoder whose attention layers are of two kinds, sliding-window and
full, with a sigmoid router over experts of which this device holds a
part. The language model of K-EXAONE-236B-A23B (``model_type``
``exaone_moe``) as its ``config.json`` gives it; the third block beside
``llama.py`` and ``ling.py``.

The pattern is data: ``layer_types[i]`` is ``sliding_attention`` or
``full_attention`` (published: three sliding, one full, repeated),
``mlp_layer_types[i]`` is ``dense`` or ``sparse`` (published: one dense
layer, then experts). The layers are NOT a stack scanned by one loop:
each is its own dict of leaves and the programs unroll them.

- **Attention**, every layer: q of ``n_heads`` x ``head_dim`` and k, v of
  ``n_kv_heads`` x ``head_dim`` from one product (``head_dim`` is a
  field: 64 x 128 is not the hidden size); a learned RMS norm over each
  head's ``head_dim`` of q and of k; causal softmax over q k^T /
  sqrt(head_dim); heads h = kv * group + r share kv head ``kv``. A
  **sliding** layer rotates q and k (RoPE, rotate-half) and query
  position p sees keys p - window + 1 ... p; a **full** layer carries no
  position encoding and sees every key <= p.
- **MLP**: a dense SwiGLU, or the expert layer of ``models/moe.py``
  (sigmoid scores in float32, a selection-only bias, no group limit,
  ``top_k`` chosen, renormalised and scaled, a shared expert beside
  them; ``held_experts = (first, count)``: the part this device
  computes).
- Pre-norm: x' = RMSNorm(x) into attention and into the MLP, both added
  to the stream.

A slot's state in the serving engine is this model's own
(:data:`SLOTS`, what ``decode_engine.slot_model`` finds through
``ExaoneConfig.slot_model``): rows of k and v in two shapes side by
side. The full layers keep ``max_len`` rows a slot, ``[L_full, slots,
max_len, Hkv * hd]``, written at ``pos``; the sliding layers keep
``sliding_window`` rows a slot, ``[L_sliding, slots, window, Hkv *
hd]``, a RING written at ``pos % window``: k is stored rotated, and
attention over a set of rows does not care for their order, so a ring
that holds the last ``window`` positions IS the layer's view. A decode
step attends with the engine's one kernel (``ops.decode_attention``) on
both stacks: the ring's lengths are ``min(pos + 1, window)``, the full
stack's ``pos + 1``; the two sets of visits are made once a step, before
the layers. A ring cannot give back the rows of an earlier position, so
the prefix cache, speculative decoding and the prefill workers refuse
this model by name (``rows_state``).

Types: matrices in ``dtype`` (bf16 as published), products accumulated
in float32; norm vectors and the router's bias float32; router scores
and softmax float32. :func:`init_params` makes the tree in those types
leaf by leaf, in blocks (``moe.draw``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary, rotary_embedding

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class ExaoneConfig(moe.HeldExperts):
    vocab_size: int = 153600
    d_model: int = 6144
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    # a layer's attention / MLP kind, one entry a layer; () = the
    # published pattern (three sliding then one full; one dense MLP,
    # then experts)
    layer_types: tuple = ()
    mlp_layer_types: tuple = ()
    sliding_window: int = 128
    dense_d_ff: int = 18432
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 2048
    shared_d_ff: int = 2048
    n_experts: int = 128
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        n = self.n_layers
        attn = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else SLIDING for i in range(n))
        mlp = tuple(self.mlp_layer_types) or tuple(
            DENSE if i == 0 else SPARSE for i in range(n))
        if len(attn) != n or len(mlp) != n \
                or set(attn) - {SLIDING, FULL} or set(mlp) - {DENSE, SPARSE}:
            raise ValueError(
                f"{n} layers need {n} layer_types of {SLIDING!r} / "
                f"{FULL!r} and {n} mlp_layer_types of {DENSE!r} / "
                f"{SPARSE!r}, not {attn} and {mlp}")
        object.__setattr__(self, "layer_types", attn)
        object.__setattr__(self, "mlp_layer_types", mlp)

    @property
    def kv_width(self) -> int:
        """What a cache row holds: the position's kv heads end to end."""
        return self.n_kv_heads * self.head_dim

    def windowed(self, i: int) -> bool:
        return self.layer_types[i] == SLIDING

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place in the stack of its kind."""
        return self.layer_types[:i].count(self.layer_types[i])

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def moe_layers(self) -> int:
        return self.mlp_layer_types.count(SPARSE)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "ExaoneConfig":
        """Test-size config: both kinds of layer and of MLP, a window
        smaller than the sequences, heads x head_dim unequal to the
        hidden size; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=5, n_heads=8, n_kv_heads=2,
            head_dim=16, layer_types=(SLIDING, SLIDING, SLIDING, FULL,
                                      SLIDING),
            sliding_window=8, dense_d_ff=96, d_ff=32, shared_d_ff=32,
            n_experts=16, top_k=4, rope_theta=1e4, max_seq_len=128,
            dtype="float32")
        base.update(kw)
        return ExaoneConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: ExaoneConfig, key):
    """The tree in the SERVING types (module docstring). Matrices are
    normal / sqrt(fan_in), and every ``w_down`` (the MLPs' writes into
    the residual stream) is scaled by (2 x depth)^-1/2 besides (GPT-2's
    and Megatron's scaled initialisation; depth is ``published_layers``,
    the model's own where the configuration is cut in depth:
    ``ling.init_params`` says what the scaling is for: an expert that a
    router near-tie flips must not move the stream far). The attention's
    ``wo`` is NOT scaled so: scaled, a layer's attention (a mean over up
    to 128 rows) adds 1.5% to a stream that the token's own embedding
    dominates, the next greedy token is all but a function of the last
    one, and decoding falls into short cycles that the slots end up
    sharing. In one seed of seven the 64 slots of the cell emitted 21
    distinct tokens after 2,000 steps and a step touched 11.7 of the 16
    held experts, not 15.7, so that seed's run was a tenth faster than
    the others'; unscaled, ten seeds keep 59-64 distinct tokens and
    15.6-15.7 experts throughout (my chip runs, PR 34): what a step
    reads must not hang on the seed. The norm scales are drawn around 1
    and the router's bias away from 0, so that a part left out of a path
    shows against the reference."""
    d, hd = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(key, 16 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def attention():
        return {
            "w_qkv": mat(d, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
            "q_norm": around_one(hd), "k_norm": around_one(hd),
            "wo": mat(cfg.n_heads * hd, d),
        }

    layers = [{
        "attn_norm": around_one(d), "attn": attention(),
        "mlp_norm": around_one(d),
        "mlp": moe.init_dense(cfg, mat) if kind == DENSE
        else moe.init_experts(cfg, mat, keys),
    } for kind in cfg.mlp_layer_types]
    return moe.init_model(cfg, mat, around_one, keys, layers)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def _qkv(cfg: ExaoneConfig, p, x, rotation):
    """x [B, T, D] (normed) -> (q [B, T, Hq, hd], k, v [B, T, Hkv, hd]):
    one product, the head-wise norms of q and k, and with ``rotation`` =
    (sin, cos) of the rows' positions (a sliding layer) the rotation of
    both; ``None`` (a full layer) leaves them unrotated."""
    b, t, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = x @ p["w_qkv"]
    q = qkv[..., :hq * hd].reshape(b, t, hq, hd)
    k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd)
    q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if rotation is not None:
        q, k = apply_rotary(q, *rotation), apply_rotary(k, *rotation)
    return q, k, v


def _attend_prompt(cfg: ExaoneConfig, q, k, v, window: int):
    """Causal attention of whole prompts from position 0, the query
    heads grouped by the kv head they share (no repeated copy); with
    ``window`` > 0 the band: query p sees keys p - window + 1 ... p.
    -> [B, T, Hq, hd]."""
    b, t, hq, hd = q.shape
    qg = q.reshape(b, t, cfg.n_kv_heads, hq // cfg.n_kv_heads, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    pos = jnp.arange(t)
    seen = pos[None, :] <= pos[:, None]  # [T, S]
    if window:
        seen &= pos[:, None] - pos[None, :] < window
    probs = jax.nn.softmax(jnp.where(seen, logits, _NEG), axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", probs.astype(q.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(b, t, hq, hd)


def ring_rows(rows, true_lens, window: int):
    """What a prompt leaves in a ring of ``window`` rows: ``rows`` [F,
    P, C] of positions 0..P-1 (``true_lens`` [F] of them real) -> [F,
    window, C], ring row r holding the LAST real position p with
    ``p % window == r`` and zeros where there is none (a prompt shorter
    than the window fills a part of it)."""
    r = jnp.arange(window, dtype=jnp.int32)[None, :]
    back = true_lens[:, None] - 1 - r  # >= 0: some position lands on r
    p = r + window * (back // window)
    got = jnp.take_along_axis(
        rows, jnp.clip(p, 0, rows.shape[1] - 1)[..., None], axis=1)
    return jnp.where((back >= 0)[..., None], got, 0)


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def _attn_scope(cfg: ExaoneConfig, i: int):
    """Attention proper of layer ``i``: ``attn``, its kind beneath."""
    return jax.named_scope(
        "attn/attn_window" if cfg.windowed(i) else "attn/attn_full")


def prefill(params, tokens, cfg: ExaoneConfig, aux: dict | None = None):
    """tokens [B, T] from position 0 (right-padding sees nothing real
    behind it: causal) -> (h [B, T, D] before the final norm, every
    layer's (k, v) rows [B, T, Hkv * hd], k rotated where the layer
    rotates). With ``aux`` every expert layer's ids are left in
    ``aux["expert_ids"]`` [L_moe, B, T, top_k] and the layers' calls
    and compact calls in ``aux["compact_calls"]`` [2]
    (``moe.compact_calls``)."""
    b, t = tokens.shape
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    with jax.named_scope("qkv"):
        rotation = rotary_embedding(
            jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t)),
            cfg.head_dim, cfg.rope_theta)
    rows, auxes = [], []
    for i, p in enumerate(params["layers"]):
        windowed = cfg.windowed(i)
        with jax.named_scope("qkv"):
            q, k, v = _qkv(cfg, p["attn"],
                           rms_norm(h, p["attn_norm"], cfg.rms_eps),
                           rotation if windowed else None)
        with _attn_scope(cfg, i):
            o = _attend_prompt(cfg, q, k, v,
                               cfg.sliding_window if windowed else 0)
        with jax.named_scope("attn_out"):
            h = h + o.reshape(b, t, -1) @ p["attn"]["wo"]
        rows.append((k.reshape(b, t, -1), v.reshape(b, t, -1)))
        layer_aux = {} if aux is not None else None
        h = moe.mlp_layer(cfg, cfg.mlp_layer_types[i] == SPARSE, p, h,
                          layer_aux)
        if layer_aux:
            auxes.append(layer_aux)
    if auxes:
        aux["expert_ids"] = jnp.stack([a["expert_ids"] for a in auxes])
        aux["compact_calls"] = moe.compact_calls(auxes)
    return h, rows


def forward(params, tokens, cfg: ExaoneConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    band mask in the sliding layers."""
    return moe.logits(cfg, params, prefill(params, tokens, cfg)[0])


loss_fn = moe.loss_fn(forward)


def step(cfg: ExaoneConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` the two pairs of stacks (:meth:`_Slots.init_state`, without
    ``pos``). A sliding layer writes its B new rows at ``[layer, slot,
    pos % window]`` of the ring and attends over the slot's
    ``min(pos + 1, window)`` rows; a full layer writes at ``[layer,
    slot, pos]`` and attends over ``pos + 1`` rows; an inactive slot
    attends over nothing (its length is 0, its output zeros). Both with
    ``ops.decode_attention`` on the stack in place, the kernel's visits
    made here once for each kind, before the layers. -> (float32 logits
    [B, V], the state updated, and for a model with expert layers three
    [L_moe] int32 counters of the ACTIVE slots' routing: distinct held
    experts touched, assignments, assignments to held experts)."""
    b = tok.shape[0]
    w = cfg.sliding_window
    slots = jnp.arange(b)
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    with jax.named_scope("qkv"):
        rotation = rotary_embedding(pos[:, None], cfg.head_dim,
                                    cfg.rope_theta)
    # by kind of layer (sliding or not): the stacks' names, the row a
    # slot writes, and the rows it holds once written with the kernel's
    # visits for them
    def kind(names, row, held):
        lengths = jnp.where(active, held, 0).astype(jnp.int32)
        return names, row, lengths, _da.visits(
            lengths, state[names[0]].shape[2])

    with jax.named_scope("attn"):
        by_kind = {True: kind(("k_win", "v_win"), pos % w,
                              jnp.minimum(pos + 1, w)),
                   False: kind(("k_full", "v_full"), pos, pos + 1)}
    state = dict(state)
    counts = []
    for i, p in enumerate(params["layers"]):
        windowed = cfg.windowed(i)
        (kn, vn), row, lengths, plan = by_kind[windowed]
        layer = cfg.stack_index(i)
        with jax.named_scope("qkv"):
            q, k, v = _qkv(cfg, p["attn"],
                           rms_norm(h, p["attn_norm"], cfg.rms_eps),
                           rotation if windowed else None)
        with jax.named_scope("cache"):
            state[kn] = state[kn].at[layer, slots, row].set(
                k.reshape(b, -1))
            state[vn] = state[vn].at[layer, slots, row].set(
                v.reshape(b, -1))
        with _attn_scope(cfg, i):
            o = _da.decode_attention(q, state[kn], state[vn], layer,
                                     lengths, plan=plan)
        with jax.named_scope("attn_out"):
            h = h + o.reshape(b, 1, -1) @ p["attn"]["wo"]
        sparse = cfg.mlp_layer_types[i] == SPARSE
        aux = {} if sparse else None
        h = moe.mlp_layer(cfg, sparse, p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return moe.logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """Two pairs of stacks: the full layers' rows and the sliding
    layers' rings, which cannot be cut or rewound at a position."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm",
                  "router_bias")

    @staticmethod
    def row_kinds(cfg: ExaoneConfig) -> dict:
        return {"window": (cfg.window_layers, cfg.sliding_window),
                "full": (cfg.full_layers, None)}

    @staticmethod
    def init_state(cfg: ExaoneConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        full = (cfg.full_layers, slots, max_len, cfg.kv_width)
        ring = (cfg.window_layers, slots, cfg.sliding_window, cfg.kv_width)
        return {"k_full": jnp.zeros(full, cdt), "v_full": jnp.zeros(full, cdt),
                "k_win": jnp.zeros(ring, cdt), "v_win": jnp.zeros(ring, cdt),
                "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k_full"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def both(kind):  # (by shape: the state may be described only)
            k = state["k_" + kind]
            return 2 * k.size * k.dtype.itemsize

        return {"window": both("win"), "full": both("full")}

    @staticmethod
    def step(cfg: ExaoneConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: ExaoneConfig, slot_len: int, prefix=None):
        """Whole prompts from position 0. Of a prompt's rows the full
        layers keep all (the bucket's padding among them: a decode step
        overwrites a pad row at its position before the growing mask can
        expose it), the sliding layers the last ``window`` real ones, at
        their ring offsets (:func:`ring_rows`). -> (the streams' rows by
        kind, [F] prompt lengths, [F] first tokens, [F] their logprobs,
        the held experts' assignments from the real positions [L_moe,
        count], the expert layer's calls and compact calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        aux = {} if cfg.moe_layers else None
        h, rows = prefill(params, prompts, cfg, aux)
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        f = prompts.shape[0]

        def stack(parts, rows_each):  # (no layer of a kind: no rows)
            return jnp.stack(parts) if parts else jnp.zeros(
                (0, f, rows_each, cfg.kv_width), cfg.compute_dtype)

        w = cfg.sliding_window
        streams = {}
        with jax.named_scope("cache"):
            for j, name in enumerate(("k", "v")):
                streams[name + "_full"] = stack(
                    [r[j] for i, r in enumerate(rows)
                     if not cfg.windowed(i)], prompts.shape[1])
                streams[name + "_win"] = stack(
                    [ring_rows(r[j], true_lens, w)
                     for i, r in enumerate(rows) if cfg.windowed(i)], w)
        loads = (moe.prefill_loads(cfg, aux["expert_ids"], true_lens),
                 aux["compact_calls"]) if aux else ()
        return streams, true_lens, toks0, logp0, *loads

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' rows into their slots, every row of a
        slot replaced (a prompt's rows, zeros behind; the whole ring)."""
        def put(all_, new):  # [L, slots, S, C] <- [L, F, P <= S, C]
            whole = jnp.zeros(
                (all_.shape[0], new.shape[1], *all_.shape[2:]), all_.dtype)
            return all_.at[:, slots].set(
                whole.at[:, :, :new.shape[2]].set(new.astype(all_.dtype)))

        return {**{name: put(state[name], new)
                   for name, new in streams.items()},
                "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
