"""A decoder whose attention layers are sliding-window layers with a
learned sink beside full ones, the two kinds with kv heads of their own
number, keys wider than values, a part of every head rotated, and a
sigmoid router over experts of which this device holds a part, with no
shared expert. The language model of MiMo-V2.5 (``model_type``
``mimo_v2``) as its ``config.json`` gives it; the sixth block.

The pattern is data: ``layer_pattern[i]`` is 1 for a window layer and 0
for a full one (published: layer 0 full, four window layers, a full one,
then five window layers to one full), ``moe_pattern[i]`` 0 for a dense
MLP and 1 for experts (published: one dense layer, then experts). Each
layer is its own dict of leaves and the programs unroll them.

- **Attention.** ``[q | k | v] = x' W_qkv`` in one product: ``n_heads``
  query heads and the layer kind's kv heads (``n_kv_heads`` full,
  ``window_kv_heads`` window) of ``head_dim`` (192) for q and k and
  ``v_head_dim`` (128) for v. The leading ``rotary_dim`` (64) numbers
  of every q and k head are rotated at the row's position (rotate-half,
  the kind's own theta); the rest carry no position. Scores q k^T /
  sqrt(head_dim); heads h = kv * group + r share kv head ``kv``; a full
  layer sees every key <= p, a window layer keys p - window + 1 ... p,
  and a window layer's softmax has one learned float32 logit a head
  (``sink``) in its denominator, which takes no value. The output is
  scaled by ``value_scale`` (attention is linear in v: v is scaled as it
  leaves the product) and meets ``W_o`` [n_heads * v_head_dim, d].
- **MLP**: a dense SwiGLU, or the expert layer of ``models/moe.py``
  (sigmoid scores, a selection-only bias, ``top_k`` chosen and
  renormalised, NO shared expert: ``shared_d_ff`` 0).
- Pre-norm, both sublayers added to the stream.

A slot's state (:data:`SLOTS`): four stacks. The full layers keep
``max_len`` rows a slot, ``k_full [L_full, slots, max_len, Hkv * 192]``
and ``v_full [.., Hkv * 128]``, written at ``pos``; the window layers
keep a RING of ``sliding_window`` rows, ``k_win [L_win, slots, window,
Hkv_w * 192]`` and ``v_win [.., Hkv_w * 128]``, written at ``pos %
window`` (k is stored rotated, and attention over a set of rows does not
care for their order). A row of keys is laid as the decode kernel reads
it (``decode_attention.pack_heads``: every head's first 128 numbers,
then every head's last 64, no padding: a full row is 4 x 192 + 4 x 128 =
1,280 numbers, a ring row 2,560). A decode step attends with the
engine's one kernel on both pairs of stacks, the sink passed with the
rings'; the visits are made once a kind a step. A ring cannot give back
an earlier position's rows: the prefix cache, speculation and the
prefill workers refuse this model by name (``rows_state``).

**Prefill** is one call a cold prompt, sized by its bucket, and every
layer one ``lax.scan`` over segments of ``moe.SEGMENT_ROWS`` rows
(``moe.in_segments``; the bucket's segments past the prompt's last real
row are dead and are not run): a segment's norm, ``W_qkv``, rotation,
its k and v written into the layer's rows so far (carried in the flash
kernel's layout), its q rows against those rows
(``ops.attention.attend_rows``: the flash kernel at a traced offset, a
band and the sink in a window layer), ``W_o`` and the MLP. Nothing of a
layer is whole but its k and v rows and the stream; no ``[P, P]`` scores
and no ``[P, dense_d_ff]`` array exist.

Types: matrices in ``dtype`` (bf16), products accumulated in float32;
norm vectors, sinks and the router's bias float32; router scores and
softmax statistics float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.exaone import ring_rows
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_rows
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary_leading, rotary_embedding


@dataclasses.dataclass(frozen=True)
class MimoConfig(moe.HeldExperts):
    vocab_size: int = 152576
    d_model: int = 4096
    n_layers: int = 48
    n_heads: int = 64  # query heads, of both kinds of layer
    n_kv_heads: int = 4  # a full layer's
    window_kv_heads: int = 8  # a window layer's
    head_dim: int = 192  # of q and k
    v_head_dim: int = 128
    rotary_dim: int = 64  # the leading numbers of a head that rotate
    # 1 = a window layer, 0 = a full one; () = the published pattern
    layer_pattern: tuple = ()
    # 0 = a dense MLP, 1 = experts; () = one dense layer, then experts
    moe_pattern: tuple = ()
    sliding_window: int = 128
    rope_theta: float = 1e7  # the full layers'
    window_rope_theta: float = 1e4
    value_scale: float = 0.707
    dense_d_ff: int = 16384
    # mixture of experts: d_ff is ONE expert's width; no shared expert
    d_ff: int = 2048
    shared_d_ff: int = 0
    n_experts: int = 256
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: the backend's choice (the flash kernel on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        n = self.n_layers
        attn = tuple(self.layer_pattern) or tuple(
            int(i > 0 and (i + 1) % 6 != 0) for i in range(n))
        mlp = tuple(self.moe_pattern) or tuple(int(i > 0) for i in range(n))
        if len(attn) != n or len(mlp) != n or set(attn + mlp) - {0, 1}:
            raise ValueError(
                f"{n} layers need {n} entries of 0 / 1 in layer_pattern "
                f"and in moe_pattern, not {attn} and {mlp}")
        object.__setattr__(self, "layer_pattern", attn)
        object.__setattr__(self, "moe_pattern", mlp)

    def windowed(self, i: int) -> bool:
        return bool(self.layer_pattern[i])

    def sparse(self, i: int) -> bool:
        return bool(self.moe_pattern[i])

    def kv_heads(self, windowed: bool) -> int:
        return self.window_kv_heads if windowed else self.n_kv_heads

    def row_widths(self, windowed: bool) -> tuple:
        """(a k row's numbers, a v row's) of a layer of that kind."""
        h = self.kv_heads(windowed)
        return h * self.head_dim, h * self.v_head_dim

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place in the stack of its kind."""
        return self.layer_pattern[:i].count(self.layer_pattern[i])

    @property
    def window_layers(self) -> int:
        return sum(self.layer_pattern)

    @property
    def full_layers(self) -> int:
        return self.n_layers - self.window_layers

    @property
    def moe_layers(self) -> int:
        return sum(self.moe_pattern)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "MimoConfig":
        """Test-size config: both kinds of layer (their kv heads apart)
        and of MLP, keys wider than values, a third of a head rotated, a
        window smaller than the sequences; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=5, n_heads=8, n_kv_heads=2,
            window_kv_heads=4, head_dim=24, v_head_dim=16, rotary_dim=8,
            layer_pattern=(0, 1, 1, 0, 1), sliding_window=8, dense_d_ff=96,
            d_ff=32, n_experts=16, top_k=4, max_seq_len=128, dtype="float32")
        base.update(kw)
        return MimoConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: MimoConfig, key):
    """The tree in the SERVING types (module docstring), leaf by leaf in
    blocks (``moe.draw``). K-EXAONE's initialisation and for its reasons
    (``exaone.init_params``): matrices normal / sqrt(fan_in), every
    ``w_down`` scaled by (2 x depth)^-1/2 besides (depth is
    ``published_layers``), the attention's ``wo`` not. The norm scales
    are drawn around 1, the sinks around 3 (a window's 128 scores of
    spread 1 sum to about e^5.3: such a sink takes a tenth of the
    softmax, more where the row has few keys) and the router's bias away
    from 0, so that a part left out of a path shows against the
    reference."""
    d, hq = cfg.d_model, cfg.n_heads
    keys = iter(jax.random.split(key, 16 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def attention(windowed: bool):
        kw, vw = cfg.row_widths(windowed)
        p = {"w_qkv": mat(d, hq * cfg.head_dim + kw + vw),
             "wo": mat(hq * cfg.v_head_dim, d)}
        if windowed:
            p["sink"] = 3.0 + jax.random.normal(next(keys), (hq,),
                                                jnp.float32)
        return p

    layers = [{
        "attn_norm": around_one(d), "attn": attention(cfg.windowed(i)),
        "mlp_norm": around_one(d),
        "mlp": moe.init_experts(cfg, mat, keys) if cfg.sparse(i)
        else moe.init_dense(cfg, mat),
    } for i in range(cfg.n_layers)]
    return moe.init_model(cfg, mat, around_one, keys, layers)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def _rotation(cfg: MimoConfig, positions, windowed: bool):
    """(sin, cos) of ``positions`` for the ``rotary_dim`` leading
    numbers of a head, at the layer kind's theta."""
    return rotary_embedding(
        positions, cfg.rotary_dim,
        cfg.window_rope_theta if windowed else cfg.rope_theta)


def _qkv(cfg: MimoConfig, p, x, windowed: bool, rotation):
    """x [B, T, D] (normed) -> (q [B, T, Hq, dk], k [B, T, Hkv, dk], v
    [B, T, Hkv, dv]) of a layer of that kind: one product, the leading
    numbers of q's and k's heads rotated by ``rotation`` = (sin, cos) of
    the rows' positions, v scaled by ``value_scale``."""
    b, t, _ = x.shape
    hq, hkv = cfg.n_heads, cfg.kv_heads(windowed)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    qkv = x @ p["w_qkv"]
    q = qkv[..., :hq * dk].reshape(b, t, hq, dk)
    k = qkv[..., hq * dk:(hq + hkv) * dk].reshape(b, t, hkv, dk)
    v = qkv[..., (hq + hkv) * dk:].reshape(b, t, hkv, dv)
    q = apply_rotary_leading(q, *rotation)
    k = apply_rotary_leading(k, *rotation)
    return q, k, (v * cfg.value_scale).astype(v.dtype)


def _attn_scope(windowed: bool):
    """Attention proper: ``attn``, the layer's kind beneath."""
    return jax.named_scope(
        "attn/attn_window" if windowed else "attn/attn_full")


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def prefill(params, tokens, true_lens, cfg: MimoConfig,
            loads: bool = False, live=None):
    """tokens [B, T] from position 0 (right-padded, ``true_lens`` [B]
    real; padding sees nothing real behind it: causal), every layer in
    segments of ``moe.segment_rows`` rows (module docstring) -> (h [B,
    T, D] before the final norm, every layer's (k, v) rows as the cache
    keeps them, k rotated and packed: a full layer's [B, T, Hkv * dk] /
    [B, T, Hkv * dv], a window layer's ring [B, window, ..] of the last
    real rows (``exaone.ring_rows``; its other rows die with the layer:
    five layers' would be 0.8 GB), and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count] int32, the
    expert layer's calls and those that took its compact branch [2]:
    ``moe.compact_calls``), else None).

    ``live`` (``jnp.max(true_lens)``, traced: the serving call's) leaves
    the DEAD segments out of every layer's scan (``moe.in_segments``:
    those that begin past the longest prompt's last real row, a call's
    last and for a full layer its dearest): their rows of h and of a
    full layer's k and v stay zeros, which nothing reads, and a ring is
    cut from the last REAL rows whatever ran. ``None`` runs every
    segment: the whole sequences of ``forward``."""
    b, t = tokens.shape
    seg = moe.segment_rows(t)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    rows, counts = [], []
    for i, p in enumerate(params["layers"]):
        windowed, sparse = cfg.windowed(i), cfg.sparse(i)
        hkv = cfg.kv_heads(windowed)
        count_loads = loads and sparse

        def layer(carry, xs, p=p, windowed=windowed, sparse=sparse,
                  count_loads=count_loads):
            k_all, v_all, count = carry
            start, h_seg = xs
            with jax.named_scope("qkv"):
                at = start + jnp.arange(h_seg.shape[1], dtype=jnp.int32)
                q, k, v = _qkv(
                    cfg, p["attn"],
                    rms_norm(h_seg, p["attn_norm"], cfg.rms_eps), windowed,
                    _rotation(cfg, jnp.broadcast_to(at, h_seg.shape[:2]),
                              windowed))
            with jax.named_scope("cache"):
                # the layer's rows so far, heads outermost: the flash
                # kernel's layout
                k_all = jax.lax.dynamic_update_slice(
                    k_all, k.transpose(0, 2, 1, 3), (0, 0, start, 0))
                v_all = jax.lax.dynamic_update_slice(
                    v_all, v.transpose(0, 2, 1, 3), (0, 0, start, 0))
            with _attn_scope(windowed):
                o = attend_rows(
                    q.transpose(0, 2, 1, 3), k_all, v_all, offset=start,
                    window=cfg.sliding_window if windowed else None,
                    sink=p["attn"].get("sink"), use_flash=cfg.use_flash)
            with jax.named_scope("attn_out"):
                o = o.transpose(0, 2, 1, 3).reshape(*h_seg.shape[:2], -1)
                h_seg = h_seg + o @ p["attn"]["wo"]
            aux = {} if count_loads else None
            h_seg = moe.mlp_layer(cfg, sparse, p, h_seg, aux)
            if count_loads:
                count = jax.tree_util.tree_map(jnp.add, count, (
                    moe.prefill_loads(cfg, aux["expert_ids"][None],
                                      true_lens - start)[0],
                    moe.compact_calls([aux])))
            return (k_all, v_all, count), h_seg

        cdt = cfg.compute_dtype
        empty = (jnp.zeros((b, hkv, t, dk), cdt),
                 jnp.zeros((b, hkv, t, dv), cdt),
                 (jnp.zeros((cfg.held[1],), jnp.int32),
                  jnp.zeros((2,), jnp.int32)) if count_loads else ())
        (k_all, v_all, count), h = moe.in_segments(layer, empty, h, seg,
                                                   live)
        with jax.named_scope("cache"):
            rows.append(tuple(_cache_rows(
                cfg, a, true_lens if windowed else None)
                for a in (k_all, v_all)))
        if count_loads:
            counts.append(count)
    return h, rows, moe.prefill_counts(counts) if counts else None


def _cache_rows(cfg: MimoConfig, rows, true_lens):
    """A layer's rows in the flash kernel's layout [B, Hkv, T, d] -> as
    the cache keeps them, a row's heads packed: [B, T, Hkv * d], or with
    ``true_lens`` [B] (a window layer) the ring [B, window, Hkv * d] of
    each prompt's last real rows."""
    b, hkv, t, d = rows.shape
    if true_lens is not None:
        rows = ring_rows(rows.reshape(b * hkv, t, d),
                         jnp.repeat(true_lens, hkv), cfg.sliding_window)
        rows = rows.reshape(b, hkv, -1, d)
    return _da.pack_heads(rows.transpose(0, 2, 1, 3))


def forward(params, tokens, cfg: MimoConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: MimoConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` the two pairs of stacks (:meth:`_Slots.init_state`, without
    ``pos``). A window layer writes its B new rows at ``[layer, slot,
    pos % window]`` of the rings and attends over the slot's ``min(pos +
    1, window)`` rows with its sinks; a full layer writes at ``[layer,
    slot, pos]`` and attends over ``pos + 1`` rows; an inactive slot
    attends over nothing. Both with ``ops.decode_attention`` on the
    stacks in place, the kernel's visits made here once for each kind,
    before the layers. -> (float32 logits [B, V], the state updated,
    three [L_moe] int32 counters of the ACTIVE slots' routing: distinct
    held experts touched, assignments, assignments to held experts)."""
    b = tok.shape[0]
    w = cfg.sliding_window
    slots = jnp.arange(b)
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]

    # by kind of layer (window or not): the stacks' names, the row a
    # slot writes, the rows it holds once written with the kernel's
    # visits for them, and the rotation at the kind's theta
    def kind(windowed, names, row, held):
        lengths = jnp.where(active, held, 0).astype(jnp.int32)
        return (names, row, lengths,
                _da.visits(lengths, state[names[0]].shape[2]),
                _rotation(cfg, pos[:, None], windowed))

    with jax.named_scope("attn"):
        by_kind = {True: kind(True, ("k_win", "v_win"), pos % w,
                              jnp.minimum(pos + 1, w)),
                   False: kind(False, ("k_full", "v_full"), pos, pos + 1)}
    state = dict(state)
    counts = []
    for i, p in enumerate(params["layers"]):
        windowed = cfg.windowed(i)
        (kn, vn), row, lengths, plan, rotation = by_kind[windowed]
        layer = cfg.stack_index(i)
        with jax.named_scope("qkv"):
            q, k, v = _qkv(cfg, p["attn"],
                           rms_norm(h, p["attn_norm"], cfg.rms_eps),
                           windowed, rotation)
        with jax.named_scope("cache"):
            state[kn] = state[kn].at[layer, slots, row].set(
                _da.pack_heads(k[:, 0]))
            state[vn] = state[vn].at[layer, slots, row].set(
                v.reshape(b, -1))
        with _attn_scope(windowed):
            o = _da.decode_attention(q, state[kn], state[vn], layer,
                                     lengths, plan=plan,
                                     sink=p["attn"].get("sink"))
        with jax.named_scope("attn_out"):
            h = h + o.reshape(b, 1, -1) @ p["attn"]["wo"]
        aux = {} if cfg.sparse(i) else None
        h = moe.mlp_layer(cfg, cfg.sparse(i), p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return moe.logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """Two pairs of stacks, a row's width its layer kind's own: the full
    layers' rows and the window layers' rings, which cannot be cut or
    rewound at a position."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "sink",
                  "router_bias")

    @staticmethod
    def row_kinds(cfg: MimoConfig) -> dict:
        return {"window": (cfg.window_layers, cfg.sliding_window),
                "full": (cfg.full_layers, None)}

    @staticmethod
    def prefill_segments(cfg: MimoConfig, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket)

    @staticmethod
    def init_state(cfg: MimoConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        state = {"pos": jnp.zeros((slots,), jnp.int32)}
        for windowed, kind, layers, rows in (
                (False, "full", cfg.full_layers, max_len),
                (True, "win", cfg.window_layers, cfg.sliding_window)):
            for name, width in zip("kv", cfg.row_widths(windowed)):
                state[f"{name}_{kind}"] = jnp.zeros(
                    (layers, slots, rows, width), cdt)
        return state

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k_full"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def both(kind):  # (by shape: the state may be described only)
            return sum(a.size * a.dtype.itemsize
                       for a in (state["k_" + kind], state["v_" + kind]))

        return {"window": both("win"), "full": both("full")}

    @staticmethod
    def step(cfg: MimoConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: MimoConfig, slot_len: int, prefix=None):
        """Whole prompts from position 0. Of a prompt's rows the full
        layers keep all (the bucket's padding among them: a decode step
        overwrites a pad row at its position before the growing mask can
        expose it), the window layers the last ``window`` real ones, at
        their ring offsets (:func:`prefill` made them). -> (the streams'
        rows by kind, [F] prompt lengths, [F] first tokens, [F] their
        logprobs, the held experts' assignments from the real positions
        [L_moe, count], the expert layer's calls and compact calls
        [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, rows, loads = prefill(params, prompts, true_lens, cfg,
                                 loads=cfg.moe_layers > 0,
                                 live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        # a list a stack, one entry a layer of its kind ([F, P or
        # window, C]): stacked they would be copied once more
        streams = {f"{name}_{kind}": [
            r[j] for i, r in enumerate(rows) if cfg.windowed(i) == windowed]
            for windowed, kind in ((False, "full"), (True, "win"))
            for j, name in enumerate("kv")}
        return streams, true_lens, toks0, logp0, *(loads or ())

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' rows into their slots: a ring replaced
        whole (a prompt shorter than the window leaves zeros), a full
        layer's P rows onto the first P rows of the slot. What the
        slot's last stream wrote behind them stays: no reader looks past
        a slot's own length (``_prefill_batch_into_slots``' docstring)."""
        return {**{name: Slots.put_rows(state[name], slots, new)
                   for name, new in streams.items()},
                "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
