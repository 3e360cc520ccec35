"""A decoder whose every layer attends through a latent (MLA) with a
sigmoid output gate, whose sublayers do not read the sublayer just
before them (FarSkip), and whose expert layers hold all their experts
with shared ones beside them. The language model of
Instella-MoE-16B-A3B-Base (``model_type`` ``deepseek_v3``) as its
``config.json`` gives it; the fourth block beside ``llama.py``,
``ling.py`` and ``exaone.py``.

Layer ``l`` on the stream ``s_l`` (``s_0`` the embedding), ``N`` a
learned RMS norm:

- **MLA** (DeepSeek-V2 section 2.1, no query compression) on a normed
  input ``x``: ``q = N_q((x W_q) a head)`` over the head's whole
  ``qk_nope_head_dim + qk_rope_head_dim``, the rope part rotated;
  ``[c ‖ k_r] = x W_kva``, ``c <- N_kv(c)``, ``k_r`` rotated, one for
  all heads; a cache row is ``[c ‖ k_r]``; ``[k_nope ‖ v]_h = c
  W_kvb,h``; scores ``(q_nope k_nope + q_r k_r) * qk^-1/2 * m^2`` with
  ``m = yarn_mscale(factor, mscale_all_dim)``, causal softmax in
  float32; ``o = (attn * sigmoid(x W_g)) W_o``, the gate elementwise
  (``gated_attention``). Rotary: interleaved pairs, YaRN's blended
  frequencies (``ops/rope.py``). Prefill attends UNABSORBED through
  ``ops.attention`` (the flash kernel on a TPU: k = [k_nope ‖ the shared
  rotated key], nothing ``T x T`` exists); a decode step attends
  ABSORBED (q_nope through ``W_kvb``'s key half into the latent's
  space, probabilities weigh latents, the value half brings them back)
  over the slot's rows up to its own length
  (``ops.decode_attention.decode_attention_latent``).
- **MLP**: a dense SwiGLU in the first ``first_k_dense`` layers, then
  the expert layer of ``models/moe.py`` (sigmoid scores in float32, a
  selection-only bias, ``top_k`` chosen, renormalised and scaled; the
  ``n_shared_experts`` shared experts side by side as one SwiGLU,
  unweighted).
- **FarSkip** (``farskip``; arXiv:2511.11505): with ``a_l``, ``m_l`` the
  attention's and the MLP's outputs, ``a_l = Attn_l(N(s_l - m_{l-1}))``
  (``m_{-1} = 0``), ``m_l = MLP_l(N'(s_l))`` (it does not see ``a_l``),
  ``s_{l+1} = s_l + a_l + m_l``; the final norm and the head read
  ``s_L`` whole. In a deployment a sublayer's collective rides behind
  the next sublayer's products; on one chip there is none and nothing
  stands in for one. The programs carry ``s_l`` and ``s_l + a_l`` (what
  the next attention reads) and never subtract. ``farskip=False`` is
  the plain pre-norm block: the MLP reads ``s_l + a_l``.

A slot's state (:data:`SLOTS`): ONE stack of rows ``[L, slots, max_len,
row_width]``, a row ``[c ‖ k_r ‖ zeros to whole lanes]`` (640 for 512 +
32; ``decode_attention.py`` says why one array; Ling's MLA layers and
``dots.py``'s full layers keep the same stack of 640, its window layers
a ring of 1,152). ``rows_state`` stays ``False``: the prefix cache and
the prefill workers carry ``[L, S, Hkv, D]`` pairs of k, v (ROADMAP A1).

Types: matrices in ``dtype`` (bf16 as published), products accumulated
in float32; norm vectors and the router's bias float32; router scores
and softmax statistics float32. :func:`init_params` makes the tree in
those types leaf by leaf, in blocks (``moe.draw``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_bucket, attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import (apply_rotary_interleaved, rotary_embedding,
                              yarn_inv_freq, yarn_mscale)

_LANES = 128


@dataclasses.dataclass(frozen=True)
class InstellaConfig(moe.HeldExperts):
    vocab_size: int = 128896
    d_model: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    first_k_dense: int = 1
    dense_d_ff: int = 10944
    # mixture of experts: d_ff is ONE expert's width, shared_d_ff the
    # shared experts' side by side
    d_ff: int = 1408
    shared_d_ff: int = 2816
    n_experts: int = 64
    top_k: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 96
    qk_rope_head_dim: int = 32
    v_head_dim: int = 128
    gated_attention: bool = True
    farskip: bool = True
    # rotary: YaRN over the trained positions (factor 1: the plain one)
    rope_theta: float = 8e6
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: ``ops.attention``'s own choice (flash on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """What a cache row holds: latent ‖ rotated key, to whole lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim)
                 // _LANES) * _LANES

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense

    @property
    def moe_layers(self) -> int:
        return self.n_layers - min(self.first_k_dense, self.n_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "InstellaConfig":
        """Test-size config: a dense layer and expert layers, a trained
        range shorter than the sequences (so YaRN's blend shows); runs
        on the CPU."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            first_k_dense=1, dense_d_ff=160, d_ff=32, shared_d_ff=64,
            n_experts=16, top_k=4, kv_lora_rank=32, qk_nope_head_dim=24,
            qk_rope_head_dim=8, v_head_dim=32, rope_theta=1e4,
            rope_factor=8.0, rope_original_max=16, rope_beta_fast=4.0,
            max_seq_len=128, dtype="float32")
        base.update(kw)
        return InstellaConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: InstellaConfig, key):
    """The tree in the SERVING types (module docstring). Matrices are
    normal / sqrt(fan_in); every ``w_down`` (the MLPs' writes into the
    residual stream) is scaled by (2 x depth)^-1/2 besides, depth being
    ``published_layers``, and the attention's ``wo`` is NOT:
    ``exaone.init_params`` says why both (an expert that a router
    near-tie flips must not move the stream far; a scaled ``wo`` lets
    greedy decoding fall into cycles that the slots share). The norm
    scales are drawn around 1 and the router's bias away from 0, so that
    a part left out of a path shows against the reference."""
    d, h = cfg.d_model, cfg.n_heads
    keys = iter(jax.random.split(key, 24 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def mla():
        r, dv = cfg.kv_lora_rank, cfg.v_head_dim
        p = {
            "wq": mat(d, h * cfg.qk_head_dim),
            "q_norm": around_one(cfg.qk_head_dim),
            "w_kva": mat(d, r + cfg.qk_rope_head_dim),
            "kv_norm": around_one(r),
            "w_kvb": mat(r, h * (cfg.qk_nope_head_dim + dv)),
            "wo": mat(h * dv, d),
        }
        if cfg.gated_attention:
            p["w_gate"] = mat(d, h * dv)
        return p

    layers = [{
        "attn_norm": around_one(d), "attn": mla(),
        "mlp_norm": around_one(d),
        "mlp": moe.init_experts(cfg, mat, keys) if cfg.sparse(i)
        else moe.init_dense(cfg, mat),
    } for i in range(cfg.n_layers)]
    return moe.init_model(cfg, mat, around_one, keys, layers)


# --------------------------------------------------------------------------
# MLA
# --------------------------------------------------------------------------

def _rotation(cfg: InstellaConfig, positions):
    """(sin, cos) of ``positions`` [B, T] under YaRN's frequencies, each
    carrying ``mscale / mscale_all_dim``."""
    sin, cos = rotary_embedding(
        positions, cfg.qk_rope_head_dim, cfg.rope_theta,
        inv_freq=yarn_inv_freq(
            cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow))
    carried = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (sin, cos) if carried == 1.0 else (sin * carried, cos * carried)


@jax.named_scope("qkv")
def _mla_inputs(cfg: InstellaConfig, p, x, rotation):
    """x [B, T, D] (normed), ``rotation`` the (sin, cos) of its positions
    -> (q_nope [B, T, H, dn], q_rope [B, T, H, dr] rotated, the latent
    [B, T, r] normalised, k_rope [B, T, dr] rotated, the gate [B, T, H x
    dv] float32 or ``None``). q carries YaRN's ``m^2`` already (in its
    norm's float32 scale: no rounding of its own), so that the logits
    want ``qk_head_dim^-1/2`` alone, what the flash kernel applies."""
    b, t, _ = x.shape
    h, dn, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    m2 = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    q = rms_norm((x @ p["wq"]).reshape(b, t, h, cfg.qk_head_dim),
                 p["q_norm"] * m2, cfg.rms_eps)
    q_nope = q[..., :dn]
    q_rope = apply_rotary_interleaved(q[..., dn:], *rotation)
    kva = x @ p["w_kva"]
    latent = rms_norm(kva[..., :r], p["kv_norm"], cfg.rms_eps)
    k_rope = apply_rotary_interleaved(kva[..., None, r:], *rotation)
    gate = jax.nn.sigmoid(jnp.dot(
        x, p["w_gate"], preferred_element_type=jnp.float32)) \
        if cfg.gated_attention else None
    return q_nope, q_rope, latent, k_rope[..., 0, :], gate


def _cache_rows(cfg: InstellaConfig, latent, k_rope):
    """[..., r] and [..., dr] -> the rows a slot keeps [..., row_width]."""
    pad = cfg.row_width - latent.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate(
        [latent, k_rope, jnp.zeros((*latent.shape[:-1], pad), latent.dtype)],
        axis=-1)


@jax.named_scope("attn_out")
def _mla_out(cfg: InstellaConfig, p, o, gate):
    """o [B, T, H, dv] -> the attention's output [B, T, D]: the gate,
    then ``W_o``."""
    b, t = o.shape[:2]
    o = o.reshape(b, t, -1)
    if gate is not None:
        o = (o.astype(jnp.float32) * gate).astype(cfg.compute_dtype)
    return o @ p["wo"]


def mla_prefill(cfg: InstellaConfig, p, x, rotation,
                differentiable: bool = False):
    """An MLA layer over whole prompts from position 0, unabsorbed: k
    and v are made from the latent and attended as any attention's of
    ``n_heads`` x ``qk_head_dim`` (``ops.attention``: the flash kernel
    on a TPU, the reference product elsewhere; the forward-only
    ``attend_bucket`` for a serving prefill, ``attention`` and its lse
    where ``differentiable``: ``forward``'s). -> ([B, T, D], the
    prompts' cache rows [B, T, row_width])."""
    b, t, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope, latent, k_rope, gate = _mla_inputs(cfg, p, x, rotation)
    with jax.named_scope("qkv"):  # (k and v out of the latent)
        kv = (latent @ p["w_kvb"]).reshape(b, t, h, dn + dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope[:, :, None], (b, t, h, cfg.qk_rope_head_dim))], axis=-1)
    with jax.named_scope("attn/attn_latent"):
        attend = attention if differentiable else attend_bucket  # (causal)
        o = attend(q, k, kv[..., dn:], use_flash=cfg.use_flash)
    with jax.named_scope("cache"):
        rows = _cache_rows(cfg, latent, k_rope)
    return _mla_out(cfg, p, o, gate), rows


def mla_step(cfg: InstellaConfig, p, x, rotation, cache, layer: int, pos,
             lengths, plan):
    """A decode step of an MLA layer in the absorbed form. x [B, 1, D]
    (normed); ``cache`` the whole stack [L, B, S, row_width]; pos,
    lengths [B]. The new row is written at [layer, slot, pos] and the
    step attends over the slot's rows themselves, up to ``lengths``:
    q_nope is carried through the key half of ``w_kvb`` into the
    latent's space, the probabilities weigh latents, and the value half
    brings the result back. -> ([B, 1, D], cache)."""
    b = x.shape[0]
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    f32 = jnp.float32
    q_nope, q_rope, latent, k_rope, gate = _mla_inputs(cfg, p, x, rotation)
    with jax.named_scope("cache"):
        cache = cache.at[layer, jnp.arange(b), pos].set(
            _cache_rows(cfg, latent[:, 0], k_rope[:, 0]))
    w_kvb = p["w_kvb"].reshape(r, h, dn + dv)
    with jax.named_scope("qkv"):  # (q into the row's space)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn],
                           preferred_element_type=f32).astype(x.dtype)
        q_row = _cache_rows(cfg, q_lat, q_rope[:, 0])
    with jax.named_scope("attn/attn_latent"):
        o_lat = _da.decode_attention_latent(
            q_row, cache, layer, lengths, dv=r,
            scale=cfg.qk_head_dim ** -0.5, plan=plan)
    with jax.named_scope("attn_out"):  # (and back out of it)
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w_kvb[..., dn:],
                       preferred_element_type=f32).astype(x.dtype)
    return _mla_out(cfg, p, o[:, None], gate), cache


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's rows, a ragged step
# --------------------------------------------------------------------------

def _layer(cfg: InstellaConfig, i: int, p, s, behind, attend,
           aux: dict | None = None):
    """Layer ``i`` on the stream ``s`` [B, T, D]; ``behind`` is ``s``
    less the last MLP's output (FarSkip: what this attention reads; at
    layer 0 ``s`` itself). ``attend(x)`` is the layer's attention on its
    normed input; the MLP is the dense SwiGLU or the expert layer on
    ITS normed input, and what each output is added to is FarSkip's.
    -> (the next layer's ``s``, its ``behind``)."""
    sparse = cfg.sparse(i)
    with jax.named_scope("qkv"):
        x = rms_norm(behind if cfg.farskip else s, p["attn_norm"],
                     cfg.rms_eps)
    a = attend(x)
    with jax.named_scope("attn_out"):
        seen = s + a  # what the next attention reads under FarSkip
    with jax.named_scope("moe_router" if sparse else "mlp"):
        x = rms_norm(s if cfg.farskip else seen, p["mlp_norm"], cfg.rms_eps)
    if sparse:
        m = moe.moe(cfg, p["mlp"], x, aux)
        with jax.named_scope("moe_shared"):
            s = seen + m
    else:
        with jax.named_scope("mlp"):
            s = seen + moe.swiglu(x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                                  p["mlp"]["w_down"])
    return s, seen if cfg.farskip else s


def prefill(params, tokens, cfg: InstellaConfig, aux: dict | None = None,
            differentiable: bool = False):
    """tokens [B, T] from position 0 (right-padding sees nothing real
    behind it: causal) -> (the stream [B, T, D] before the final norm,
    every layer's cache rows [L, B, T, row_width]). With ``aux`` every
    expert layer's ids are left in ``aux["expert_ids"]`` [L_moe, B, T,
    top_k]. ``differentiable``: :func:`mla_prefill`'s, which ``forward``
    alone sets."""
    b, t = tokens.shape
    with jax.named_scope("embed"):
        s = params["embed"][tokens]
    with jax.named_scope("qkv"):
        rotation = _rotation(cfg, jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32), (b, t)))
    behind, rows, ids = s, [], []
    for i, p in enumerate(params["layers"]):
        def attend(x, p=p):
            y, made = mla_prefill(cfg, p["attn"], x, rotation,
                                  differentiable)
            rows.append(made)
            return y

        layer_aux = {} if aux is not None else None
        s, behind = _layer(cfg, i, p, s, behind, attend, layer_aux)
        if layer_aux:
            ids.append(layer_aux["expert_ids"])
    if ids:
        aux["expert_ids"] = jnp.stack(ids)
    with jax.named_scope("cache"):
        return s, jnp.stack(rows)


def forward(params, tokens, cfg: InstellaConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    unabsorbed attention."""
    return moe.logits(cfg, params, prefill(
        params, tokens, cfg, differentiable=True)[0])


loss_fn = moe.loss_fn(forward)


def step(cfg: InstellaConfig, params, tok, cache, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``cache`` the stack of rows [L, B, S, row_width]. Every layer writes
    its B new rows at ``[layer, slot, pos]`` and attends over the slot's
    ``pos + 1`` rows; an inactive slot attends over nothing (its length
    is 0, its output zeros). The kernel's visits are made here once,
    before the layers. -> (float32 logits [B, V], the stack updated, and
    for a model with expert layers three [L_moe] int32 counters of the
    ACTIVE slots' routing: distinct held experts touched, assignments,
    assignments to held experts)."""
    with jax.named_scope("embed"):
        s = params["embed"][tok][:, None]  # [B, 1, D]
    with jax.named_scope("qkv"):
        rotation = _rotation(cfg, pos[:, None])
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        size = cache.shape[2]
        plan = _da.visits(lengths, size, _da.block_rows(size, _da.LATENT_BLOCK_ROWS))
    behind, counts = s, []
    for i, p in enumerate(params["layers"]):
        def attend(x, p=p, i=i):
            nonlocal cache
            y, cache = mla_step(cfg, p["attn"], x, rotation, cache, i, pos,
                                lengths, plan)
            return y

        aux = {} if cfg.sparse(i) else None
        s, behind = _layer(cfg, i, p, s, behind, attend, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return moe.logits(cfg, params, s)[:, 0], cache, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """One stack of latent rows: rows of positions, but not the [L, S,
    Hkv, D] pairs of k and v that the prefix cache, speculation and the
    prefill workers carry (so ``rows_state`` stays False)."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "kv_norm",
                  "router_bias")

    @staticmethod
    def row_kinds(cfg: InstellaConfig) -> dict:
        return {"latent": (cfg.n_layers, None)}

    @staticmethod
    def init_state(cfg: InstellaConfig, slots: int, max_len: int) -> dict:
        return {"rows": jnp.zeros(
                    (cfg.n_layers, slots, max_len, cfg.row_width),
                    cfg.compute_dtype),
                "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict) -> int:
        return state["rows"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        rows = state["rows"]  # (by shape: the state may be described only)
        return {"latent": rows.size * rows.dtype.itemsize}

    @staticmethod
    def step(cfg: InstellaConfig, params, prepared, tok, state, pos, active):
        logits, rows, *counters = step(
            cfg, params, tok, state["rows"], pos, active)
        return logits, {"rows": rows}, *counters

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: InstellaConfig, slot_len: int, prefix=None):
        """Whole prompts from position 0 -> (the streams' rows {"rows":
        [L, F, P, row_width]}, the bucket's padding among them, [F]
        prompt lengths, [F] first tokens, [F] their logprobs, the held
        experts' assignments from the real positions [L_moe, count])."""
        Slots.refuse_prefix(cfg, prefix)
        aux = {} if cfg.moe_layers else None
        s, rows = prefill(params, prompts, cfg, aux)
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, s, true_lens,
            seeds, temps, top_ps)
        loads = (moe.prefill_loads(cfg, aux["expert_ids"], true_lens),) \
            if aux else ()
        return {"rows": rows}, true_lens, toks0, logp0, *loads

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' P rows onto the first P rows of their
        slots. What the slot's last stream wrote behind them stays: no
        reader looks past a slot's own length (a step writes row ``pos``
        before it attends, the kernel and the XLA body read up to
        ``lengths``; ``_prefill_batch_into_slots``' docstring)."""
        made = streams["rows"]
        return {"rows": state["rows"].at[:, slots, :made.shape[2]].set(
                    made.astype(state["rows"].dtype)),
                "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
