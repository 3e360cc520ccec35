"""A hybrid decoder: delta-rule linear attention (KDA) beside latent
attention (MLA), and a group-limited sigmoid router over experts of
which this device holds a part. The language model of Ling-3.0-flash-VL
as its ``config.json`` gives it; the second block beside ``llama.py``.

Three kinds of layer in one stack. Layer ``i`` attends with **MLA** if
``(i + 1) % layer_group_size == 0`` and with **KDA** otherwise; its MLP
is a dense SwiGLU if ``i < first_k_dense`` and the mixture of experts
after that. The layers are NOT a stack scanned by one loop: each is its
own dict of leaves and the programs unroll them, so no layer's weights
are ever sliced out of a stack.

- **KDA** (Kimi Delta Attention, arXiv:2510.26692): q, k, v through a
  causal depthwise convolution (kernel 4) and SiLU; q and k L2-normalised
  a head; a per-channel decay ``log a = lower_bound * sigmoid(exp(A_log)
  * (x W_f + dt_bias))`` and a per-head ``beta = sigmoid(x W_beta)``; a
  float32 state ``S [H, dk, dv]`` a stream: ``S <- Diag(a) S``,
  ``S <- S + beta k (v - S^T k)^T``, ``o = S^T q``; a head-wise RMS norm
  gated by ``sigmoid(x W_g)``. A decode step is that recurrence
  (:func:`kda_step`); prefill is its chunkwise form
  (``ops/kda_chunk.py``, chunks of ``kda_chunk``: on a TPU where a head
  is whole lanes ONE Pallas kernel a layer, ``kda_chunk``; elsewhere,
  and where it is differentiated, the XLA body :func:`kda_chunked`) and
  leaves the same ``S`` and the same last ``conv_kernel - 1``
  convolution inputs. No position encoding. A second block runs this
  code at 64 heads (``models/solar.py``: ``ops/kda_inputs``,
  ``_kda_out``, ``ops/kda_chunk``, ``ops/kda_step``);
  the decay's two forms are told apart in the blocks' ``_kda_inputs``
  (this one's bounded by ``kda_lower_bound``, that one's ``-exp(A_log)
  softplus``, unbounded below), never in the shared functions.
- **MLA** (DeepSeek-V2 section 2.1, no query compression): a cache row
  is the normalised latent and the one rotated key all heads share
  (``kv_lora_rank + qk_rope_head_dim`` numbers a token). Prefill attends
  unabsorbed; a decode step attends in the absorbed form over the latent
  rows (:func:`mla_step`). A learned RMS norm over each head's whole q;
  a head-wise sigmoid gate on the output.
- **MoE** (DeepSeek-V3's routing, ``models/moe.py``, shared with
  ``models/exaone.py``): sigmoid scores in float32, a bias added for
  selection only, ``topk_group`` of ``n_group`` groups kept, the
  ``top_k`` best of those chosen; a shared expert beside them.
  ``held_experts = (first, count)`` tells the layer which experts live
  here: it routes over all of them and computes the part of the result
  that its own give; what the others would add is left out.

A slot's state in the serving engine is this model's own
(:data:`SLOTS`, what ``decode_engine.slot_model`` finds through
``LingConfig.slot_model``): for a KDA layer ``S`` and the convolution
rows, for an MLA layer ``max_len`` rows of the latent and of the rotated
key. It is not rows that can
be cut at a position, so the prefix cache, speculative decoding and the
prefill workers refuse this model by name (``rows_state``).

Types: matrices in ``dtype`` (bf16 as published), products accumulated
in float32; norm vectors, ``a_log``, ``dt_bias`` and the router's bias
float32; router scores, softmax and the KDA state float32.
:func:`init_params` makes the tree in those types leaf by leaf, in
blocks: the whole model as float32 masters does not fit the chip that
serves it.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops.kda_chunk import kda_chunk as _kda_chunk
from ray_tpu.ops.kda_inputs import kda_inputs as _kda_qkvg, kept_rows
from ray_tpu.ops.kda_chunk import kda_chunked  # noqa: F401 — the chunkwise
# form's XLA body (the tests' second opinion), under this module's name
from ray_tpu.ops.kda_step import kda_recurrence  # noqa: F401 — the
# recurrence kda_chunked is the chunkwise form of, under this module's name
from ray_tpu.ops.kda_step import kda_step as _kda_step
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary, rotary_embedding


@dataclasses.dataclass(frozen=True)
class LingConfig(moe.HeldExperts):
    vocab_size: int = 157184
    d_model: int = 2560
    n_layers: int = 42
    n_heads: int = 32
    first_k_dense: int = 2
    layer_group_size: int = 6  # every sixth layer attends with MLA
    dense_d_ff: int = 6144
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 768
    shared_d_ff: int = 768
    n_experts: int = 512
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    # KDA
    kda_head_dim: int = 128  # key and value width of a head
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def attn_kind(self, i: int) -> str:
        return "mla" if (i + 1) % self.layer_group_size == 0 else "kda"

    def mlp_kind(self, i: int) -> str:
        return "dense" if i < self.first_k_dense else "moe"

    @property
    def moe_layers(self) -> int:
        return self.n_layers - min(self.first_k_dense, self.n_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "LingConfig":
        """Test-size config: every kind of layer, runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=7, n_heads=4,
            first_k_dense=1, layer_group_size=6, dense_d_ff=128, d_ff=32,
            shared_d_ff=32, n_experts=32, top_k=4, n_group=4, topk_group=2,
            kda_head_dim=16, kda_chunk=8, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=1e4, max_seq_len=128, dtype="float32")
        base.update(kw)
        return LingConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: LingConfig, key):
    """The tree in the SERVING types (module docstring). Matrices are
    normal / sqrt(fan_in), and those that write into the residual stream
    (``wo``, every ``w_down``) are scaled by (2 x depth)^-1/2 besides
    (GPT-2's and Megatron's scaled initialisation; depth is
    ``published_layers``, the model's own where the configuration is cut
    in depth: the layers kept write into the stream of the whole
    model). Unscaled, one near-tie of the router that bf16 decides the
    other way moves the stream by a sixth of its norm, and the logits of
    a bf16 step part from a float32 reference's by over 1 where the top
    two are 0.5 apart (my chip run, PR 32). The norm scales are drawn around 1, the decay parameters and
    the router's bias away from 0, so that a part left out of a path
    shows against the reference."""
    cdt = cfg.compute_dtype
    d, h = cfg.d_model, cfg.n_heads
    dk = cfg.kda_head_dim
    keys = iter(jax.random.split(key, 32 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def kda():
        return {
            "w_qkv": mat(d, 3 * h * dk),
            "conv": moe.draw(next(keys), (cfg.conv_kernel, 3 * h * dk),
                             cfg.conv_kernel ** -0.5, cdt),
            "w_f": mat(d, h * dk),
            "dt_bias": jax.random.normal(next(keys), (h * dk,), jnp.float32),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (h,), jnp.float32, 0.5, 4.0)),
            "w_beta": mat(d, h),
            "w_g": mat(d, h * dk),
            "o_norm": around_one(dk),
            "wo": mat(h * dk, d, out=True),
        }

    def mla():
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        r = cfg.kv_lora_rank
        return {
            "wq": mat(d, h * qk),
            "q_norm": around_one(qk),
            "w_kva": mat(d, r + cfg.qk_rope_head_dim),
            "kv_norm": around_one(r),
            "w_kvb": mat(r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "w_gate": mat(d, h),
            "wo": mat(h * cfg.v_head_dim, d, out=True),
        }

    layers = []
    for i in range(cfg.n_layers):
        layers.append({
            "attn_norm": around_one(d),
            "attn": kda() if cfg.attn_kind(i) == "kda" else mla(),
            "mlp_norm": around_one(d),
            "mlp": moe.init_dense(cfg, mat) if cfg.mlp_kind(i) == "dense"
            else moe.init_experts(cfg, mat, keys),
        })
    return moe.init_model(cfg, mat, around_one, keys, layers)


# --------------------------------------------------------------------------
# KDA
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _kda_inputs(cfg: LingConfig, p, x, conv_rows, real_rows=None):
    """What both forms of KDA start from. x: [B, T, D] (normed);
    ``conv_rows`` [B, K-1, 3*H*dk]: the projection rows before x's
    first; ``real_rows`` [B]: rows from that index on are padding (g 0).
    -> (q, k, v [B, T, H, dk] float32, log decay g [B, T, H, dk],
    beta [B, T, H], the output gate [B, T, H, dk], the projection's
    rows [B, T, 3*H*dk]: behind ``conv_rows`` they hold the next
    ``conv_rows``). The convolution, the norms and the decay are
    ``ops.kda_inputs``: one kernel over a prefill's rows on a TPU, the
    XLA body elsewhere and for a step."""
    b, t, _ = x.shape
    h, dk = cfg.n_heads, cfg.kda_head_dim
    f32 = jnp.float32
    proj = x @ p["w_qkv"]

    def f():
        return jnp.dot(x, p["w_f"], preferred_element_type=f32) \
            + p["dt_bias"]

    q, k, v, g = _kda_qkvg(proj, conv_rows, p["conv"], f, p["a_log"],
                           lower_bound=cfg.kda_lower_bound,
                           real_rows=real_rows)
    beta = jax.nn.sigmoid(jnp.dot(x, p["w_beta"],
                                  preferred_element_type=f32))
    gate = jax.nn.sigmoid(jnp.dot(
        x, p["w_g"], preferred_element_type=f32)).reshape(b, t, h, dk)
    return q, k, v, g, beta, gate, proj


@jax.named_scope("attn_out")
def _kda_out(cfg, p, o, gate):
    """o, gate [B, T, H, dk] float32 -> [B, T, D]: the head-wise RMS
    norm, the sigmoid gate and the output projection."""
    b, t = o.shape[:2]
    o = rms_norm(o, p["o_norm"], cfg.rms_eps) * gate
    return o.reshape(b, t, -1).astype(cfg.compute_dtype) @ p["wo"]


def kda_step(cfg: LingConfig, p, x, state, active):
    """A decode step of a KDA layer. x [B, 1, D] (normed); ``state``
    {"s" [B, H, dk, dv] float32, "conv" [B, K-1, 3*H*dk]}. A slot that
    is not ``active`` keeps its state. -> ([B, 1, D], state). The
    recurrence is ``ops.kda_step``: on a TPU one kernel that touches
    ``s`` once, in the buffer it lies in; :func:`kda_recurrence` and a
    ``where`` elsewhere."""
    q, k, v, g, beta, gate, proj = _kda_inputs(cfg, p, x, state["conv"])
    with jax.named_scope("attn/attn_linear"):
        s, o = _kda_step(state["s"], q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                         beta[:, 0], active)
    with jax.named_scope("cache"):
        rows = jnp.concatenate([state["conv"], proj], axis=1)[:, 1:]
        new = {"s": s, "conv": jnp.where(active[:, None, None], rows,
                                         state["conv"])}
    return _kda_out(cfg, p, o[:, None], gate), new


def kda_prefill(cfg: LingConfig, p, x, true_lens):
    """A KDA layer over whole prompts from an empty state. x [B, T, D]
    (normed, right-padded; ``true_lens`` [B] real). -> ([B, T, D], the
    state after each prompt's last real token)."""
    b, t, _ = x.shape
    h, dk = cfg.n_heads, cfg.kda_head_dim
    kw = cfg.conv_kernel - 1
    zeros = jnp.zeros((b, kw, 3 * h * dk), cfg.compute_dtype)
    q, k, v, g, beta, gate, proj = _kda_inputs(cfg, p, x, zeros, true_lens)
    with jax.named_scope("attn/attn_linear"):
        real = jnp.arange(t)[None, :] < true_lens[:, None]  # [B, T]
        beta = jnp.where(real[..., None], beta, 0.0)
        pad = -t % cfg.kda_chunk
        if pad:  # (a bucket narrower than a chunk: the CPU rehearsal's)
            q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                        + ((0, 0),) * (a.ndim - 2))
                                for a in (q, k, v, g, beta))
        o, s = _kda_chunk(q, k, v, g, beta,
                          jnp.zeros((b, h, dk, dk), jnp.float32),
                          chunk=cfg.kda_chunk)
    with jax.named_scope("cache"):
        # the last K-1 projection rows of each prompt: rows true_len ..
        # true_len + K-2 of the K-1 rows before the prompt and its own
        conv = kept_rows(zeros, proj, true_lens)
    return _kda_out(cfg, p, o[:, :t], gate), {"s": s, "conv": conv}


# --------------------------------------------------------------------------
# MLA
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _mla_inputs(cfg: LingConfig, p, x, positions):
    """x [B, T, D] (normed) at ``positions`` [B, T] -> (q_nope [B, T, H,
    dn], q_rope [B, T, H, dr] rotated, the cache rows {"latent" [B, T,
    r]: the normalised latent, "k_rope" [B, T, dr]: the rotated key all
    heads share}, the head gate [B, T, H] float32)."""
    b, t, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    sin, cos = rotary_embedding(positions, dr, cfg.rope_theta)
    q = rms_norm((x @ p["wq"]).reshape(b, t, h, dn + dr), p["q_norm"],
                 cfg.rms_eps)
    q_nope, q_rope = q[..., :dn], apply_rotary(q[..., dn:], sin, cos)
    kva = x @ p["w_kva"]
    latent = rms_norm(kva[..., :cfg.kv_lora_rank], p["kv_norm"],
                      cfg.rms_eps)
    k_rope = apply_rotary(kva[..., None, cfg.kv_lora_rank:], sin, cos)
    gate = jax.nn.sigmoid(jnp.dot(x, p["w_gate"],
                                  preferred_element_type=jnp.float32))
    return q_nope, q_rope, {"latent": latent, "k_rope": k_rope[..., 0, :]}, \
        gate


@jax.named_scope("attn_out")
def _mla_out(cfg: LingConfig, p, o, gate):
    b, t = o.shape[:2]
    o = (o.astype(jnp.float32) * gate[..., None]).astype(cfg.compute_dtype)
    return o.reshape(b, t, -1) @ p["wo"]


def mla_prefill(cfg: LingConfig, p, x):
    """An MLA layer over whole prompts from position 0, unabsorbed: k
    and v are made from the latent and attended as any attention's.
    -> ([B, T, D], the prompts' cache rows {"latent" [B, T, r],
    "k_rope" [B, T, dr]})."""
    b, t, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    q_nope, q_rope, rows, gate = _mla_inputs(cfg, p, x, positions)
    with jax.named_scope("qkv"):  # (k and v out of the latent)
        kv = (rows["latent"] @ p["w_kvb"]).reshape(b, t, h, dn + dv)
    f32 = jnp.float32
    with jax.named_scope("attn/attn_latent"):
        logits = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :dn],
                             preferred_element_type=f32)
                  + jnp.einsum("bthd,bsd->bhts", q_rope, rows["k_rope"],
                               preferred_element_type=f32))
        logits = logits * (dn + cfg.qk_rope_head_dim) ** -0.5
        causal = jnp.tril(jnp.ones((t, t), bool))
        probs = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", probs.astype(x.dtype),
                       kv[..., dn:], preferred_element_type=f32)
    return _mla_out(cfg, p, o, gate), rows


def mla_step(cfg: LingConfig, p, x, cache, pos):
    """A decode step of an MLA layer in the absorbed form. x [B, 1, D]
    (normed); ``cache`` {"latent" [B, S, r], "k_rope" [B, S, dr]}, each
    slot's rows (two arrays, so that neither product reads a slice of
    the other's); pos [B]. The new row is written at [slot, pos] and the
    step attends over the latent rows themselves: q_nope is carried
    through the key half of ``w_kvb`` into the latent's space, the
    probabilities weigh latents, and the value half brings the result
    back. -> ([B, 1, D], cache)."""
    b = x.shape[0]
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    f32 = jnp.float32
    q_nope, q_rope, rows, gate = _mla_inputs(cfg, p, x, pos[:, None])
    with jax.named_scope("cache"):
        cache = {k: cache[k].at[jnp.arange(b), pos].set(rows[k][:, 0])
                 for k in ("latent", "k_rope")}
    w_kvb = p["w_kvb"].reshape(r, h, dn + dv)
    with jax.named_scope("qkv"):  # (q into the latent's space)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn],
                           preferred_element_type=f32).astype(x.dtype)
    with jax.named_scope("attn/attn_latent"):
        logits = (jnp.einsum("bhr,bsr->bhs", q_lat, cache["latent"],
                             preferred_element_type=f32)
                  + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0],
                               cache["k_rope"], preferred_element_type=f32))
        logits = logits * (dn + cfg.qk_rope_head_dim) ** -0.5
        live = jnp.arange(cache["latent"].shape[1])[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(live[:, None], logits, -1e30), -1)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(x.dtype),
                           cache["latent"], preferred_element_type=f32)
    with jax.named_scope("attn_out"):  # (and back out of it)
        o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype),
                       w_kvb[..., dn:], preferred_element_type=f32)
    return _mla_out(cfg, p, o[:, None], gate), cache


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def prefill(params, tokens, true_lens, cfg: LingConfig,
            aux: dict | None = None):
    """tokens [B, T] (right-padded, ``true_lens`` [B] real) from empty
    state -> (h [B, T, D] before the final norm, the streams' state: a
    list with one entry a layer, {"s", "conv"} or {"latent", "k_rope"}:
    the T cache rows, padding's among them). With
    ``aux`` every expert layer's ids are left in ``aux["expert_ids"]``
    [L_moe, B, T, top_k]."""
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    state, ids = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("qkv"):
            x = rms_norm(h, p["attn_norm"], cfg.rms_eps)
        if cfg.attn_kind(i) == "kda":
            y, st = kda_prefill(cfg, p["attn"], x, true_lens)
        else:
            y, st = mla_prefill(cfg, p["attn"], x)
        with jax.named_scope("attn_out"):
            h = h + y
        state.append(st)
        layer_aux = {} if aux is not None else None
        h = moe.mlp_layer(cfg, cfg.mlp_kind(i) == "moe", p, h, layer_aux)
        if layer_aux:
            ids.append(layer_aux["expert_ids"])
    if ids:
        aux["expert_ids"] = jnp.stack(ids)
    return h, state


def forward(params, tokens, cfg: LingConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    chunkwise KDA and the unabsorbed MLA."""
    b, t = tokens.shape
    h, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: LingConfig, params, tok, layers_state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``layers_state`` as :func:`prefill` leaves it, over all slots.
    -> (float32 logits [B, V], the state updated, and for a model with
    expert layers three [L_moe] int32 counters of the ACTIVE slots'
    routing: distinct held experts touched, assignments, assignments
    to held experts)."""
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    new_state, counts = [], []
    for i, (p, st) in enumerate(zip(params["layers"], layers_state)):
        with jax.named_scope("qkv"):
            x = rms_norm(h, p["attn_norm"], cfg.rms_eps)
        if cfg.attn_kind(i) == "kda":
            y, st = kda_step(cfg, p["attn"], x, st, active)
        else:
            y, st = mla_step(cfg, p["attn"], x, st, pos)
        with jax.named_scope("attn_out"):
            h = h + y
        new_state.append(st)
        sparse = cfg.mlp_kind(i) == "moe"
        aux = {} if sparse else None
        h = moe.mlp_layer(cfg, sparse, p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return moe.logits(cfg, params, h)[:, 0], new_state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """A recurrent state (``S`` and the convolution rows) a KDA layer,
    ``max_len`` rows of latents an MLA layer: not rows that can be cut
    at a position. (No ``row_kinds``: one layer in six keeps rows.)"""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "o_norm", "q_norm",
                  "kv_norm", "a_log", "dt_bias", "router_bias")

    @staticmethod
    def init_state(cfg: LingConfig, slots: int, max_len: int) -> dict:
        h, dk = cfg.n_heads, cfg.kda_head_dim
        cdt = cfg.compute_dtype
        layers = []
        for i in range(cfg.n_layers):
            if cfg.attn_kind(i) == "kda":
                layers.append({
                    "s": jnp.zeros((slots, h, dk, dk), jnp.float32),
                    "conv": jnp.zeros((slots, cfg.conv_kernel - 1,
                                       3 * h * dk), cdt)})
            else:
                layers.append({
                    "latent": jnp.zeros(
                        (slots, max_len, cfg.kv_lora_rank), cdt),
                    "k_rope": jnp.zeros(
                        (slots, max_len, cfg.qk_rope_head_dim), cdt)})
        return {"layers": layers, "max_len": jnp.int32(max_len),
                "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict):
        # (a scalar on the device: a recurrent state has no shape that
        # says how far a slot's position may grow)
        return state["max_len"]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        by_kind = {"recurrent": 0, "latent": 0}
        for st in state["layers"]:
            kind = "latent" if "latent" in st else "recurrent"
            by_kind[kind] += sum(a.size * a.dtype.itemsize
                                 for a in st.values())
        return by_kind

    @staticmethod
    def step(cfg: LingConfig, params, prepared, tok, state, pos, active):
        logits, layers, *counters = step(
            cfg, params, tok, state["layers"], pos, active)
        return logits, {**state, "layers": layers}, *counters

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: LingConfig, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state (a reused slot starts from a
        zero ``S`` and zero convolution rows: the state returned here
        replaces the slot's whole). -> (the streams' state, [F] prompt
        lengths, [F] first tokens, [F] their logprobs, the held experts'
        assignments from the real positions [L_moe, count])."""
        Slots.refuse_prefix(cfg, prefix)
        aux = {} if cfg.moe_layers else None
        h, layers = prefill(params, prompts, true_lens, cfg, aux)
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        loads = (moe.prefill_loads(cfg, aux["expert_ids"], true_lens),) \
            if aux else ()
        return {"layers": layers}, true_lens, toks0, logp0, *loads

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' state into their slots, every leaf of
        a slot replaced whole (a prompt's latent rows, zeros behind)."""
        def put(all_, new):
            whole = jnp.zeros((new.shape[0], *all_.shape[1:]), all_.dtype)
            return all_.at[slots].set(
                whole.at[:, :new.shape[1]].set(new.astype(all_.dtype)))

        layers = jax.tree_util.tree_map(put, state["layers"],
                                        streams["layers"])
        return {**state, "layers": layers,
                "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
