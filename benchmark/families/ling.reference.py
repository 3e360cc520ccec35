"""The plain reference of the family ``ling``: the language model of
Ling-3.0-flash-VL as its ``config.json`` and the public descriptions of
its three mechanisms give it, in straightforward ``jax.numpy``, float32,
highest matmul precision. No cache, no kernel, no sort, no chunked form:

- **KDA** (Kimi Delta Attention, arXiv:2510.26692; flash-linear-attention
  ``fla/layers/kda.py``) as the token-by-token recurrence: q, k, v
  through a causal depthwise convolution of kernel 4 and SiLU; q and k
  L2-normalised a head (q scaled by dk^-1/2); ``log a = lower_bound *
  sigmoid(exp(A_log_h) (x W_f + dt_bias))`` a channel, ``beta =
  sigmoid(x W_beta)`` a head; ``S <- Diag(a) S``, ``S <- S + beta k (v -
  S^T k)^T``, ``o = S^T q``; a head-wise RMS norm times ``sigmoid(x
  W_g)``. No position encoding.
- **MLA** (DeepSeek-V2 section 2.1, no query compression), unabsorbed:
  keys and values are made from the normalised latent for every
  position; a learned RMS norm over each head's whole 192-wide q; RoPE
  (rotate-half) on q's last 64 and on the one 64-wide key every head
  shares; causal softmax over (q_nope k_nope + q_rope k_rope) / sqrt(192);
  each head's output times ``sigmoid(x W_gate)[h]``.
- **MoE** (DeepSeek-V3's routing): ``s = sigmoid(x W_r)``; ``s + b``
  for the selection only; a group's score the sum of its two largest
  biased scores, the ``topk_group`` best groups kept; the ``top_k``
  largest biased scores among them chosen (the lower index on a tie);
  weights ``routed_scaling_factor * s_e / sum_chosen s``. EVERY held
  expert is applied to every token and masked by the gate: that is the
  definition. ``held_experts = (first, count)`` leaves out the same
  experts the program leaves out; the shared expert is added in full.

It computes in blocks: a layer at a time, each under its own ``jit``
with that layer's leaves cast to float32 inside, and the experts one at
a time within the expert layer, so that beside a serving engine's 11 GB
the reference needs a few hundred MB, not the 21 GB of the whole tree
in float32.

``m`` is the dict of ``families/ling.py``'s ``fields``. Shares no code
with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of layers, each
``attn_norm`` / ``attn`` / ``mlp_norm`` / ``mlp``; ``[in, out]``
matrices; q, k and v projections side by side in ``w_qkv`` and their
convolution taps in ``conv [K, 3 H dk]``; the held experts stacked in
``w_gate`` / ``w_up`` / ``w_down``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

L2_EPS = 1e-6  # fla's l2norm: x * rsqrt(sum(x^2) + eps)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, H, D], positions 0..T-1, rotate-half convention."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attn_kind(m, i: int) -> str:
    return "mla" if (i + 1) % m["layer_group_size"] == 0 else "kda"


def mlp_kind(m, i: int) -> str:
    return "dense" if i < m["first_k_dense"] else "moe"


# ---------------------------------------------------------------- KDA


def kda_inputs(m, p, x):
    """x [B, T, D] -> (q, k, v, log decay [B, T, H, dk], beta [B, T, H],
    the convolution's inputs [B, T, 3 H dk])."""
    b, t, _ = x.shape
    h, dk, kk = m["n_heads"], m["kda_head_dim"], m["conv_kernel"]
    u = x @ p["w_qkv"]
    padded = jnp.pad(u, ((0, 0), (kk - 1, 0), (0, 0)))
    y = sum(p["conv"][i] * padded[:, i:i + t] for i in range(kk))
    q, k, v = (a.reshape(b, t, h, dk)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = (x @ p["w_f"] + p["dt_bias"]).reshape(b, t, h, dk)
    g = m["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * f)
    return q, k, v, g, jax.nn.sigmoid(x @ p["w_beta"]), u


def kda_recurrence(q, k, v, g, beta, s0=None):
    """The delta rule, a token at a time. -> (o [B, T, H, dv], the state
    after the last token [B, H, dk, dv])."""
    b, t, h, dk = q.shape

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + b_t[..., None, None] * k_t[..., None] * err[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    if s0 is None:
        s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    s, o = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _kda(m, p, x):
    b, t, _ = x.shape
    q, k, v, g, beta, _ = kda_inputs(m, p, x)
    o, _ = kda_recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid(x @ p["w_g"]).reshape(o.shape)
    o = _rms_norm(o, p["o_norm"], m["rms_eps"]) * gate
    return o.reshape(b, t, -1) @ p["wo"]


# ---------------------------------------------------------------- MLA


def _mla(m, p, x):
    b, t, _ = x.shape
    h, dn, dr = m["n_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    dv, r = m["v_head_dim"], m["kv_lora_rank"]
    q = _rms_norm((x @ p["wq"]).reshape(b, t, h, dn + dr), p["q_norm"],
                  m["rms_eps"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])],
                        -1)
    kva = x @ p["w_kva"]
    latent = _rms_norm(kva[..., :r], p["kv_norm"], m["rms_eps"])
    k_rope = _rope(kva[..., None, r:], m["rope_theta"])  # one for all heads
    kv = (latent @ p["w_kvb"]).reshape(b, t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(
        jnp.float32(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kv[..., dn:])
    o = o * jax.nn.sigmoid(x @ p["w_gate"])[..., None]
    return o.reshape(b, t, h * dv) @ p["wo"]


# ---------------------------------------------------------------- MoE


def router(m, scores, bias):
    """scores [..., E] (the sigmoids) -> (gates [..., E] with ``top_k``
    nonzero entries, the chosen ids [..., top_k])."""
    e, ng, kk = m["n_experts"], m["n_group"], m["top_k"]
    biased = scores + bias
    groups = biased.reshape(*biased.shape[:-1], ng, e // ng)
    group_score = jnp.sort(groups, -1)[..., -2:].sum(-1)
    # the topk_group best groups, the lower index on a tie
    kept = jnp.argsort(-group_score, -1, stable=True)[..., :m["topk_group"]]
    keep = jax.nn.one_hot(kept, ng).sum(-2) > 0  # [..., ng]
    allowed = jnp.where(jnp.repeat(keep, e // ng, -1), biased, -jnp.inf)
    chosen = jnp.argsort(-allowed, -1, stable=True)[..., :kk]
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = m["routed_scaling_factor"] * picked / picked.sum(-1,
                                                              keepdims=True)
    gates = (jax.nn.one_hot(chosen, e) * weights[..., None]).sum(-2)
    return gates, chosen


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_layer(m, p, x, held=None):
    """x [..., D] float32; ``p`` the layer's leaves as stored (the
    experts are cast one at a time). ``held`` = (first, count) says which
    experts ``p`` holds (default: ``m``'s); the others' part is left
    out. -> the held experts' weighted sum plus the shared expert."""
    first, count = held or m.get("held_experts") or (0, m["n_experts"])
    f32 = jnp.float32
    gates, _ = router(m, jax.nn.sigmoid(x @ p["router"].astype(f32)),
                      p["router_bias"].astype(f32))
    held_gates = jnp.moveaxis(gates[..., first:first + count], -1, 0)

    def one(out, e):
        w_gate, w_up, w_down, gate = e
        y = _swiglu(x, w_gate.astype(f32), w_up.astype(f32),
                    w_down.astype(f32))
        return out + gate[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate"], p["w_up"], p["w_down"], held_gates))
    return out + _swiglu(x, p["shared_gate"].astype(f32),
                         p["shared_up"].astype(f32),
                         p["shared_down"].astype(f32))


# ---------------------------------------------------------------- model


@functools.partial(jax.jit, static_argnames=("kind", "m"))
def _attn_block(h, norm, p, kind: str, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return h + (_kda if kind == "kda" else _mla)(m, _f32(p), x)


@functools.partial(jax.jit, static_argnames=("kind", "m"))
def _mlp_block(h, norm, p, kind: str, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        if kind == "dense":
            p = _f32(p)
            return h + _swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
        return h + moe_layer(m, p, x)


@functools.partial(jax.jit, static_argnames="eps")
def _head(h, norm, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, norm.astype(jnp.float32), eps) \
            @ w.astype(jnp.float32)


def _static(m: dict) -> tuple:
    """``m`` as a hashable static argument (its lists as tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def forward(params, tokens, m: dict):
    """tokens [B, T] -> float32 logits [B, T, V], a layer at a time."""
    ms = _static(m)
    h = params["embed"][tokens].astype(jnp.float32)
    for i, p in enumerate(params["layers"]):
        h = _attn_block(h, p["attn_norm"], p["attn"], attn_kind(m, i), ms)
        h = _mlp_block(h, p["mlp_norm"], p["mlp"], mlp_kind(m, i), ms)
    return _head(h, params["final_norm"], params["lm_head"], m["rms_eps"])


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against this f32 reference. Readings on the chip
# at the cell's configuration (2560 wide, 7 layers, 128 of 512 experts
# held, seeded weights; my chip run, PR 32). Logits spread by 1.02. The
# program's forward over 4 x (2 x 512) tokens: they differ by 0.0050 in
# the median and 0.245 at most; its argmax differs from the reference's
# at 78 of 4,096 positions, only where the reference's top two are
# closer than 0.064 (11 of them over 0.02, 2 over 0.05, none over
# 0.075). Prefill then 40 decoded positions through the slot state, four
# slots at different positions (prompts of 65, 100, 333 and 700 tokens):
# median 0.0048-0.0054, largest 0.205, the argmax parts only under 0.060.
# The same forward with its matrices cut to 3 mantissa bits (a float8
# with an ideal scale, the nearest precision below bf16): median 0.032,
# 439 positions part, up to a gap of 0.214 (144 over 0.05, 39 over 0.1,
# 5 over 0.15, 1 over 0.2). So the limit lies between 0.064 and 0.214,
# at their geometric mean: the served token must be the reference's
# argmax wherever its top two are further apart than this; nearer ties
# are counted, not failed. (Why the matrices that write into the stream
# are scaled for the published depth, ``ling.init_params``: unscaled,
# a router near-tie that bf16 decides the other way moved logits by
# over 1, the program parted at gaps up to 0.55 and the control at
# 0.87-1.06: no limit had room on both sides.)
SERVE_TOP2_GAP = 0.12
# Training: the program's bf16 loss against this reference's on the same
# 2 x 512 tokens (a loss of 11.08-11.11 at initialisation). Readings (my
# chip run, PR 32): 0.0000-0.0003 away over four sets; with 3 mantissa
# bits 0.0014-0.0061, every set outside the limit. The precision hardly
# moves a mean over 1,024 positions, so the limit is three times the
# first reading alone. (No cell trains this family.)
TRAIN_LOSS_TOL = 0.001


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens: the served token must
    be the reference's argmax wherever its top two logits are further
    apart than ``SERVE_TOP2_GAP``; nearer ties are counted, not failed."""
    import numpy as np

    seq = jnp.asarray([list(prompt) + list(tokens)], jnp.int32)
    logits = forward(params, seq, m)
    rows = np.asarray(logits[0, len(prompt) - 1: len(prompt) - 1
                             + len(tokens)])
    top2 = np.sort(rows, -1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    want = rows.argmax(-1)
    decided = gap > SERVE_TOP2_GAP
    wrong = decided & (want != np.asarray(tokens))
    return {"positions": int(len(tokens)), "near_ties": int((~decided).sum()),
            "agree": int((want == np.asarray(tokens)).sum()),
            "wrong": int(wrong.sum()), "tolerance": SERVE_TOP2_GAP,
            "ok": bool(wrong.sum() == 0 and decided.sum() > 0)}
