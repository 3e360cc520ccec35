"""The plain reference of the family ``exaone_moe``: the language model of
K-EXAONE-236B-A23B as its ``config.json`` gives it, in straightforward
``jax.numpy``, float32, highest matmul precision. A full forward over
the whole sequence: no cache, no ring, no kernel, no sort.

- **Attention**, every layer: q = x' Wq (``n_heads`` x ``head_dim``),
  k = x' Wk, v = x' Wv (``n_kv_heads`` x ``head_dim``); a learned RMS
  norm over each head's ``head_dim`` of q and of k; in a
  ``sliding_attention`` layer RoPE (rotate-half, theta 1e6) on both, in
  a ``full_attention`` layer none; the kv heads repeated for their
  query heads (head h = kv * group + r); scores q k^T / sqrt(head_dim);
  the mask WRITTEN OUT: key j is seen from query i if j <= i and, in a
  sliding layer, i - j < ``sliding_window``; softmax; Wo.
- **MLP**: layer kinds from ``mlp_layer_types``. ``dense``: SwiGLU.
  ``sparse`` (DeepSeek-V3's routing without a group limit): ``s =
  sigmoid(x W_r)``; ``s + b`` for the selection only; the ``top_k``
  largest biased scores chosen (the lower index on a tie); weights
  ``routed_scaling_factor * s_e / sum_chosen s``. EVERY held expert is
  applied to every token and masked by the gate: that is the
  definition. ``held_experts = (first, count)`` leaves out the same
  experts the program leaves out; the shared expert is added in full,
  unweighted.
- Pre-norm: x' = RMSNorm(x) (eps 1e-5) into attention and into the MLP.

Departures from the published description, each the configuration
file's ``assumed`` or ``left_out``: where ``config.json`` is silent the
family's convention is taken (the head-wise q and k norms and rotary
positions in the sliding layers only are EXAONE 4.0's; the pre-norm
block and the router's selection-only bias are DeepSeek-V3's, whose keys
``exaone_moe`` carries); the multi-token-prediction module is not built;
the absent experts' part of an expert layer's sum is left out.

It computes in blocks: a layer at a time, each under its own ``jit``
with that layer's leaves cast to float32 inside, and the experts one at
a time within the expert layer, so that beside a serving engine's 9 GB
the reference needs the dense MLP's 1.4 GB in float32 at most, not the
15 GB of the whole tree.

``m`` is the dict of ``families/exaone_moe.py``'s ``fields``. Shares no
code with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of layers, each
``attn_norm`` / ``attn`` / ``mlp_norm`` / ``mlp``; ``[in, out]``
matrices; the q, k and v projections side by side in ``w_qkv``; the held
experts stacked in ``w_gate`` / ``w_up`` / ``w_down``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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


# ---------------------------------------------------------- attention


def attention(m, p, x, sliding: bool):
    """x [B, T, D] (normed) -> [B, T, D]."""
    b, t, _ = x.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    qkv = x @ p["w_qkv"]
    q = qkv[..., :hq * hd].reshape(b, t, hq, hd)
    k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd)
    q = _rms_norm(q, p["q_norm"], m["rms_eps"])
    k = _rms_norm(k, p["k_norm"], m["rms_eps"])
    if sliding:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    i = jnp.arange(t)[:, None]  # the query's position
    j = jnp.arange(t)[None, :]  # the key's
    seen = j <= i
    if sliding:
        seen = seen & (i - j < m["sliding_window"])
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, t, hq * hd) @ p["wo"]


# ---------------------------------------------------------------- MoE


def router(m, scores, bias):
    """scores [..., E] (the sigmoids) -> (gates [..., E] with ``top_k``
    nonzero entries, the chosen ids [..., top_k])."""
    e, kk = m["n_experts"], m["top_k"]
    chosen = jnp.argsort(-(scores + bias), -1, stable=True)[..., :kk]
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


@functools.partial(jax.jit, static_argnames=("sliding", "m"))
def _attn_block(h, norm, p, sliding: bool, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return h + attention(m, _f32(p), x, sliding)


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
    for p, attn_kind, mlp_kind in zip(
            params["layers"], m["layer_types"], m["mlp_layer_types"]):
        h = _attn_block(h, p["attn_norm"], p["attn"],
                        attn_kind == "sliding_attention", ms)
        h = _mlp_block(h, p["mlp_norm"], p["mlp"], mlp_kind, ms)
    return _head(h, params["final_norm"], params["lm_head"], m["rms_eps"])


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against this f32 reference. Readings on the chip
# at the cell's configuration (6144 wide, 5 layers, 16 of 128 experts
# held, seeded weights, two seeds; my chip runs, PR 34). Logits spread
# by 1.03. The program's forward over 4 x 1,024 tokens: they differ by
# 0.0043 in the median and 0.118 / 0.126 at most; its argmax differs
# from the reference's at 57 / 63 of 4,096 positions, only where the
# reference's top two are closer than 0.058 / 0.040 (3 of them over
# 0.02, 1 / 0 over 0.05); the 3,584 positions past the window read the
# same. Prefill then 300 decoded positions through the rings and the
# full stack with the kernel, four slots at different positions and two
# inactive (prompts of 65, 100, 333 and 700 tokens: two wraps of every
# ring and more): median 0.0042, largest 0.127, the argmax parts at 24 /
# 16 of 1,200 positions, only under 0.020 / 0.019. The same forward
# with its matrices cut to 3 mantissa bits (a float8 with an ideal
# scale, the nearest precision below bf16): median 0.035, 449 / 426
# positions part, up to a gap of 0.236 / 0.201 (138 / 125 over 0.05,
# 40 / 33 over 0.1, 6 / 10 over 0.15, 1 over 0.2). So the limit lies
# between 0.058 (the largest bf16 reading) and 0.201 (the smaller
# control), at their geometric mean: the served token must be the
# reference's argmax wherever its top two are further apart than this;
# nearer ties are counted, not failed. (With ``wo`` scaled like the
# MLPs' ``w_down``, ``exaone.init_params``, the readings were 0.045 and
# 0.142; that initialisation made what a step reads hang on the seed.)
SERVE_TOP2_GAP = 0.11
# Training: no cell trains this family and no reading was taken; the
# limit is the ``ling`` family's, whose block shares the expert layer
# and the scaled initialisation (a loss near ln(vocabulary) that bf16
# moves by under 0.0003 over 1,024 positions).
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
