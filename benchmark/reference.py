"""The plain reference: the block's forward and loss in straightforward
``jax.numpy``, float32, highest matmul precision; no cache, no kernels,
no batching tricks, no remat. Follows the published description of a
pre-norm GQA + RoPE (rotate-half) + SwiGLU decoder; ``m`` is the dict of
``manifest.llama_fields``. Shares no code with ``ray_tpu.models``; the
only thing it takes from the program is the parameter tree's layout
(stacked layers, ``[in, out]`` matrices).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def _layer(m, h, p):
    b, t, d = h.shape
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    hd = d // hq
    x = _rms_norm(h, p["attn_norm"], m["rms_eps"])
    q = _rope((x @ p["wq"]).reshape(b, t, hq, hd), m["rope_theta"])
    k = _rope((x @ p["wk"]).reshape(b, t, hkv, hd), m["rope_theta"])
    v = (x @ p["wv"]).reshape(b, t, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(b, t, hq * hd) @ p["wo"]
    x = _rms_norm(h, p["mlp_norm"], m["rms_eps"])
    return h + (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def forward(params, tokens, m: dict):
    """tokens [B, T] -> float32 logits [B, T, V]."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        h = f32["embed"][tokens]
        h, _ = jax.lax.scan(lambda h_, p: (_layer(m, h_, p), None), h,
                            f32["layers"])
        h = _rms_norm(h, f32["final_norm"], m["rms_eps"])
        head = f32["embed"].T if m.get("tie_embeddings") else f32["lm_head"]
        return h @ head


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against the f32 reference. With random weights
# the logits of a 2048-4096-wide model spread by about 1 and bf16 (8
# bits of mantissa, through 24-32 layers) moves one by a few hundredths,
# so the served token must be the reference's argmax wherever the
# reference's top two are further apart than this; nearer ties are
# counted, not failed. Serving in float8 or int8 (errors of tenths)
# fails it on most positions.
SERVE_TOP2_GAP = 0.15
# Training: the program's bf16 loss against the f32 reference's on the
# same 512-token sequences. At initialisation the loss is about
# ln(vocabulary) ~ 10-11 and bf16 moves it by under 0.01; a lower
# precision, or a missing part of the block, moves it by tenths.
TRAIN_LOSS_TOL = 0.03


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens."""
    import numpy as np

    seq = jnp.asarray([list(prompt) + list(tokens)], jnp.int32)
    logits = jax.jit(lambda p, t: forward(p, t, m))(params, seq)
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
