"""The plain reference of the family ``olmoe``: OLMoE's decoder block as
its authors publish it (``modeling_olmoe.py`` of
huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct; arXiv:2409.02060), in
straightforward ``jax.numpy``, float32, highest matmul precision. No
cache, no kernel, no sort, no batching trick:

- pre-norm attention whose q and k projections are RMS-normalised over
  the WHOLE projection (all heads' channels together, a learned scale
  each) before the heads are split and rotated; rotate-half RoPE,
  causal softmax attention;
- a router that is a softmax over all experts' logits in float32, of
  which the ``top_k`` largest are kept (``lax.top_k``) and **not
  renormalised** (``norm_topk_prob: false``; with the flag on they are
  divided by their sum);
- SwiGLU experts. Every expert is applied to every token and masked by
  the gate: that is the definition (E / top_k times the work of a
  routed implementation, fine at the sizes a reference runs);
- mean next-token cross-entropy.

Departures from the published model, each on purpose: the
load-balancing and router z losses of the pretraining recipe are not
part of the published forward (``output_router_logits`` is off) and are
left out; ``clip_qkv`` is null in the published configuration and is
not implemented (the family file refuses a configuration that sets it).

``m`` is the dict of ``families/olmoe.py``'s ``fields``. Shares no code
with ``ray_tpu.models`` nor with ``benchmark/reference.py``; it takes
from the program the parameter tree's layout alone (stacked layers,
``[in, out]`` matrices, experts stacked in ``w_gate`` / ``w_up`` /
``w_down``, ``q_norm`` / ``k_norm`` beside ``wq`` / ``wk``).
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


def router(m, x, w_router):
    """x [..., D] -> (gates [..., E] with top_k nonzero entries, the
    chosen experts' ids [..., top_k])."""
    probs = jax.nn.softmax(x @ w_router, -1)
    kept, chosen = jax.lax.top_k(probs, m["top_k"])
    if m.get("norm_topk_prob", True):
        kept = kept / kept.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(chosen, m["n_experts"]) * kept[..., None]).sum(-2)
    return gates, chosen


def _experts(m, x, p):
    gates, chosen = router(m, x, p["router"])

    def one(out, e):
        w_gate, w_up, w_down, gate = e
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return out + gate[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate"], p["w_up"], p["w_down"], jnp.moveaxis(gates, -1, 0)))
    return out, chosen


def _layer(m, h, p):
    b, t, d = h.shape
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    hd = d // hq
    x = _rms_norm(h, p["attn_norm"], m["rms_eps"])
    q, k = x @ p["wq"], x @ p["wk"]
    if m.get("qk_norm"):
        q = _rms_norm(q, p["q_norm"], m["rms_eps"])
        k = _rms_norm(k, p["k_norm"], m["rms_eps"])
    q = _rope(q.reshape(b, t, hq, hd), m["rope_theta"])
    k = _rope(k.reshape(b, t, hkv, hd), m["rope_theta"])
    v = (x @ p["wv"]).reshape(b, t, hkv, hd)
    k, v = (jnp.repeat(a, hq // hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(b, t, hq * hd) @ p["wo"]
    y, chosen = _experts(m, _rms_norm(h, p["mlp_norm"], m["rms_eps"]), p)
    return h + y, chosen


def forward_and_routing(params, tokens, m: dict):
    """tokens [B, T] -> (float32 logits [B, T, V], the experts each
    layer chose [L, B, T, top_k])."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        h = f32["embed"][tokens]
        h, chosen = jax.lax.scan(lambda h_, p: _layer(m, h_, p), h,
                                 f32["layers"])
        h = _rms_norm(h, f32["final_norm"], m["rms_eps"])
        return h @ f32["lm_head"], chosen


def forward(params, tokens, m: dict):
    """tokens [B, T] -> float32 logits [B, T, V]."""
    return forward_and_routing(params, tokens, m)[0]


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against this f32 reference. Readings on the chip
# at the cell's configuration (2048 wide, 4 layers, 64 experts, top-8,
# seeded weights, 2 x 512 tokens on each of two seeds, the program's
# forward and its prefill through the cache; my chip run, PR 27): logits
# spread by 1.00; they differ by 0.0054 in the median and 0.27 at most
# (a bf16 router flips near-ties: the top-8 sets agree on 95.0-95.5% of
# the (token, layer) pairs, and one other expert moves a logit by
# tenths); the program's argmax differs from the reference's only where
# the reference's top two are closer than 0.104 (the largest over 8,192
# positions; 0.035-0.075 in the other seven sets of 1,024). The same
# forward with its weights cut to 3 mantissa bits (a float8 with an
# ideal scale, the nearest precision below bf16) misses positions whose
# top two are up to 0.233-0.242 apart, 8-11 a thousand over 0.15; with
# the q/k norm left out 0.37-0.42 (26-33 over 0.15); with the kept gates
# renormalised 0.66-0.77 (185-189). So the limit lies between the two
# readings, about their geometric mean: the served token must be the
# reference's argmax wherever its top two are further apart than this;
# nearer ties are counted, not failed.
SERVE_TOP2_GAP = 0.15
# Training: the program's bf16 loss against this reference's on the same
# 2 x 512 tokens (a loss of 11.3-11.4 at initialisation). Readings (my
# chip run, PR 27): bf16 0.0000-0.0007 away over four seeds; gates
# renormalised 0.0079-0.0157, q/k norm left out 0.0021-0.0049: both
# fail this. 3-mantissa-bit weights read 0.0036 and 0.0002: a mean over
# 1,024 positions averages unbiased rounding away, so a lower precision
# is caught by the logits above, not reliably by a loss; no limit on the
# loss could. (No cell trains this family yet.)
TRAIN_LOSS_TOL = 0.0015


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens: the served token must
    be the reference's argmax wherever its top two logits are further
    apart than ``SERVE_TOP2_GAP``; nearer ties are counted, not failed."""
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
