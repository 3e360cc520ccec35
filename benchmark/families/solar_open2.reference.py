"""The plain reference of the family ``solar_open2``: the language model
of Solar-Open2-250B as its ``config.json`` and the public descriptions
of its mechanisms give it, in straightforward ``jax.numpy``, float32,
highest matmul precision. No cache, no kernel, no sort, no chunkwise
form, no flash:

- **KDA** (Kimi Delta Attention, arXiv:2510.26692; flash-linear-attention
  ``fla/layers/kda.py``) as the recurrence A TOKEN AT A TIME: q, k, v
  through a causal depthwise convolution of kernel 4 (no bias) and SiLU;
  q and k L2-normalised a head (eps 1e-6; q scaled by dk^-1/2); log
  decay a channel ``g = -exp(A_log_h) softplus(x W_f_down W_f_up +
  dt_bias)`` (``kda_use_full_proj`` false: rank = the head's width; no
  lower bound); ``beta = 2 sigmoid(x W_beta)`` a head
  (``kda_allow_neg_eigval``: fla doubles beta, "Unlocking State-Tracking
  in Linear RNNs Through Negative Eigenvalues", Grazzi et al. 2024); ``S
  <- Diag(e^g) S``, ``S <- S + beta k (v - S^T k)^T``, ``o = S^T q``; a
  head-wise RMS norm times ``sigmoid(x W_g_down W_g_up)``; ``W_o``. No
  position encoding.
- **GQA** (layers in ``gqa_layers``): q of ``n_heads`` x ``head_dim``,
  k, v of ``n_kv_heads`` x ``head_dim``, NO rotary (``use_rope`` false)
  and no q / k norm; query head h = kv * group + r attends on kv head
  ``kv``; scores q k^T / sqrt(head_dim) WRITTEN OUT, causal
  softmax; the output times ``sigmoid(x W_gate)`` elementwise
  (``use_gqa_gate``; arXiv:2505.06708, the gate after the attention and
  before ``W_o``); ``W_o``.
- **MoE**, every layer (DeepSeek-V3's routing with one group): ``s =
  sigmoid(x W_r)``; ``s + b`` for the selection only; the ``top_k``
  largest biased scores chosen (the lower index on a tie); weights
  ``routed_scaling_factor * s_e / sum_chosen s``. EVERY held expert is
  applied to every token and masked by the gate: that is the
  definition. ``held_experts = (first, count)`` leaves out the same
  experts the program leaves out; the shared expert is added in full,
  unweighted.
- Pre-norm: x' = RMSNorm(x) (eps 1e-5) into attention and into the MLP.

It computes in blocks so that 32,832 positions at the published widths
fit beside a serving engine: a layer at a time, each under its own
``jit`` with that layer's leaves cast to float32 inside; whatever is a
function of a row alone in blocks of :data:`ROWS` rows; the KDA
recurrence over blocks of rows in order, ``S`` and the last three
projection rows handed from block to block (the recurrence is
sequential anyway: the blocks change nothing but what is alive at
once); the scores of :data:`QUERY_ROWS` query rows at a time against
every key; the experts one at a time.

``m`` is the dict of ``families/solar_open2.py``'s ``fields``. Shares no
code with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of layers, each
``attn_norm`` / ``attn`` / ``mlp_norm`` / ``mlp``; ``[in, out]``
matrices; a KDA layer's q, k and v projections side by side in
``w_qkv`` and their convolution taps in ``conv [K, 3 H dk]``; a GQA
layer's q, k and v side by side in ``w_qkv``; the held experts stacked
in ``w_gate`` / ``w_up`` / ``w_down``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

L2_EPS = 1e-6  # fla's l2norm: x * rsqrt(sum(x^2) + eps)
ROWS = 4096  # rows of a block of tokenwise work
QUERY_ROWS = 128  # query rows whose scores exist at once


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def is_gqa(m, i: int) -> bool:
    return i in tuple(m["gqa_layers"])


# ---------------------------------------------------------------- KDA


def kda_inputs(m, p, x, before=None):
    """x [B, T, D] -> (q, k, v, log decay [B, T, H, dk], beta [B, T, H],
    the convolution's inputs [B, T, 3 H dk]). ``before`` [B, K-1, 3 H
    dk]: the convolution's inputs of the rows before x's first (zeros
    at a sequence's start)."""
    b, t, _ = x.shape
    h, dk, kk = m["n_heads"], m["kda_head_dim"], m["conv_kernel"]
    u = x @ p["w_qkv"]
    if before is None:
        before = jnp.zeros((b, kk - 1, u.shape[-1]), u.dtype)
    padded = jnp.concatenate([before, u], axis=1)
    y = sum(p["conv"][i] * padded[:, i:i + t] for i in range(kk))
    q, k, v = (a.reshape(b, t, h, dk)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = ((x @ p["w_f_down"]) @ p["w_f_up"] + p["dt_bias"]).reshape(
        b, t, h, dk)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f)
    beta = 2.0 * jax.nn.sigmoid(x @ p["w_beta"])
    return q, k, v, g, beta, u


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


def _kda_rows(m, p, x, s, before):
    """One block of rows of a KDA layer from the state and the
    convolution's inputs the rows before it left -> (the layer's output
    [B, T, D], S, the block's last K-1 convolution inputs)."""
    b, t, _ = x.shape
    q, k, v, g, beta, u = kda_inputs(m, p, x, before)
    o, s = kda_recurrence(q, k, v, g, beta, s)
    gate = jax.nn.sigmoid((x @ p["w_g_down"]) @ p["w_g_up"]).reshape(o.shape)
    o = _rms_norm(o, p["o_norm"], m["rms_eps"]) * gate
    tail = jnp.concatenate([before, u], axis=1)[:, -(m["conv_kernel"] - 1):]
    return o.reshape(b, t, -1) @ p["wo"], s, tail


def _kda(m, p, x):
    """A KDA layer over whole sequences from an empty state."""
    b = x.shape[0]
    h, dk = m["n_heads"], m["kda_head_dim"]
    return _kda_rows(
        m, p, x, jnp.zeros((b, h, dk, dk), jnp.float32),
        jnp.zeros((b, m["conv_kernel"] - 1, 3 * h * dk), jnp.float32))[0]


# ---------------------------------------------------------------- GQA


def gqa_qkv(m, p, x):
    """x [B, T, D] -> (q [B, T, Hq, hd], k, v [B, T, Hkv, hd])."""
    b, t, _ = x.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    qkv = x @ p["w_qkv"]
    return (qkv[..., :hq * hd].reshape(b, t, hq, hd),
            qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd),
            qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd))


def attend(q, k, v, first):
    """Query rows ``first`` .. of q [B, Tq, Hq, hd] over every key [B,
    T, Hkv, hd], query head h = kv * group + r on kv head ``kv``: the
    scores written out, the causal mask, softmax. -> [B, Tq, Hq, hd]."""
    b, tq, hq, hd = q.shape
    t, hkv = k.shape[1:3]
    qg = q.reshape(b, tq, hkv, hq // hkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k) / jnp.sqrt(jnp.float32(hd))
    seen = jnp.arange(t)[None, :] <= first + jnp.arange(tq)[:, None]
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, tq, hq, hd)


def _gqa(m, p, x):
    """A GQA layer over whole sequences, every query row at once (the
    tests' sizes; :func:`forward` takes ``QUERY_ROWS`` at a time)."""
    b, t, _ = x.shape
    q, k, v = gqa_qkv(m, p, x)
    o = attend(q, k, v, 0).reshape(b, t, -1)
    return (o * jax.nn.sigmoid(x @ p["w_gate"])) @ p["wo"]


# ---------------------------------------------------------------- MoE


def router(m, scores, bias):
    """scores [..., E] (the sigmoids) -> (gates [..., E] with ``top_k``
    nonzero entries, the chosen ids [..., top_k]). One group: the
    ``top_k`` largest biased scores, the lower index on a tie."""
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


def _static(m: dict) -> tuple:
    """``m`` as a hashable static argument (its lists as tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnames="m")
def _kda_block(h, norm, p, s, before, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        y, s, tail = _kda_rows(m, _f32(p), x, s, before)
        return h + y, s, tail


@functools.partial(jax.jit, static_argnames="m")
def _gqa_project(h, norm, p, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return gqa_qkv(m, {"w_qkv": p["w_qkv"].astype(jnp.float32)}, x)


@functools.partial(jax.jit, static_argnames="m")
def _gqa_attend(h, norm, p, q, k, v, first, m):
    """The stream's rows ``first`` .. (h, q: those rows' own) over every
    key, gated, projected and added."""
    m = dict(m)
    b, t, _ = h.shape
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        o = attend(q, k, v, first).reshape(b, t, -1)
        o = o * jax.nn.sigmoid(x @ p["w_gate"].astype(jnp.float32))
        return h + o @ p["wo"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames="m")
def _mlp_block(h, norm, p, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return h + moe_layer(m, p, x)


@functools.partial(jax.jit, static_argnames="eps")
def _head(h, norm, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, norm.astype(jnp.float32), eps) \
            @ w.astype(jnp.float32)


def _by_rows(fn, h, rows: int):
    """``fn`` of each block of ``rows`` rows of h [B, T, ...] in order,
    its results end to end."""
    return jnp.concatenate([fn(i, h[:, i:i + rows])
                            for i in range(0, h.shape[1], rows)], axis=1)


def hidden(params, tokens, m: dict, states: list | None = None):
    """tokens [B, T] -> the stream [B, T, D] before the final norm, a
    layer at a time. With ``states`` (a list) every KDA layer's state
    after the last token is appended to it."""
    ms = _static(m)
    b = tokens.shape[0]
    hh, dk = m["n_heads"], m["kda_head_dim"]
    h = params["embed"][tokens].astype(jnp.float32)
    for i, p in enumerate(params["layers"]):
        if is_gqa(m, i):
            q, k, v = (jnp.concatenate(a, axis=1) for a in zip(*(
                _gqa_project(h[:, j:j + ROWS], p["attn_norm"], p["attn"], ms)
                for j in range(0, h.shape[1], ROWS))))
            h = _by_rows(lambda j, rows: _gqa_attend(
                rows, p["attn_norm"], p["attn"], q[:, j:j + QUERY_ROWS], k,
                v, j, ms), h, QUERY_ROWS)
        else:
            carry = {"s": jnp.zeros((b, hh, dk, dk), jnp.float32),
                     "tail": jnp.zeros((b, m["conv_kernel"] - 1,
                                        3 * hh * dk), jnp.float32)}

            def rows(j, h_rows, p=p, carry=carry):
                out, carry["s"], carry["tail"] = _kda_block(
                    h_rows, p["attn_norm"], p["attn"], carry["s"],
                    carry["tail"], ms)
                return out

            h = _by_rows(rows, h, ROWS)
            if states is not None:
                states.append(carry["s"])
        h = _by_rows(lambda j, rows: _mlp_block(
            rows, p["mlp_norm"], p["mlp"], ms), h, ROWS)
    return h


def forward(params, tokens, m: dict, last: int | None = None):
    """tokens [B, T] -> float32 logits [B, T, V] (``last``: of the last
    ``last`` positions alone, [B, last, V])."""
    h = hidden(params, tokens, m)
    if last is not None:
        h = h[:, -last:]
    return _by_rows(lambda j, rows: _head(
        rows, params["final_norm"], params["lm_head"], m["rms_eps"]), h,
        ROWS)


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against this f32 reference. Readings on the chip
# (PR 42, the cell's configuration, one seed: a 32,768-token prompt
# through the engine's segmented prefill and 64 served tokens, logits of
# the prompt's last 1,024 positions; logits span about 1): the program in
# bf16 is off by 0.0027 in the median and 0.052 at most, its argmax
# parts at 10 of 1,024 positions (2 of the 64 served), only under a gap
# of 0.0128; its three float32 states are 0.4-0.6% off the recurrence's.
# The same program with its matrices cut to 3 mantissa bits (a float8
# with an ideal scale, the nearest precision below bf16): median 0.027,
# 100 positions part, up to a gap of 0.107 (50 over 0.02, 13 over
# 0.05); states 7% off. Their geometric mean is 0.037. At the cell's own
# probe (127-token prompts, 24 served tokens, 8 prompts a seed, 16 seeds,
# 3,072 positions) the program in bf16 parts at 41 positions and never
# over a gap of 0.0201; the 3-bit control parts at 11-24 positions a
# seed, 2-12 of them over 0.037 in every seed, its largest gap
# 0.0615-0.190 by seed: the limit lies between 0.0201 and 0.0615 too
# (geometric mean 0.035). The served token must be the reference's
# argmax wherever its top two are further apart than this; nearer ties
# are counted, not failed.
SERVE_TOP2_GAP = 0.037
# Training: no cell trains this family; the limit is Ling's, whose block
# this one shares its KDA and expert layers with.
TRAIN_LOSS_TOL = 0.001


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens: the served token must
    be the reference's argmax wherever its top two logits are further
    apart than ``SERVE_TOP2_GAP``; nearer ties are counted, not failed."""
    import numpy as np

    seq = jnp.asarray([list(prompt) + list(tokens)], jnp.int32)
    rows = np.asarray(forward(params, seq, m, last=len(tokens) + 1)[0, :-1])
    top2 = np.sort(rows, -1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    want = rows.argmax(-1)
    decided = gap > SERVE_TOP2_GAP
    wrong = decided & (want != np.asarray(tokens))
    return {"positions": int(len(tokens)), "near_ties": int((~decided).sum()),
            "agree": int((want == np.asarray(tokens)).sum()),
            "wrong": int(wrong.sum()), "tolerance": SERVE_TOP2_GAP,
            "ok": bool(wrong.sum() == 0 and decided.sum() > 0)}
