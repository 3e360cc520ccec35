"""The plain reference of the family ``glm5_next``: the language model of
GLM-5.3-Flash as its ``config.json`` and the public descriptions of its
mechanisms give it, in straightforward ``jax.numpy``, float32, highest
matmul precision. A full forward over the whole sequence: no cache, no
kernel, no chunkwise form, no absorbed product, no bias, no batching.

``N`` a learned RMS norm (eps ``rms_eps``). Layer ``i`` is a KDA layer
where ``layer_types[i] == 0`` and a sparse latent-attention layer where
it is 1.

- **The residual path (mHC, arXiv:2512.24880), round every sublayer
  ``F``** (each with its own ``hc_phi``, ``hc_b``, ``hc_alpha``): the
  state ``X [n, D]`` a row (n = ``hc_mult``). ``x~ = vec(X) / sqrt(mean(
  vec(X)^2) + hc_eps)``; ``c = x~ phi``; ``H_pre = sigmoid(alpha_0 c[:n]
  + b[:n])``; ``H_post = 2 sigmoid(alpha_1 c[n:2n] + b[n:2n])``; ``H_res``:
  ``exp(alpha_2 c[2n:] + b[2n:])`` as an n x n matrix (row-major), then a
  LOOP of ``hc_sinkhorn_iters`` rounds, each dividing every row by its
  sum + ``hc_eps`` and then every column by its sum + ``hc_eps``. ``u =
  H_pre X``; ``y = F(N(u))``; ``X <- H_res X + H_post^T y``. The
  embedding enters as n copies; the final norm and the head read the
  streams' sum.
- **KDA** (Kimi Delta Attention, arXiv:2510.26692) as the recurrence A
  TOKEN AT A TIME: q, k, v through a causal depthwise convolution of
  kernel 4 (no bias) and SiLU; q and k L2-normalised a head (eps 1e-6; q
  scaled by dk^-1/2); log decay a channel ``g = kda_lower_bound sigmoid(
  exp(A_log_h) (x W_f_down W_f_up + dt_bias))`` (``gate_lower_bound``);
  ``beta = sigmoid(x W_beta)`` a head; ``S <- Diag(e^g) S``, ``S <- S +
  beta k (v - S^T k)^T``, ``o = S^T q``; a head-wise RMS norm times
  ``sigmoid(x W_g_down W_g_up)``; ``W_o``. No position encoding.
- **Latent attention without positions**: ``c_q = N(x W_qa)``, ``q = c_q
  W_qb`` as ``n_heads`` heads of ``qk_nope_head_dim``; ``c = N(x
  W_kva)``; UNABSORBED a head: ``[k | v]_h = c W_kvb,h``, scores ``q . k /
  sqrt(qk_nope_head_dim)`` over the rows of the set ``S(t)``, softmax,
  ``o_h = a v_h``; ``W_o``. Nothing rotated, no gate.
- **``S(t)``, the indexer with pooled keys**: ``q_I = c_q W_Iq`` as
  ``index_heads`` heads of ``index_head_dim``, ``k_I = LayerNorm(x
  W_Ik)`` (eps 1e-6, a scale and a bias), nothing rotated; ``w = x W_Iw /
  sqrt(index_heads) / sqrt(index_head_dim)``. Block ``j`` holds
  positions ``pool j .. pool j + pool - 1`` (``pool`` = ``index_pool``);
  its key is the MEAN of its ``k_I`` (a reshaped array's mean); it is
  whole for row ``t`` when ``pool j + pool - 1 <= t``. ``I[t, j] = sum_h
  w[t, h] relu(q_I[t, h] . kbar[j])``; the whole blocks sorted by ``-I``
  with a STABLE full ``argsort`` (a tie: the earlier block), the first
  ``min(index_topk / pool, whole blocks)`` of them; ``S(t)`` = their
  rows and the tail, rows ``pool ((t + 1) // pool) .. t``.
- **MLP**: layer ``i < first_k_dense`` a dense SwiGLU; else DeepSeek-V3's
  routing with one group (sigmoid scores, a selection-only bias, the
  ``top_k`` largest, the lower index on a tie, weights
  ``routed_scaling_factor s / sum s``), EVERY held expert applied to
  every token and masked by the gate, the shared expert added
  unweighted. Every SwiGLU is ``silu(min(g, swiglu_limit)) clip(u,
  -limit, limit)``.

Departures from the published description (the configuration file's
``assumed`` and ``left_out``): the pooling function (mean) and what
``index_topk`` counts (rows: ``index_topk / index_kpool`` blocks) are
readings (``assumed.index_kpool``); mHC is the paper's form, the norm
without a learned scale (``assumed.mhc``); the prediction layer and the
tower are not run (``left_out``); one chip's experts and an eighth of
the vocabulary (``reduced``); no exchange between chips.

It computes in blocks so that 33,280 positions at the published widths
fit one chip: a sublayer at a time under its own ``jit`` with that
layer's leaves cast to float32 inside, the streams in blocks of
:data:`ROWS` rows (kept on the HOST between sublayers where the
sequence is longer than two blocks: 33,280 x 4 x 4,096 float32 numbers
are 2.2 GB), the KDA recurrence over the blocks in order, ``S`` and the
last three projection rows handed on; index scores, the sort and
attention for :data:`QUERY_ROWS` query rows at a time, the heads one
after another; the experts one at a time.

``m`` is the dict of ``families/glm5_next.py``'s ``fields``. Shares no
code with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of layers, each
``attn_norm`` / ``attn`` / ``hc_attn`` / ``mlp_norm`` / ``mlp`` /
``hc_mlp``; ``[in, out]`` matrices; a KDA layer's q, k and v projections
side by side in ``w_qkv`` and their taps in ``conv [K, 3 H dk]``; a
sparse layer's ``w_kvb`` a head's ``[k | v]`` side by side).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

L2_EPS = 1e-6
ROWS = 4096  # rows of a block of tokenwise work
QUERY_ROWS = 256  # query rows whose scores exist at once
NO_BLOCK = 2 ** 30  # an empty place of a row's chosen blocks


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def _swiglu(m, x, w_gate, w_up, w_down):
    g, u = x @ w_gate, x @ w_up
    limit = m.get("swiglu_limit")
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return (jax.nn.silu(g) * u) @ w_down


# ----------------------------------------------------------- the streams


def sinkhorn(h, iters: int, eps: float):
    """h [..., n, n] positive: ``iters`` rounds of rows then columns."""
    for _ in range(iters):
        h = h / (h.sum(-1, keepdims=True) + eps)
        h = h / (h.sum(-2, keepdims=True) + eps)
    return h


def hc_coefficients(m, p, xs, iters: int | None = None):
    """The streams xs [B, T, n, D] -> (H_pre [B, T, n], H_post [B, T, n],
    H_res [B, T, n, n])."""
    n = m["hc_mult"]
    flat = xs.reshape(*xs.shape[:2], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + m["hc_eps"])
    c = flat @ p["hc_phi"]
    a, b = p["hc_alpha"], p["hc_b"]
    pre = jax.nn.sigmoid(a[0] * c[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * c[..., n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * c[..., 2 * n:] + b[2 * n:]).reshape(
        *c.shape[:2], n, n)
    return pre, post, sinkhorn(
        res, m["hc_sinkhorn_iters"] if iters is None else iters, m["hc_eps"])


def hc_sublayer(m, p, xs, fn, iters: int | None = None,
                keep_res: bool = True):
    """One sublayer round the streams: ``u = H_pre X``, ``y = fn(u)``,
    ``X <- H_res X + H_post^T y``. ``fn`` -> (y, whatever it hands on).
    (``iters`` / ``keep_res``: a test's controls, a shorter Sinkhorn
    loop and the state's update without ``H_res``.)"""
    pre, post, res = hc_coefficients(m, p, xs, iters)
    y, extra = fn(jnp.einsum("btn,btnd->btd", pre, xs))
    mixed = jnp.einsum("btij,btjd->btid", res, xs) if keep_res else xs
    return mixed + post[..., None] * y[:, :, None, :], extra


# ---------------------------------------------------------------- KDA


def kda_inputs(m, p, x, before):
    b, t, _ = x.shape
    h, dk, kk = m["n_heads"], m["kda_head_dim"], m["conv_kernel"]
    u = x @ p["w_qkv"]
    padded = jnp.concatenate([before, u], axis=1)
    y = sum(p["conv"][i] * padded[:, i:i + t] for i in range(kk))
    q, k, v = (a.reshape(b, t, h, dk)
               for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    f = ((x @ p["w_f_down"]) @ p["w_f_up"] + p["dt_bias"]).reshape(
        b, t, h, dk)
    g = m["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["a_log"])[:, None] * f)
    beta = jax.nn.sigmoid(x @ p["w_beta"])
    return q, k, v, g, beta, u


def kda_recurrence(q, k, v, g, beta, s0):
    """The delta rule, a token at a time."""
    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + b_t[..., None, None] * k_t[..., None] * err[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s, o = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def kda_rows(m, p, x, s, before):
    """One block of rows of a KDA layer from the state and the
    convolution's inputs the rows before it left -> (the layer's output
    [B, T, D], (S, the block's last K-1 convolution inputs))."""
    b, t, _ = x.shape
    q, k, v, g, beta, u = kda_inputs(m, p, x, before)
    o, s = kda_recurrence(q, k, v, g, beta, s)
    gate = jax.nn.sigmoid((x @ p["w_g_down"]) @ p["w_g_up"]).reshape(o.shape)
    o = _rms_norm(o, p["o_norm"], m["rms_eps"]) * gate
    tail = jnp.concatenate([before, u], axis=1)[:, -(m["conv_kernel"] - 1):]
    return o.reshape(b, t, -1) @ p["wo"], (s, tail)


def kda_empty(m, b: int):
    h, dk = m["n_heads"], m["kda_head_dim"]
    return (jnp.zeros((b, h, dk, dk), jnp.float32),
            jnp.zeros((b, m["conv_kernel"] - 1, 3 * h * dk), jnp.float32))


# -------------------------------------------------- sparse latent attention


def latents(m, p, x):
    """x [B, T, D] (normed) -> (the latent c [B, T, r], the index key
    k_I [B, T, di]): what a row leaves for later rows."""
    c = _rms_norm(x @ p["w_kva"], p["kv_norm"], m["rms_eps"])
    k_i = _layer_norm(x @ p["w_ik"], p["ik_norm"], p["ik_bias"],
                      m["index_norm_eps"])
    return c, k_i


def pooled(k_i, pool: int):
    """k_I [B, T, di] -> the whole blocks' keys [B, T // pool, di]."""
    b, t, di = k_i.shape
    n = t // pool
    return k_i[:, :n * pool].reshape(b, n, pool, di).mean(2)


def queries(m, p, x):
    b, t, _ = x.shape
    hi, di = m["index_heads"], m["index_head_dim"]
    c_q = _rms_norm(x @ p["w_qa"], p["q_norm"], m["rms_eps"])
    q = (c_q @ p["w_qb"]).reshape(b, t, m["n_heads"], m["qk_nope_head_dim"])
    q_i = (c_q @ p["w_iq"]).reshape(b, t, hi, di)
    w = (x @ p["w_iw"]) / jnp.sqrt(jnp.float32(hi)) / jnp.sqrt(
        jnp.float32(di))
    return q, q_i, w


def index_scores(q_i, w, keys):
    """q_i [B, T, Hi, di], w [B, T, Hi], keys [B, S, di] -> I [B, T, S],
    a head at a time."""
    def head(acc, xs):
        q_j, w_j = xs
        return acc + w_j[..., None] * jax.nn.relu(
            jnp.einsum("btd,bsd->bts", q_j, keys)), None

    zero = jnp.zeros((*q_i.shape[:2], keys.shape[1]), jnp.float32)
    return jax.lax.scan(head, zero, (jnp.moveaxis(q_i, 2, 0),
                                     jnp.moveaxis(w, 2, 0)))[0]


def chosen_blocks(scores, q_first, pool: int, blocks: int):
    """scores [B, T, S] of query rows at ``q_first`` .. over S pooled
    keys -> the rows' chosen blocks as INDICES [B, T, min(blocks, S)]
    int32: the whole blocks in the order of a stable full argsort of
    ``-I``, the first ``min(blocks, whole)`` of them; the places a short
    row leaves empty hold :data:`NO_BLOCK`."""
    t, s = scores.shape[1:]
    at = q_first + jnp.arange(t)[:, None]
    whole = pool * jnp.arange(s)[None, :] + pool - 1 <= at
    order = jnp.argsort(jnp.where(whole[None], -scores, jnp.inf), -1,
                        stable=True)[..., :blocks]
    filled = jnp.arange(order.shape[-1])[None, :] < jnp.minimum(
        blocks, (at + 1) // pool)
    return jnp.where(filled[None], order, NO_BLOCK).astype(jnp.int32)


def seen_rows(sets, q_first, pool: int, rows: int, tail: bool = True):
    """Chosen blocks as indices [B, T, k] -> [B, T, rows] bool: whether
    row ``s`` is in ``S(t)``: a chosen block's row, or (``tail``) one of
    the open block's, ``pool ((t + 1) // pool) .. t``."""
    b, t, _ = sets.shape
    n = -(-rows // pool)
    hit = jnp.zeros((b, t, n + 1), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        jnp.minimum(sets, n)].set(True)[..., :n]
    seen = jnp.repeat(hit, pool, axis=-1)[..., :rows]
    if tail:
        at = q_first + jnp.arange(t)[:, None]
        s = jnp.arange(rows)[None, :]
        seen = seen | ((s >= (at + 1) // pool * pool) & (s <= at))[None]
    return seen


def attend(m, p, q, c, seen):
    """q [B, T, H, dn] over the keys' latents c [B, S, r], ``seen`` [B,
    T, S] bool -> [B, T, H, dv]: unabsorbed, a head at a time."""
    dn, dv, r = m["qk_nope_head_dim"], m["v_head_dim"], m["kv_lora_rank"]
    w_kvb = jnp.moveaxis(p["w_kvb"].reshape(r, m["n_heads"], dn + dv), 1, 0)
    scale = 1.0 / jnp.sqrt(jnp.float32(dn))

    def head(_, xs):
        w_h, q_h = xs
        kv = c @ w_h  # [B, S, dn + dv]
        s = jnp.einsum("btd,bsd->bts", q_h, kv[..., :dn]) * scale
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        # (a row that sees no key, the first of a control without the
        # tail, reads zeros)
        a = jnp.where(seen.any(-1, keepdims=True), a, 0.0)
        return None, jnp.einsum("bts,bsd->btd", a, kv[..., dn:])

    _, o = jax.lax.scan(head, None, (w_kvb, jnp.moveaxis(q, 2, 0)))
    return jnp.moveaxis(o, 0, 2)


# ---------------------------------------------------------------- MoE


def router(m, scores, bias):
    e, kk = m["n_experts"], m["top_k"]
    chosen = jnp.argsort(-(scores + bias), -1, stable=True)[..., :kk]
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = m["routed_scaling_factor"] * picked / picked.sum(-1,
                                                              keepdims=True)
    gates = (jax.nn.one_hot(chosen, e) * weights[..., None]).sum(-2)
    return gates, chosen


def moe_layer(m, p, x, held=None, shared: bool = True):
    """x [..., D] float32; ``held`` = (first, count) says which experts
    ``p`` holds (default: ``m``'s); the others' part is left out. -> the
    held experts' weighted sum plus (``shared``) the shared expert."""
    first, count = held or m.get("held_experts") or (0, m["n_experts"])
    f32 = jnp.float32
    gates, _ = router(m, jax.nn.sigmoid(x @ p["router"].astype(f32)),
                      p["router_bias"].astype(f32))
    held_gates = jnp.moveaxis(gates[..., first:first + count], -1, 0)

    def one(out, e):
        w_gate, w_up, w_down, gate = e
        y = _swiglu(m, x, w_gate.astype(f32), w_up.astype(f32),
                    w_down.astype(f32))
        return out + gate[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_gate"], p["w_up"], p["w_down"], held_gates))
    if shared:
        out = out + _swiglu(m, x, p["shared_gate"].astype(f32),
                            p["shared_up"].astype(f32),
                            p["shared_down"].astype(f32))
    return out


# ---------------------------------------------------------------- model


def _static(m: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


_CONTROLS = ("iters", "keep_res")


@functools.partial(jax.jit, static_argnames=("m", *_CONTROLS))
def _kda_block(xs, norm, p, hc, state, m, iters=None, keep_res=True):
    m = dict(m)
    p, hc = _f32(p), _f32(hc)
    with jax.default_matmul_precision("highest"):
        return hc_sublayer(m, hc, xs, lambda u: kda_rows(
            m, p, _rms_norm(u, norm.astype(jnp.float32), m["rms_eps"]),
            *state), iters, keep_res)


@functools.partial(jax.jit, static_argnames="m")
def _latents(xs, norm, p, hc, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        pre = hc_coefficients(m, _f32(hc), xs)[0]
        u = jnp.einsum("btn,btnd->btd", pre, xs)
        return latents(m, _f32(p), _rms_norm(
            u, norm.astype(jnp.float32), m["rms_eps"]))


@functools.partial(jax.jit, static_argnames=("m", "tail", "bf16_index",
                                             *_CONTROLS))
def _attend(xs, norm, p, hc, c, keys, q_first, m, tail=True,
            bf16_index=False, iters=None, keep_res=True):
    """The streams' rows ``q_first`` .. over every key's latent: their
    chosen blocks, the attention and ``W_o`` as the sublayer's output.
    -> (the rows' streams, their chosen blocks as indices)."""
    m = dict(m)
    p, hc = _f32(p), _f32(hc)
    pool = m["index_pool"]

    def fn(u):
        b, t, _ = u.shape
        x = _rms_norm(u, norm.astype(jnp.float32), m["rms_eps"])
        q, q_i, w = queries(m, p, x)
        if bf16_index:  # (a control: the index computed a precision lower)
            scores = index_scores(
                q_i.astype(jnp.bfloat16).astype(jnp.float32), w,
                keys.astype(jnp.bfloat16).astype(jnp.float32)
            ).astype(jnp.bfloat16).astype(jnp.float32)
        else:
            scores = index_scores(q_i, w, keys)
        sets = chosen_blocks(scores, q_first, pool, m["index_topk"] // pool)
        o = attend(m, p, q, c, seen_rows(sets, q_first, pool, c.shape[1],
                                         tail))
        return o.reshape(b, t, -1) @ p["wo"], sets

    with jax.default_matmul_precision("highest"):
        return hc_sublayer(m, hc, xs, fn, iters, keep_res)


@functools.partial(jax.jit, static_argnames=("sparse", "m", *_CONTROLS))
def _mlp_block(xs, norm, p, hc, sparse: bool, m, iters=None, keep_res=True):
    m = dict(m)
    hc = _f32(hc)

    def fn(u):
        x = _rms_norm(u, norm.astype(jnp.float32), m["rms_eps"])
        if sparse:
            return moe_layer(m, p, x), None
        q = _f32(p)
        return _swiglu(m, x, q["w_gate"], q["w_up"], q["w_down"]), None

    with jax.default_matmul_precision("highest"):
        return hc_sublayer(m, hc, xs, fn, iters, keep_res)[0]


@functools.partial(jax.jit, static_argnames="eps")
def _head(xs, norm, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(xs.sum(2), norm.astype(jnp.float32), eps) \
            @ w.astype(jnp.float32)


def hidden(params, tokens, m: dict, sets: list | None = None,
           controls: dict | None = None):
    """tokens [B, T] -> the streams [B, T, n, D] before the final norm as
    a list of row blocks, a sublayer at a time. With ``sets`` every
    sparse layer's rows' chosen blocks, indices [B, T, k], are appended
    to it. ``controls``: a test's departures, each of which must show
    (``tail`` False: the open block not read; ``bf16_index``; ``iters``:
    a shorter Sinkhorn loop; ``keep_res`` False: ``H_res`` left out of
    the state's update)."""
    ms = _static(m)
    ctl = dict(controls or {})
    mix = {k: ctl[k] for k in _CONTROLS if k in ctl}
    sel = {k: ctl[k] for k in ("tail", "bf16_index") if k in ctl}
    t = tokens.shape[1]
    keep = np.asarray if t > 2 * ROWS else (lambda a: a)
    emb = params["embed"]
    blocks = [keep(jnp.repeat(emb[tokens[:, j:j + ROWS]].astype(
        jnp.float32)[:, :, None], m["hc_mult"], axis=2))
        for j in range(0, t, ROWS)]
    pool = m["index_pool"]
    for i, p in enumerate(params["layers"]):
        a, hc = p["attn"], p["hc_attn"]
        if m["layer_types"][i]:
            made = [_latents(jnp.asarray(x), p["attn_norm"], a, hc, ms)
                    for x in blocks]
            c = jnp.concatenate([x[0] for x in made], axis=1)
            keys = pooled(jnp.concatenate([x[1] for x in made], axis=1), pool)
            chose, out = [], []
            for n, x in enumerate(blocks):
                x = jnp.asarray(x)
                parts = []
                for j in range(0, x.shape[1], QUERY_ROWS):
                    rows, s = _attend(
                        x[:, j:j + QUERY_ROWS], p["attn_norm"], a, hc, c,
                        keys, n * ROWS + j, ms, **sel, **mix)
                    parts.append(rows)
                    chose.append(s)
                out.append(keep(jnp.concatenate(parts, axis=1)))
            blocks = out
            if sets is not None:
                sets.append(jnp.concatenate(chose, axis=1))
        else:
            state, out = kda_empty(m, tokens.shape[0]), []
            for x in blocks:
                x, state = _kda_block(jnp.asarray(x), p["attn_norm"], a, hc,
                                      state, ms, **mix)
                out.append(keep(x))
            blocks = out
        blocks = [keep(_mlp_block(
            jnp.asarray(x), p["mlp_norm"], p["mlp"], p["hc_mlp"],
            i >= m["first_k_dense"], ms, **mix)) for x in blocks]
    return blocks


def forward(params, tokens, m: dict, last: int | None = None,
            sets: list | None = None, controls: dict | None = None):
    """tokens [B, T] -> float32 logits [B, T, V] (``last``: of the last
    ``last`` positions alone, [B, last, V])."""
    blocks = hidden(params, tokens, m, sets, controls)
    if last is not None:
        rows = jnp.concatenate([jnp.asarray(x) for x in blocks[-(
            -(-last // ROWS) + 1):]], axis=1)[:, -last:]
        blocks = [rows[:, j:j + ROWS] for j in range(0, last, ROWS)]
    return jnp.concatenate([_head(
        jnp.asarray(x), params["final_norm"], params["lm_head"],
        m["rms_eps"]) for x in blocks], axis=1)


def loss(params, inputs, targets, m: dict):
    """Mean next-token cross-entropy, float32."""
    logp = jax.nn.log_softmax(forward(params, inputs, m), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


# Serving: bf16 compute against this f32 reference. Readings on the chip
# (my chip runs, PR 65, the cell's configuration, logits spread by 1.03).
# At the cell's own probe (127-token prompts through the 8,192-row
# prefill call and 24 more tokens, four prompts, 96 positions) the
# program in bf16 is off by 0.0022-0.0024 in the median of all logits (a
# row's largest error 0.013 in the median, 0.12 at most) and its token
# never parts from the reference's argmax; a 32,768-token prompt through
# the engine's segmented prefill and 512 teacher-forced steps through the
# slot (PERF.md section 6, PR 65) reads the same medians and parts at 3
# positions, never over a gap of 0.0069. The same program with its
# matrices cut to 3 mantissa bits (a float8 with an ideal scale, the
# nearest precision below bf16), judged by the reference on the uncut
# weights: 0.028 in the median (a row's largest 0.17-0.18, 0.25 at most);
# at the probe it parts up to gaps of 0.030, 0.0036, 0.056 and at no
# position in the fourth prompt (24 tokens cannot always tell 8 mantissa
# bits from 3: PERF.md section 7(i)), over 32,768 + 512 positions at 46,
# up to a gap of 0.124. So the limit lies between 0.0069 (the largest
# bf16 reading, four times under it) and the control's 0.056 at the probe
# and 0.124 at 32,768 rows (two and four times over it): the served token
# must be the reference's argmax wherever its top two are further apart
# than this; nearer ties are counted, not failed.
SERVE_TOP2_GAP = 0.03
# Training: no cell trains this family (its attention kernels are
# forward only); the limit is K-EXAONE's, whose expert layer and
# initialisation this block shares.
TRAIN_LOSS_TOL = 0.001


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens: the served token must
    be the reference's argmax wherever its top two logits are further
    apart than ``SERVE_TOP2_GAP``; nearer ties are counted, not failed."""
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
