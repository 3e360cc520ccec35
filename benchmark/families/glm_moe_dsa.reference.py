"""The plain reference of the family ``glm_moe_dsa``: the language model
of GLM-5.2 as its ``config.json`` gives it, in straightforward
``jax.numpy``, float32, highest matmul precision. A full forward over the
whole sequence: no cache, no kernel, no absorbed product, no batching,
no bias: a selection is a set of INDICES, made by a stable sort in the
layers that own an indexer and handed, as indices, to the layers behind
them.

``x`` a layer's normed input, ``N`` a learned RMS norm (eps 1e-5):

- **Latent attention, every layer**: ``c_q = N(x W_qa)``, ``q = c_q
  W_qb`` as ``n_heads`` heads of ``[q_n | q_r]``; ``[c | k_r] = x
  W_kva``, ``c <- N(c)``; ``q_r`` and ``k_r`` rotated in INTERLEAVED
  pairs (2i, 2i + 1) at the row's position (``rope_theta``, no stretched
  frequencies), ``k_r`` one for all heads; UNABSORBED a head: ``[k_n |
  v]_h = c W_kvb,h``, scores ``(q_n . k_n + q_r . k_r) / sqrt(dn + dr)``
  over the keys of the row's set ``S(t)``, softmax, ``o_h = a v_h``;
  ``W_o``. No gate, no rescale of the latents.
- **``S(t)`` in a layer with ``indexer_layers[i] == 1``** (published
  ``indexer_types[i] == "full"``): ``q_I = c_q W_Iq`` as ``index_heads``
  heads of ``index_head_dim``, ``k_I = LayerNorm(x W_Ik)`` (eps 1e-6, a
  scale and a bias), the leading ``qk_rope_head_dim`` numbers of each
  rotated as above; ``w = x W_Iw / sqrt(index_heads) /
  sqrt(index_head_dim)``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] .
  k_I[s])``; the row's positions ``s <= t`` sorted by ``-I`` with a
  STABLE full ``argsort`` (a tie: the earlier position first), the first
  ``min(index_topk, t + 1)`` of them. **In a layer of 0** (``"shared"``):
  the ``S(t)`` of the nearest earlier layer of 1, whatever this layer's
  own input is; the layer has no ``W_Iq``, ``W_Ik``, ``W_Iw``.
- **MLP**: layer ``i < first_k_dense`` a dense SwiGLU; else DeepSeek-V3's
  routing with one group: ``s = sigmoid(x W_r)``, ``s + b`` for the
  selection only, the ``top_k`` largest chosen (the lower index on a
  tie), weights ``routed_scaling_factor * s_e / sum_chosen s``; EVERY
  held expert applied to every token and masked by the gate;
  ``held_experts = (first, count)`` leaves out the experts the program
  leaves out; the shared expert added unweighted.
- Pre-norm; a final RMS norm before the untied head.

Departures from the published description (the configuration file's
``assumed`` and ``left_out``): what a ``"shared"`` layer reads is
``described_as``'s IndexShare, not spelled out by ``config.json``
(``assumed.index_share``); the prediction layer and its shared index
(``num_nextn_predict_layers``, ``index_share_for_mtp_iteration``) are
not run (``left_out.mtp``); the indexer's FP8 keys and Hadamard rotation
are serving types that change no product at bf16 (``left_out.
index_fp8``); one chip's experts and an eighth of the vocabulary
(``reduced``); no exchange between chips (``left_out.exchange``).

It computes in blocks so that 33,280 positions at the published widths
fit beside a serving engine: a layer at a time under its own ``jit``
with that layer's leaves cast to float32 inside; what is a function of a
row alone in blocks of :data:`ROWS` rows; index scores, the sort and
attention for :data:`QUERY_ROWS` query rows at a time, the index heads
and the attention heads one after another (a head's k and v of every
row exist for that head alone); the experts one at a time. A selection
kept for the layers behind it is ``[B, T, index_topk]`` int32.

``m`` is the dict of ``families/glm_moe_dsa.py``'s ``fields``. Shares no
code with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of layers, each
``attn_norm`` / ``attn`` / ``mlp_norm`` / ``mlp``; ``[in, out]``
matrices; in ``attn``: ``w_qa``, ``q_norm``, ``w_qb``, ``w_kva``,
``kv_norm``, ``w_kvb`` (a head's ``[k_n | v]`` side by side), ``wo``
and, in a layer that owns an indexer, ``w_iq``, ``w_ik``, ``ik_norm``,
``ik_bias``, ``w_iw``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 4096  # rows of the stream whose tokenwise work is done at once
QUERY_ROWS = 256  # query rows whose scores exist at once


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def _rope_pairs(x, first, theta: float):
    """x [B, T, H, R] at positions ``first`` .. : the interleaved pairs
    (x[2i], x[2i + 1]) rotated by ``pos * theta^(-2i / R)``."""
    t, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = (first + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _leading_rotated(m, a, first):
    """a [B, T, H, di]: its leading ``qk_rope_head_dim`` numbers rotated."""
    dr = m["qk_rope_head_dim"]
    return jnp.concatenate([_rope_pairs(a[..., :dr], first, m["rope_theta"]),
                            a[..., dr:]], -1)


# ---------------------------------------------------------- attention


def latents(m, p, x, first, indexes: bool):
    """x [B, T, D] (normed) at positions ``first`` .. -> what a row
    leaves for later rows: (the latent c [B, T, r] normed, k_r [B, T,
    dr] rotated, and in a layer that owns an indexer the index key [B,
    T, di], else None)."""
    r = m["kv_lora_rank"]
    kva = x @ p["w_kva"]
    c = _rms_norm(kva[..., :r], p["kv_norm"], m["rms_eps"])
    k_r = _rope_pairs(kva[..., None, r:], first, m["rope_theta"])[..., 0, :]
    k_i = None
    if indexes:
        k_i = _layer_norm(x @ p["w_ik"], p["ik_norm"], p["ik_bias"],
                          m["index_norm_eps"])
        k_i = _leading_rotated(m, k_i[..., None, :], first)[..., 0, :]
    return c, k_r, k_i


def queries(m, p, x, first, indexes: bool):
    """x [B, T, D] (normed) -> (q_n [B, T, H, dn], q_r [B, T, H, dr]
    rotated, and in a layer that owns an indexer (q_I [B, T, Hi, di], w
    [B, T, Hi]), else None)."""
    b, t, _ = x.shape
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    c_q = _rms_norm(x @ p["w_qa"], p["q_norm"], m["rms_eps"])
    q = (c_q @ p["w_qb"]).reshape(b, t, m["n_heads"], dn + dr)
    q_r = _rope_pairs(q[..., dn:], first, m["rope_theta"])
    index = None
    if indexes:
        hi, di = m["index_heads"], m["index_head_dim"]
        q_i = _leading_rotated(m, (c_q @ p["w_iq"]).reshape(b, t, hi, di),
                               first)
        index = (q_i, (x @ p["w_iw"]) / jnp.sqrt(jnp.float32(hi))
                 / jnp.sqrt(jnp.float32(di)))
    return q[..., :dn], q_r, index


def index_scores(q_i, w, k_i):
    """q_i [B, T, Hi, di], w [B, T, Hi], k_i [B, S, di] -> I [B, T, S]:
    the sum written out, a head at a time."""
    def head(acc, xs):
        q_j, w_j = xs  # [B, T, di], [B, T]
        return acc + w_j[..., None] * jax.nn.relu(
            jnp.einsum("btd,bsd->bts", q_j, k_i)), None

    zero = jnp.zeros((*q_i.shape[:2], k_i.shape[1]), jnp.float32)
    return jax.lax.scan(head, zero, (jnp.moveaxis(q_i, 2, 0),
                                     jnp.moveaxis(w, 2, 0)))[0]


def selected(scores, q_first, topk: int):
    """scores [B, T, S] of query rows at positions ``q_first`` .. over
    keys 0 .. S - 1 -> the rows' sets as INDICES [B, T, min(topk, S)]
    int32: the row's positions ``s <= t`` in the order of a stable full
    argsort of ``-I``, the first ``min(topk, t + 1)`` of them; the
    places a short row leaves empty hold ``S``, which is no position."""
    t, s = scores.shape[1:]
    at = q_first + jnp.arange(t)[:, None]
    causal = jnp.arange(s)[None, :] <= at
    order = jnp.argsort(jnp.where(causal[None], -scores, jnp.inf), -1,
                        stable=True)[..., :topk]
    filled = jnp.arange(order.shape[-1])[None, :] < jnp.minimum(topk, at + 1)
    return jnp.where(filled[None], order, s).astype(jnp.int32)


def members(sets, s: int):
    """Sets as indices [B, T, k] (``s``: an empty place) -> [B, T, s]
    bool: whether key ``j`` is in row t's set."""
    b, t, _ = sets.shape
    seen = jnp.zeros((b, t, s + 1), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        sets].set(True)
    return seen[..., :s]


def attend(m, p, q_n, q_r, c, k_r, seen):
    """q_n [B, T, H, dn], q_r [B, T, H, dr] over the keys' latents c [B,
    S, r] and rotated keys k_r [B, S, dr], ``seen`` [B, T, S] bool ->
    [B, T, H, dv]: unabsorbed, a head at a time."""
    dn, dv, r = m["qk_nope_head_dim"], m["v_head_dim"], m["kv_lora_rank"]
    w_kvb = jnp.moveaxis(p["w_kvb"].reshape(r, m["n_heads"], dn + dv), 1, 0)
    scale = 1.0 / jnp.sqrt(jnp.float32(dn + m["qk_rope_head_dim"]))

    def head(_, xs):
        w_h, qn_h, qr_h = xs  # [r, dn + dv], [B, T, dn], [B, T, dr]
        kv = c @ w_h  # [B, S, dn + dv]
        s = (jnp.einsum("btd,bsd->bts", qn_h, kv[..., :dn])
             + jnp.einsum("btd,bsd->bts", qr_h, k_r)) * scale
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return None, jnp.einsum("bts,bsd->btd", a, kv[..., dn:])

    _, o = jax.lax.scan(head, None, (w_kvb, jnp.moveaxis(q_n, 2, 0),
                                     jnp.moveaxis(q_r, 2, 0)))
    return jnp.moveaxis(o, 0, 2)


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


def moe_layer(m, p, x, held=None, shared: bool = True):
    """x [..., D] float32; ``p`` the layer's leaves as stored (the
    experts are cast one at a time). ``held`` = (first, count) says which
    experts ``p`` holds (default: ``m``'s); the others' part is left
    out. -> the held experts' weighted sum plus (``shared``) the shared
    expert."""
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
    if shared and "shared_gate" in p:
        out = out + _swiglu(x, p["shared_gate"].astype(f32),
                            p["shared_up"].astype(f32),
                            p["shared_down"].astype(f32))
    return out


# ---------------------------------------------------------------- model


def _static(m: dict) -> tuple:
    """``m`` as a hashable static argument (its lists as tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnames=("indexes", "m"))
def _latents(h, norm, p, first, indexes: bool, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return latents(m, _f32(p), x, first, indexes)


@functools.partial(jax.jit, static_argnames=("indexes", "m"))
def _attend(h, norm, p, c, k_r, k_i, sets, q_first, indexes: bool, m):
    """The stream's rows ``q_first`` .. over every key: queries, the
    rows' sets (made here from the index keys ``k_i`` where the layer
    ``indexes``, else the ``sets`` handed in), the attention and
    ``W_o``, added. -> (the rows, their sets as indices)."""
    m = dict(m)
    p = _f32(p)
    b, t, _ = h.shape
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        q_n, q_r, index = queries(m, p, x, q_first, indexes)
        if indexes:
            sets = selected(index_scores(*index, k_i), q_first,
                            m["index_topk"])
        o = attend(m, p, q_n, q_r, c, k_r, members(sets, c.shape[1]))
        return h + o.reshape(b, t, -1) @ p["wo"], sets


@functools.partial(jax.jit, static_argnames=("sparse", "m"))
def _mlp_block(h, norm, p, sparse: bool, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        if sparse:
            return h + moe_layer(m, p, x)
        p = _f32(p)
        return h + _swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


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


def hidden(params, tokens, m: dict, sets: list | None = None,
           replace: dict | None = None):
    """tokens [B, T] -> the stream [B, T, D] before the final norm, a
    layer at a time. With ``sets`` every layer's rows' sets as it read
    them, indices [B, T, k], are appended to it (a layer of 0: the
    array its indexer layer made, handed on). ``replace`` {layer: sets}
    puts another selection in an indexer layer's place (a test's: what
    reads it moves, what does not stays)."""
    ms = _static(m)
    h = params["embed"][tokens].astype(jnp.float32)
    handed = None  # the newest selection, as indices
    for i, (p, own) in enumerate(zip(params["layers"], m["indexer_layers"])):
        indexes = bool(own)
        if not indexes and handed is None:
            raise ValueError(f"layer {i} reads a selection that no "
                             "earlier layer made")
        a = p["attn"]
        made = [_latents(h[:, j:j + ROWS], p["attn_norm"], a, j, indexes, ms)
                for j in range(0, h.shape[1], ROWS)]
        c, k_r = (jnp.concatenate([x[n] for x in made], axis=1)
                  for n in range(2))
        k_i = jnp.concatenate([x[2] for x in made], axis=1) if indexes \
            else None
        given = (replace or {}).get(i)
        reads = handed if given is None else given
        chose = []

        def rows(j, h_rows, a=a, c=c, k_r=k_r, k_i=k_i, p=p,
                 indexes=indexes and given is None, reads=reads):
            out, s = _attend(
                h_rows, p["attn_norm"], a, c, k_r, k_i,
                None if indexes else reads[:, j:j + h_rows.shape[1]],
                j, indexes, ms)
            chose.append(s)
            return out

        h = _by_rows(rows, h, QUERY_ROWS)
        if indexes:
            handed = jnp.concatenate(chose, axis=1)
        if sets is not None:
            sets.append(handed)
        h = _by_rows(lambda j, r: _mlp_block(
            r, p["mlp_norm"], p["mlp"], i >= m["first_k_dense"], ms), h,
            ROWS)
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
# (my chip runs, PR 60, the cell's configuration, logits spread by 1.03)
# at the cell's own probe (127-token prompts through the 8,192-row
# prefill call and 24 more tokens, eight prompts, 192 positions): the
# program in bf16 is off by 0.0043 in the median of all logits (a row's
# largest error 0.025 in the median, 0.069 at most) and its token parts
# from the reference's argmax at 2 positions, never over a gap of 0.0033;
# the same program with its matrices cut to 3 mantissa bits (a float8
# with an ideal scale, the nearest precision below bf16), judged by the
# reference on the uncut weights: 0.044 in the median (a row's largest
# 0.27, 0.37 at most), 30 positions part, up to a gap of 0.120. A
# 32,768-token prompt through the engine's segmented prefill and 512
# served tokens (PERF.md section 6, PR 60) parts at 16 positions, never
# over a gap of 0.026. So the limit lies between 0.0033 (the largest
# bf16 reading at the probe; 0.026 at 32,768 rows) and 0.120 (the
# control's), over both bf16 readings with room and a third of the
# control's: the served token must be the reference's argmax wherever
# its top two are further apart than this; nearer ties are counted, not
# failed.
SERVE_TOP2_GAP = 0.035
# Training: no cell trains this family (its attention kernels are
# forward only); the limit is K-EXAONE's, whose expert layer and
# initialisation this block shares.
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
