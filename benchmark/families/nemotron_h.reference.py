"""The plain reference of the family ``nemotron_h``: the language model
of NVIDIA-Nemotron-3-Nano-30B-A3B as its ``config.json`` and
transformers' ``NemotronH*`` classes give it, in straightforward
``jax.numpy``, float32, highest matmul precision. No cache, no kernel,
no sort, no chunked scan, no flash:

- ``h_0 = E[token]``; block i: ``h <- h + Mix_i(RMSNorm_i(h))`` with
  ``Mix_i`` by ``pattern[i]`` (``M``, ``E`` or ``*``); logits
  ``RMSNorm_f(h_L) W_head`` (untied). No multiplier anywhere.
- **M**, Mamba-2 (``NemotronHMamba2Mixer``) as the recurrence A TOKEN AT
  A TIME: ``[z | xBC | dt] = n W_in``; ``xBC <- silu(conv_K(xBC) + b)``,
  depthwise and causal; ``[x | B | C]``, x as heads of ``ssm_head_dim``,
  B and C as ``ssm_groups`` rows of ``ssm_state`` each; ``dt =
  softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; head j of
  group ``g = j // (heads / groups)``: ``H_t = exp(dt_t A) H_{t-1} +
  dt_t x_t B_{g,t}^T``, ``y_t = H_t C_{g,t} + D x_t``; ``y <-
  RMSNorm(y * silu(z)) * w`` OVER EACH GROUP'S CHANNELS (inner / groups
  of them), the gate before the norm; ``W_out``.
- **``*``**, attention (``NemotronHAttention``): q of ``n_heads`` x
  ``head_dim``, k, v of ``n_kv_heads`` x ``head_dim``, no position
  encoding; query head h = kv * group + r attends on kv head ``kv``;
  scores ``q k^T / sqrt(head_dim)`` WRITTEN OUT, causal softmax; ``W_o``.
- **E**, experts (``NemotronHMOE``), in the published order: ``s =
  sigmoid(n W_r)``; the ``top_k`` largest of ``s + bias`` (one group:
  nothing masked; the lower index on a tie); weights ``s[ids] / sum
  s[ids] * routed_scaling_factor``; expert e ``W_down,e relu(W_up,e
  n)^2``. EVERY held expert is applied to every token and masked by the
  gate: that is the definition. ``held_experts = (first, count)`` leaves
  out the same experts the program leaves out; the shared expert (the
  same form, its own width) is added in full, unweighted.

Departures from the published code, none of which changes a result: the
published ``+ 1e-20`` under the chosen scores' sum vanishes in float32
(the sum of six sigmoids is never under 1e-12); q, k and v are one
product's columns side by side; the convolution reads the K - 1 rows of
``xBC`` before a row where the published cache keeps K columns.

It computes in blocks so that 1,024 + 2,048 positions at the published
widths fit beside a serving engine: a block at a time, each under its
own ``jit`` with that block's leaves cast to float32 inside; whatever is
a function of a row alone in blocks of :data:`ROWS` rows; the recurrence
over blocks of rows in order, ``H`` and the last three ``xBC`` rows
handed from block to block; the scores of :data:`QUERY_ROWS` query rows
at a time against every key; the experts one at a time.

``m`` is the dict of ``families/nemotron_h.py``'s ``fields``. Shares no
code with ``ray_tpu`` nor with the other references; it takes from the
program the parameter tree's layout alone (a list of blocks, each
``norm`` / ``mix``; ``[in, out]`` matrices; an M block's ``w_in``
columns in the published order gate | x | B (group by group) | C (group
by group) | dt, its taps ``conv [K, inner + 2 G N]``; an attention
block's q, k and v side by side in ``w_qkv``; the held experts stacked
in ``w_up`` ``[count, F, D]``, the ONE matrix stored ``[out, in]``, and
``w_down`` ``[count, F, D]``; the shared expert ``shared_up`` /
``shared_down``; ``embed`` and ``lm_head``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 2048  # rows of a block of tokenwise work
QUERY_ROWS = 256  # query rows whose scores exist at once


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# ---------------------------------------------------------------- Mamba-2


def ssm_inputs(m, p, u, before=None):
    """u [B, T, D] -> (z [B, T, inner], x [B, T, H, P], dt [B, T, H], B,
    C [B, T, G, N], the convolution's inputs [B, T, inner + 2 G N]).
    ``before`` [B, K-1, inner + 2 G N]: the convolution's inputs of the
    rows before u's first (zeros at a sequence's start)."""
    b, t, _ = u.shape
    h, hd, n, g, kk = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
                       m["ssm_groups"], m["conv_kernel"])
    inner = h * hd
    proj = u @ p["w_in"]
    z, xbc, dt = (proj[..., :inner],
                  proj[..., inner:2 * inner + 2 * g * n],
                  proj[..., 2 * inner + 2 * g * n:])
    if before is None:
        before = jnp.zeros((b, kk - 1, xbc.shape[-1]), xbc.dtype)
    padded = jnp.concatenate([before, xbc], axis=1)
    y = jax.nn.silu(sum(p["conv"][i] * padded[:, i:i + t]
                        for i in range(kk)) + p["conv_bias"])
    return (z, y[..., :inner].reshape(b, t, h, hd),
            jax.nn.softplus(dt + p["dt_bias"]),
            y[..., inner:inner + g * n].reshape(b, t, g, n),
            y[..., inner + g * n:].reshape(b, t, g, n), xbc)


def ssm_recurrence(x, dt, a, b, c, h0=None):
    """The selective state-space recurrence, a token at a time. x [B, T,
    H, P], dt [B, T, H], a [H] (< 0), b, c [B, T, G, N]: head j reads
    group ``j // (H / G)``. -> (y [B, T, H, P] without the skip, the
    state after the last token [B, H, P, N])."""
    bsz, t, h, hd = x.shape
    of_head = jnp.arange(h) // (h // b.shape[2])  # a head's group

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, of_head][:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t[:, of_head])

    if h0 is None:
        h0 = jnp.zeros((bsz, h, hd, b.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(token, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def gated_group_norm(m, y, z, w):
    """y, z [B, T, inner]: ``RMSNorm(y * silu(z)) * w`` over each of the
    ``ssm_groups`` groups' channels."""
    b, t, inner = y.shape
    g = m["ssm_groups"]
    y = (y * jax.nn.silu(z)).reshape(b, t, g, inner // g)
    return _rms_norm(y, w.reshape(g, inner // g), m["rms_eps"]).reshape(
        b, t, inner)


def _ssm_rows(m, p, u, state, before):
    """One block of rows of an M block from the state and the
    convolution's inputs the rows before it left -> (the mixer's output
    [B, T, D], H, the block's last K-1 convolution inputs)."""
    bsz, t, _ = u.shape
    z, x, dt, b, c, xbc = ssm_inputs(m, p, u, before)
    y, state = ssm_recurrence(x, dt, -jnp.exp(p["a_log"]), b, c, state)
    y = (y + p["d_skip"][:, None] * x).reshape(bsz, t, -1)
    y = gated_group_norm(m, y, z, p["y_norm"])
    tail = jnp.concatenate([before, xbc], axis=1)[:, -(m["conv_kernel"] - 1):]
    return y @ p["w_out"], state, tail


def _ssm_empty(m, bsz: int):
    h, hd, n = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"]
    return (jnp.zeros((bsz, h, hd, n), jnp.float32),
            jnp.zeros((bsz, m["conv_kernel"] - 1,
                       h * hd + 2 * m["ssm_groups"] * n), jnp.float32))


def _ssm(m, p, u):
    """An M block's mixer over whole sequences from an empty state."""
    return _ssm_rows(m, p, u, *_ssm_empty(m, u.shape[0]))[0]


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
    scores written out over ``sqrt(hd)``, the causal mask, softmax.
    -> [B, Tq, Hq, hd]."""
    b, tq, hq, hd = q.shape
    t, hkv = k.shape[1:3]
    qg = q.reshape(b, tq, hkv, hq // hkv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k) / jnp.sqrt(jnp.float32(hd))
    seen = jnp.arange(t)[None, :] <= first + jnp.arange(tq)[:, None]
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, tq, hq, hd)


def _gqa(m, p, x):
    """An attention block's mixer over whole sequences, every query row
    at once (the tests' sizes; :func:`hidden` takes ``QUERY_ROWS`` at a
    time)."""
    b, t, _ = x.shape
    q, k, v = gqa_qkv(m, p, x)
    return attend(q, k, v, 0).reshape(b, t, -1) @ p["wo"]


# ---------------------------------------------------------------- experts


def router(m, logits, bias):
    """The published order: logits [..., E] -> (gates [..., E] with
    ``top_k`` nonzero entries, the chosen ids [..., top_k]): sigmoid
    scores, the ``top_k`` largest of score + bias (one group: none is
    masked; the lower index on a tie), the chosen scores over their sum,
    scaled."""
    e, kk = m["n_experts"], m["top_k"]
    scores = jax.nn.sigmoid(logits)
    chosen = jnp.argsort(-(scores + bias), -1, stable=True)[..., :kk]
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = picked / picked.sum(-1, keepdims=True) \
        * m["routed_scaling_factor"]
    gates = (jax.nn.one_hot(chosen, e) * weights[..., None]).sum(-2)
    return gates, chosen


def relu2_mlp(x, w_up, w_down):
    """An expert: ``W_down relu(W_up x)^2``, no gate, no bias."""
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def moe_layer(m, p, x, held=None, shared: bool = True):
    """x [..., D] float32; ``p`` the block's leaves as stored (the
    experts are cast one at a time). ``held`` = (first, count) says which
    experts ``p`` holds (default: ``m``'s); the others' part is left
    out. -> the held experts' weighted sum plus (with ``shared``) the
    shared expert."""
    first, count = held or m.get("held_experts") or (0, m["n_experts"])
    f32 = jnp.float32
    gates, _ = router(m, x @ p["router"].astype(f32),
                      p["router_bias"].astype(f32))
    held_gates = jnp.moveaxis(gates[..., first:first + count], -1, 0)

    def one(out, e):
        w_up, w_down, gate = e
        y = relu2_mlp(x, w_up.astype(f32).T, w_down.astype(f32))
        return out + gate[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["w_up"], p["w_down"], held_gates))
    if not shared:
        return out
    return out + relu2_mlp(x, p["shared_up"].astype(f32),
                           p["shared_down"].astype(f32))


# ---------------------------------------------------------------- model


def _static(m: dict) -> tuple:
    """``m`` as a hashable static argument (its lists as tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnames="m")
def _ssm_block(h, norm, p, state, before, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        y, state, tail = _ssm_rows(m, _f32(p), x, state, before)
        return h + y, state, tail


@functools.partial(jax.jit, static_argnames="m")
def _gqa_project(h, norm, p, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return gqa_qkv(m, {"w_qkv": p["w_qkv"].astype(jnp.float32)}, x)


@jax.jit
def _gqa_attend(h, wo, q, k, v, first):
    """The stream's rows ``first`` .. (h, q: those rows' own) over every
    key, projected and added."""
    b, t, _ = h.shape
    with jax.default_matmul_precision("highest"):
        o = attend(q, k, v, first).reshape(b, t, -1)
        return h + o @ wo.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames="m")
def _moe_block(h, norm, p, m):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, norm.astype(jnp.float32), m["rms_eps"])
        return h + moe_layer(m, p, x)


@functools.partial(jax.jit, static_argnames="eps")
def _head(h, norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, norm.astype(jnp.float32), eps) \
            @ head.astype(jnp.float32)


def _by_rows(fn, h, rows: int):
    """``fn`` of each block of ``rows`` rows of h [B, T, ...] in order,
    its results end to end."""
    return jnp.concatenate([fn(i, h[:, i:i + rows])
                            for i in range(0, h.shape[1], rows)], axis=1)


def hidden(params, tokens, m: dict, states: list | None = None):
    """tokens [B, T] -> the stream [B, T, D] before the final norm, a
    block at a time. With ``states`` (a list) every M block's state
    after the last token is appended to it."""
    ms = _static(m)
    h = params["embed"][tokens].astype(jnp.float32)
    for kind, p in zip(m["pattern"], params["layers"]):
        if kind == "*":
            q, k, v = (jnp.concatenate(a, axis=1) for a in zip(*(
                _gqa_project(h[:, j:j + ROWS], p["norm"], p["mix"], ms)
                for j in range(0, h.shape[1], ROWS))))
            h = _by_rows(lambda j, rows: _gqa_attend(
                rows, p["mix"]["wo"], q[:, j:j + QUERY_ROWS], k, v, j), h,
                QUERY_ROWS)
        elif kind == "M":
            state, tail = _ssm_empty(m, tokens.shape[0])
            carry = {"h": state, "tail": tail}

            def rows(j, h_rows, p=p, carry=carry):
                out, carry["h"], carry["tail"] = _ssm_block(
                    h_rows, p["norm"], p["mix"], carry["h"], carry["tail"],
                    ms)
                return out

            h = _by_rows(rows, h, ROWS)
            if states is not None:
                states.append(carry["h"])
        else:
            h = _by_rows(lambda j, rows: _moe_block(
                rows, p["norm"], p["mix"], ms), h, ROWS)
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


# Serving: bf16 compute against this f32 reference. Both limits are read
# on the chip AT THE HARNESS'S OWN PROBE (``serve_driver``: 127 tokens
# in, 24 served), the cell's configuration with ``nemotron.init_params``'
# rule for the seeded weights (the mixers' writes x 5, the expert
# blocks' x 0.25), five seeds of weights x 32 prompts = 160 probes a
# side, through the program's served path at the cell's 32 slots
# (``_Slots.prefill`` -> ``scatter`` -> ``step``: ``flash_fwd``, the
# grouped ``ssd_step``, ``decode_attn`` at sixteen query rows a kv head,
# ``moe_gmm`` at the two-matrix experts' widths); seven runs through
# ``python3 -m benchmark.run`` itself lie inside them (my chip runs, PR
# 70; ``PERF.md`` section 6). Logits spread by 1.03, the reference's top
# two 0.18-0.19 apart in the median, greedy streams 29-32 distinct tokens
# in their last 32. A probe's widest parting gap | its mean regret a
# token:
#
#   the program                      0.003-0.020 median by seed, 0.074 at
#                                    most | 0.0001-0.0016 median, 0.0052
#                                    at most (0.063 | 0.0028 at most
#                                    through the harness)
#   its matrices in 3 mantissa bits  0.036 at least, 0.22-0.29 median by
#   (a float8 with an ideal scale,   seed, 0.65 at most | 0.0019 at least,
#   the nearest precision below      0.047-0.056 median, 0.117 at most
#   bf16; judged on the uncut ones)
#
# ``SERVE_MEAN_REGRET`` stands 2.9 times over the program's largest
# reading and 3.1-3.7 times under the lower precision's medians;
# ``SERVE_TOP2_GAP`` holds one wide parting, which a mean over 24 tokens
# would thin out: 2.7 times the program's widest and under the control's
# median at every seed. By one limit or the other the program passes at
# 160 probes of 160 and the lower precision fails at 157 of 160 (155 by
# the regret's, 119 by the gap's). Teacher-forced over 1,000 + 256
# positions (three seeds) the program's logits stand 0.0148 off in the
# median and 0.14 at most (a row's worst 0.089-0.090 in the median), the
# control's 0.106-0.107 and 0.87. With ``moe.makers``' scale alone on
# every writer NO limit stood: the program gave up to 0.0149 and the
# control 0.0145 in the median (``nemotron.init_params`` says why).
SERVE_TOP2_GAP = 0.2
SERVE_MEAN_REGRET = 0.015
# Training: no cell trains this family; the limit is Ling's, whose
# expert layer this block shares.
TRAIN_LOSS_TOL = 0.001


def check_served_tokens(params, prompt, tokens, m: dict) -> dict:
    """The served greedy ``tokens`` after ``prompt`` against the
    reference's full forward over prompt + tokens, by two limits: the
    served token must be the reference's argmax wherever its top two
    logits are further apart than ``SERVE_TOP2_GAP`` (nearer ties are
    counted, not failed), and what the served tokens give up against
    the reference's choices, the reference's largest logit less its
    logit of the served token, must be ``SERVE_MEAN_REGRET`` a token at
    most in the mean."""
    import numpy as np

    seq = jnp.asarray([list(prompt) + list(tokens)], jnp.int32)
    rows = np.asarray(forward(params, seq, m, last=len(tokens) + 1)[0, :-1])
    served = np.asarray(tokens)
    top2 = np.sort(rows, -1)[:, -2:]
    gap = top2[:, 1] - top2[:, 0]
    parted = rows.argmax(-1) != served
    decided = gap > SERVE_TOP2_GAP
    wrong = decided & parted
    regret = float(np.mean(top2[:, 1] - rows[np.arange(len(served)), served]))
    return {"positions": int(len(tokens)), "near_ties": int((~decided).sum()),
            "agree": int((~parted).sum()), "wrong": int(wrong.sum()),
            # (the widest gap the served token parted at: what the first
            # limit is read against, whatever it stands at)
            "parted_up_to": round(float(np.where(parted, gap, 0).max()), 4),
            "tolerance": SERVE_TOP2_GAP,
            "mean_regret": round(regret, 4),
            "regret_tolerance": SERVE_MEAN_REGRET,
            "ok": bool(wrong.sum() == 0 and decided.sum() > 0
                       and regret <= SERVE_MEAN_REGRET)}
