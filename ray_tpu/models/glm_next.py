"""A hybrid decoder whose residual state is SEVERAL streams: delta-rule
linear attention (KDA) three layers in four beside latent attention
without positions (NoPE MLA) over the BLOCKS of rows a learned indexer
with pooled keys chooses, every sublayer read from and written back to
``hc_mult`` residual streams through coefficients made of the streams
themselves (mHC: manifold-constrained hyper-connections, arXiv:
2512.24880); leading dense MLPs and then a sigmoid router over experts
of which this device holds a part beside a shared one, every SwiGLU
clamped (``swiglu_limit``). The language model of GLM-5.3-Flash
(``model_type`` ``glm5_next_text``) as its ``config.json`` gives it; the
tenth block.

``layer_types[i]`` is 1 for a sparse latent-attention layer and 0 for a
KDA layer (published: every fourth, layers 3, 7, ...). ``N`` a learned
RMS norm.

- **The residual path**, round every sublayer ``F`` (attention or MLP,
  each with its own ``hc_phi``, ``hc_b``, ``hc_alpha``): the state ``X``
  is ``[hc_mult, D]`` a row. ``x~ = RMSNorm(vec(X))`` over all of its
  numbers (no scale, eps ``hc_eps``); ``c = x~ phi`` (``2 n + n^2``
  numbers); ``H_pre = sigmoid(a_pre c_pre + b_pre)``, ``H_post = 2
  sigmoid(a_post c_post + b_post)`` (n each), ``H_res`` = ``hc_sinkhorn_
  iters`` rounds on ``exp(a_res mat(c_res) + b_res)`` (n x n), each
  dividing every row by its sum and then every column by its sum (``hc_
  eps`` added to each sum): doubly stochastic. ``u = H_pre X`` (one row
  of D), ``y = F(N(u))``, ``X <- H_res X + H_post^T y``. The embedding
  enters as n copies, the final norm and the head read the streams' sum.
  All of it float32 under the scope ``mhc`` (:func:`hc_coefficients`,
  :func:`hc_read`, :func:`hc_write`); ``X`` itself is kept in the
  compute dtype between sublayers, as ``h`` is elsewhere.
- **KDA**: ``models/solar.py``'s layer (``solar.kda_segment`` /
  ``kda_step``, the kernels of ``ops/kda_*.py``) with Ling's bounded
  decay ``g = kda_lower_bound sigmoid(exp(A_log) (x W_f + dt_bias))`` and
  ``beta = sigmoid(x W_beta)`` (:func:`_kda_inputs`, this block's form).
- **Sparse latent attention**: ``models/dots.py``'s layer (``dots.
  sparse_segment`` / ``sparse_step_layer``) at a ``Kind`` with NO rotated
  part (``dr`` 0: a cache row is the 512-wide latent alone) and an
  indexer whose keys are POOLED (``index_pool`` 4: block ``j`` holds
  positions ``4 j .. 4 j + 3``, its key the mean of theirs; a query reads
  the ``index_topk / 4`` best whole blocks and the open block behind
  them: ``dots.pooled_bias``).
- **MLP**: ``models/moe.py``'s, without its residual.

A slot's state (:data:`SLOTS`), three kinds side by side: for each KDA
layer ``S [slots, H, dk, dv]`` float32 and the convolution's last rows
(Solar-Open2's); for the sparse layers ``lat [L, slots, max_len, 512]``;
``idx [L, slots, ceil(max_len / 4), di]``, the pooled keys of whole
blocks, and ``tail [L, slots, 3, di]``, the raw keys of the open block.

**Prefill** is one call a cold prompt and ONE ``lax.scan`` over segments
of ``moe.SEGMENT_ROWS`` rows whose body runs every layer on the segment
(causality allows it: layer l + 1's segment s needs layer l's segments
<= s), carrying each KDA layer's state and the sparse layers' rows so
far: the streams ``[rows, 4, D]`` exist for a segment alone, never for
the prompt, and the scan hands out their sum.

Types: the other blocks'; ``hc_phi``, ``hc_b``, ``hc_alpha`` float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import dots, moe, solar
from ray_tpu.ops.kda_inputs import kda_inputs as _kda_qkvg
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class GlmNextConfig(moe.HeldExperts):
    vocab_size: int = 154880
    d_model: int = 4096
    n_layers: int = 45
    # 1 = sparse latent attention, 0 = KDA; () = the published pattern
    layer_types: tuple = ()
    first_k_dense: int = 3
    dense_d_ff: int = 12288
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 2048
    shared_d_ff: int = 2048
    n_experts: int = 288
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    swiglu_limit: float | None = 10.0  # every SwiGLU's clamp (moe.gated)
    # the sparse layers' MLA (no rotated part) and their heads; the KDA
    # layers' heads are as many
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 256
    qk_rope_head_dim: int = 0
    v_head_dim: int = 256
    # the indexer, its keys pooled
    index_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_pool: int = 4
    index_norm_eps: float = 1e-6
    # KDA
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_rank: int = 128
    kda_chunk: int = 64
    kda_lower_bound: float = -5.0
    # the residual streams
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: the backend's choice (the kernels on a TPU)
    use_flash: bool | None = None
    # groups of heads a sparse layer's prefill attends one after another
    prefill_head_groups: int = 8
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        n = self.n_layers
        kinds = tuple(self.layer_types) or tuple(
            int(i % 4 == 3) for i in range(n))
        if len(kinds) != n or set(kinds) - {0, 1}:
            raise ValueError(f"{n} layers need {n} entries of 0 / 1 in "
                             f"layer_types, not {kinds}")
        if self.qk_rope_head_dim or self.index_topk % self.index_pool:
            raise ValueError(
                "this block's latent attention has no rotated part "
                "(qk_rope_head_dim 0) and chooses whole blocks (index_topk "
                f"a multiple of index_pool), not {self.qk_rope_head_dim} / "
                f"{self.index_topk} / {self.index_pool}")
        object.__setattr__(self, "layer_types", kinds)

    def attends(self, i: int) -> bool:
        """Whether layer ``i`` is a sparse latent-attention layer."""
        return bool(self.layer_types[i])

    def sparse(self, i: int) -> bool:
        """Whether layer ``i``'s MLP is the expert layer."""
        return i >= self.first_k_dense

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind."""
        return self.layer_types[:i].count(self.layer_types[i])

    @property
    def mla(self) -> dots.Kind:
        return dots.Kind(self.n_heads, self.q_lora_rank, self.kv_lora_rank,
                         self.qk_nope_head_dim, 0, self.v_head_dim, 0.0,
                         False, False)

    @property
    def sparse_layers(self) -> int:
        return sum(self.layer_types)

    @property
    def kda_layers(self) -> int:
        return self.n_layers - self.sparse_layers

    @property
    def moe_layers(self) -> int:
        return self.n_layers - min(self.first_k_dense, self.n_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "GlmNextConfig":
        """Test-size config: the cell's five-layer pattern (a dense KDA
        layer, then a sparse attention layer and three KDA layers with
        experts), a selection that bites (2 blocks of 4 of the
        sequences' dozens of rows); runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=5,
            layer_types=(0, 1, 0, 0, 0), first_k_dense=1, dense_d_ff=96,
            d_ff=32, shared_d_ff=32, n_experts=16, top_k=4, n_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24,
            v_head_dim=24, index_heads=2, index_head_dim=16, index_topk=8,
            index_pool=4, kda_head_dim=16, kda_rank=8, kda_chunk=8,
            prefill_head_groups=2, max_seq_len=256, dtype="float32")
        base.update(kw)
        return GlmNextConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: GlmNextConfig, key):
    """The tree in the SERVING types, leaf by leaf in blocks
    (``moe.draw``): a KDA layer's leaves are ``solar.init_kda``'s, a
    sparse layer's as ``dots.init_layers`` draws them (no rescale), the
    MLPs' ``moe``'s. The streams' leaves so that a static mix, a
    skipped Sinkhorn round or a missing stream shows against the
    reference: ``hc_phi`` normal / sqrt(its rows) (the coefficients'
    inputs spread by 1), ``hc_b`` N(0, 1), ``hc_alpha`` around 1."""
    d, n = cfg.d_model, cfg.hc_mult
    keys = iter(jax.random.split(key, 48 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)
    k = cfg.mla

    def mla():
        di = cfg.index_head_dim
        return {
            "w_qa": mat(d, k.q_lora), "q_norm": around_one(k.q_lora),
            "w_qb": mat(k.q_lora, k.heads * k.dn),
            "w_kva": mat(d, k.kv_lora), "kv_norm": around_one(k.kv_lora),
            "w_kvb": mat(k.kv_lora, k.heads * (k.dn + k.dv)),
            "wo": mat(k.heads * k.dv, d, out=True),
            "w_iq": mat(k.q_lora, cfg.index_heads * di),
            "w_ik": mat(d, di), "ik_norm": around_one(di),
            "ik_bias": 0.1 * jax.random.normal(next(keys), (di,),
                                               jnp.float32),
            "w_iw": mat(d, cfg.index_heads)}

    def streams():
        return {
            "hc_phi": jax.random.normal(
                next(keys), (n * d, 2 * n + n * n), jnp.float32)
            * (n * d) ** -0.5,
            "hc_b": jax.random.normal(next(keys), (2 * n + n * n,),
                                      jnp.float32),
            "hc_alpha": around_one(3)}

    layers = [{
        "attn_norm": around_one(d),
        "attn": mla() if cfg.attends(i) else solar.init_kda(
            cfg, mat, around_one, keys),
        "hc_attn": streams(),
        "mlp_norm": around_one(d),
        "mlp": moe.init_experts(cfg, mat, keys) if cfg.sparse(i)
        else moe.init_dense(cfg, mat),
        "hc_mlp": streams(),
    } for i in range(cfg.n_layers)]
    return moe.init_model(cfg, mat, around_one, keys, layers)


# --------------------------------------------------------------------------
# The residual streams (mHC)
# --------------------------------------------------------------------------

def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive -> doubly stochastic over its two leading
    axes: ``iters`` rounds, each dividing every row by its sum and then
    every column by its sum (``eps`` added to each sum)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def hc_coefficients(cfg: GlmNextConfig, p, x):
    """The streams x [B, T, n, D] -> (H_pre [n, B, T], H_post [n, B, T],
    H_res [n, n, B, T]) float32, the streams' axes LEADING: a row's
    coefficients are 24 numbers, and with the rows along the lanes the
    Sinkhorn rounds are whole registers (rows-major they were 4 lanes of
    128). The product with ``hc_phi`` is float32 at the highest
    precision (the streams' numbers are exact in float32), the norm's
    rsqrt applied to its 24 results instead of its 16,384 inputs."""
    n = cfg.hc_mult
    b, t = x.shape[:2]
    x32 = x.astype(jnp.float32).reshape(b, t, -1)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                      + cfg.hc_eps)
    c = jnp.dot(x32, p["hc_phi"], precision=jax.lax.Precision.HIGHEST) * r
    c = jnp.moveaxis(c, -1, 0)  # [2n + n^2, B, T]
    a, bias = p["hc_alpha"], p["hc_b"][:, None, None]
    pre = jax.nn.sigmoid(a[0] * c[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * c[n:2 * n] + bias[n:2 * n])
    res = jnp.exp(a[2] * c[2 * n:] + bias[2 * n:]).reshape(n, n, b, t)
    return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def hc_read(cfg: GlmNextConfig, p, x):
    """What a sublayer reads of the streams x [B, T, n, D]: (``u = H_pre
    X`` [B, T, D] in the compute dtype, (H_post, H_res) for
    :func:`hc_write`)."""
    with jax.named_scope("mhc"):
        pre, post, res = hc_coefficients(cfg, p, x)
        u = sum(pre[i][..., None] * x[:, :, i].astype(jnp.float32)
                for i in range(cfg.hc_mult))
        return u.astype(x.dtype), (post, res)


def hc_write(cfg: GlmNextConfig, x, y, coefficients):
    """``X <- H_res X + H_post^T y``: the streams x [B, T, n, D] after a
    sublayer whose output is y [B, T, D]."""
    with jax.named_scope("mhc"):
        post, res = coefficients
        n = cfg.hc_mult
        parts = [x[:, :, j].astype(jnp.float32) for j in range(n)]
        y32 = y.astype(jnp.float32)
        return jnp.stack([
            sum(res[i, j][..., None] * parts[j] for j in range(n))
            + post[i][..., None] * y32 for i in range(n)],
            axis=2).astype(x.dtype)


def hc_out(x):
    """What the final norm and the head read: the streams' sum."""
    with jax.named_scope("mhc"):
        return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


# --------------------------------------------------------------------------
# KDA's inputs: this block's decay and beta
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _kda_inputs(cfg: GlmNextConfig, p, x, conv_rows, real_rows=None):
    """``solar._kda_inputs`` with Ling's bounded decay (``g =
    kda_lower_bound sigmoid(exp(A_log) f)``, in (lower bound, 0)) and
    ``beta = sigmoid(x W_beta)`` in (0, 1): no key of the model allows a
    negative eigenvalue. The decay's and the gate's projections of low
    rank, as Solar-Open2's."""
    f32 = jnp.float32
    proj = x @ p["w_qkv"]

    def f():
        return jnp.dot(x @ p["w_f_down"], p["w_f_up"],
                       preferred_element_type=f32) + p["dt_bias"]

    q, k, v, g = _kda_qkvg(proj, conv_rows, p["conv"], f, p["a_log"],
                           lower_bound=cfg.kda_lower_bound,
                           real_rows=real_rows)
    beta = jax.nn.sigmoid(jnp.dot(x, p["w_beta"],
                                  preferred_element_type=f32))
    return q, k, v, g, beta, proj


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def _attn_norm(cfg, p, u):
    with jax.named_scope("qkv"):
        return rms_norm(u, p["attn_norm"], cfg.rms_eps)


def prefill(params, tokens, true_lens, cfg: GlmNextConfig,
            loads: bool = False, live=None):
    """tokens [B, T] from position 0 (right-padded, ``true_lens`` [B]
    real), ONE scan over segments of ``moe.segment_rows`` rows whose body
    runs every layer (module docstring) -> (the streams' sum [B, T, D]
    before the final norm, {"kda": a list of {"s", "conv"} a KDA layer,
    "lat": a list of latent rows [B, T, 512] a sparse layer, "idx": their
    RAW index keys [B, T, di]}, and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count], the expert
    layer's calls and compact calls [2]), else None). ``live`` as
    ``solar.prefill``'s: the dead segments are not run."""
    b, t = tokens.shape
    seg = moe.segment_rows(t, cfg.kda_chunk)
    cdt = cfg.compute_dtype
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    counted = [i for i in range(cfg.n_layers) if loads and cfg.sparse(i)]

    def body(carry, xs):
        kda, lat, idx, count = carry
        start, h_seg = xs
        kda, lat, idx, count = list(kda), list(lat), list(idx), list(count)
        with jax.named_scope("mhc"):  # (the embedding enters as n copies)
            x = jnp.broadcast_to(h_seg[:, :, None], (
                *h_seg.shape[:2], cfg.hc_mult, h_seg.shape[2]))
        for i, p in enumerate(params["layers"]):
            at = cfg.stack_index(i)
            u, mix = hc_read(cfg, p["hc_attn"], x)
            if cfg.attends(i):
                a, lat[at], idx[at], _ = dots.sparse_segment(
                    cfg, cfg.mla, p["attn"], _attn_norm(cfg, p, u), None,
                    start, lat[at], idx[at], None)
            else:
                a, kda[at] = solar.kda_segment(
                    cfg, p["attn"], _attn_norm(cfg, p, u), kda[at], start,
                    true_lens, inputs=_kda_inputs)
            x = hc_write(cfg, x, a, mix)
            u, mix = hc_read(cfg, p["hc_mlp"], x)
            aux = {} if i in counted else None
            y = moe.mlp_layer(cfg, cfg.sparse(i), p, u, aux, residual=False)
            x = hc_write(cfg, x, y, mix)
            if aux is not None:
                n = counted.index(i)
                count[n] = jax.tree_util.tree_map(jnp.add, count[n], (
                    moe.prefill_loads(cfg, aux["expert_ids"][None],
                                      true_lens - start)[0],
                    moe.compact_calls([aux])))
        return (kda, lat, idx, count), hc_out(x)

    empty = ([solar.kda_empty(cfg, b) for _ in range(cfg.kda_layers)],
             [jnp.zeros((b, t, cfg.mla.row_width), cdt)
              for _ in range(cfg.sparse_layers)],
             [jnp.zeros((b, t, cfg.index_head_dim), cdt)
              for _ in range(cfg.sparse_layers)],
             [(jnp.zeros((cfg.held[1],), jnp.int32),
               jnp.zeros((2,), jnp.int32)) for _ in counted])
    (kda, lat, idx, count), h = moe.in_segments(body, empty, h, seg, live)
    return h, {"kda": kda, "lat": lat, "idx": idx}, \
        moe.prefill_counts(count) if count else None


def forward(params, tokens, cfg: GlmNextConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: GlmNextConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` as :meth:`_Slots.init_state` makes it, without ``pos``. A
    KDA layer updates its ``S`` and convolution rows; a sparse layer
    writes its latent row, closes or extends the open block of index
    keys, selects blocks and attends absorbed over the slot's latent
    rows with the unchosen masked (``dots.sparse_step_layer``); an
    inactive slot keeps its state and attends over nothing. -> (float32
    logits [B, V], the state updated, three [L_moe] int32 counters of the
    ACTIVE slots' routing, and three [1] int32: the rows the sparse
    layers selected (the open block's among them), the rows their
    attentions were handed (the same: every sparse layer selects), and
    the index keys scored or kept raw (whole blocks' pooled keys and the
    open block's), each summed over active slots and sparse layers)."""
    pool = cfg.index_pool
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    plan = dots.step_plan(state["lat"].shape[2], pos, active)
    with jax.named_scope("mhc"):
        x = jnp.broadcast_to(h[:, :, None], (*h.shape[:2], cfg.hc_mult,
                                             h.shape[2]))
    kda = list(state["kda"])
    rows = {name: state[name] for name in ("lat", "idx", "tail")}
    counts, selected = [], jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        at = cfg.stack_index(i)
        u, mix = hc_read(cfg, p["hc_attn"], x)
        if cfg.attends(i):
            a, rows, _, chosen = dots.sparse_step_layer(
                cfg, cfg.mla, p["attn"], _attn_norm(cfg, p, u), None, plan,
                rows, at, at, None)
            with jax.named_scope("attn/attn_index"):
                selected = selected + chosen
        else:
            a, kda[at] = solar.kda_step(
                cfg, p["attn"], _attn_norm(cfg, p, u), kda[at], active,
                inputs=_kda_inputs)
        x = hc_write(cfg, x, a, mix)
        u, mix = hc_read(cfg, p["hc_mlp"], x)
        aux = {} if cfg.sparse(i) else None
        y = moe.mlp_layer(cfg, cfg.sparse(i), p, u, aux, residual=False)
        x = hc_write(cfg, x, y, mix)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    with jax.named_scope("attn/attn_index"):
        scored = cfg.sparse_layers * jnp.sum(
            plan.lengths // pool + plan.lengths % pool, dtype=jnp.int32)
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return (moe.logits(cfg, params, hc_out(x))[:, 0], {"kda": kda, **rows},
            *counters, selected[None], selected[None], scored[None])


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(dots.SparseSlots):
    """Three kinds of state in one slot: a recurrent state a KDA layer,
    which cannot be cut or rewound at a position; the sparse layers'
    latent rows; their index keys, pooled a block of ``index_pool`` rows
    with the open block's raw keys beside them."""

    F32_LEAVES = (*dots.SparseSlots.F32_LEAVES, "o_norm", "a_log", "dt_bias",
                  "hc_phi", "hc_b", "hc_alpha")
    step_counters = (*dots.SparseSlots.step_counters, "attended_rows",
                     "index_keys_scored")

    @staticmethod
    def row_kinds(cfg: GlmNextConfig) -> dict:
        # (a recurrent layer keeps no rows; an index key stands for
        # ``index_pool`` positions: a quarter of a key a row)
        return {"recurrent": (cfg.kda_layers, 0),
                "latent": (cfg.sparse_layers, None),
                "index": (cfg.sparse_layers, None)}

    @staticmethod
    def prefill_segments(cfg: GlmNextConfig, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket, cfg.kda_chunk)

    @staticmethod
    def init_state(cfg: GlmNextConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        ls, pool, di = cfg.sparse_layers, cfg.index_pool, cfg.index_head_dim
        return {
            "kda": [solar.kda_empty(cfg, slots)
                    for _ in range(cfg.kda_layers)],
            "lat": jnp.zeros((ls, slots, max_len, cfg.mla.row_width), cdt),
            "idx": jnp.zeros((ls, slots, -(-max_len // pool), di), cdt),
            "tail": jnp.zeros((ls, slots, pool - 1, di), cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def size(a):  # (by shape: the state may be described only)
            return a.size * a.dtype.itemsize

        return {"recurrent": sum(size(a) for st in state["kda"]
                                 for a in st.values()),
                "latent": size(state["lat"]),
                "index": size(state["idx"]) + size(state["tail"])}

    @staticmethod
    def step(cfg: GlmNextConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: GlmNextConfig, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state. Of a prompt's rows a sparse
        layer keeps every latent row, the pooled key of every block of
        ``index_pool`` rows (a block the prompt leaves open holds
        padding's keys: the steps behind close it and write its key
        before a length can expose it) and the raw keys of the open
        block, rows ``pool * (len // pool) ..``. -> (the streams' state,
        [F] prompt lengths, [F] first tokens, [F] their logprobs, the
        held experts' assignments from the real positions [L_moe,
        count], the expert layer's calls and compact calls [2])."""
        _Slots.refuse_prefix(cfg, prefix)
        h, streams, loads = prefill(params, prompts, true_lens, cfg,
                                    loads=cfg.moe_layers > 0,
                                    live=jnp.max(true_lens))
        toks0, logp0 = _Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        pool = cfg.index_pool
        with jax.named_scope("cache"):
            first = true_lens // pool * pool  # the open block's first row
            at = jnp.minimum(first[:, None] + jnp.arange(pool - 1),
                             prompts.shape[1] - 1)  # [F, pool - 1]
            streams = {
                "kda": streams["kda"], "lat": streams["lat"],
                "idx": [dots.pooled_keys(k, pool) for k in streams["idx"]],
                "tail": [jnp.take_along_axis(k, at[..., None], axis=1)
                         for k in streams["idx"]]}
        return streams, true_lens, toks0, logp0, *(loads or ())

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' state into their slots: a KDA layer's
        ``S`` and convolution rows replaced whole (``solar``'s), the
        rows' stacks as ``dots.SparseSlots.scatter`` puts them."""
        rows = dots.SparseSlots.scatter(
            state, slots, {name: new for name, new in streams.items()
                           if name != "kda"}, full_lens)
        return {**rows, "kda": [
            {name: st[name].at[slots].set(new[name].astype(st[name].dtype))
             for name in st}
            for st, new in zip(state["kda"], streams["kda"])]}


SLOTS = _Slots
