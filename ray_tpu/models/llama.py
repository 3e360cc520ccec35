"""Llama-family decoder-only transformer, TPU-first.

Design choices (vs. the reference, which ships no models — its Llama/GPT-J
workloads live in torch release tests, e.g. reference
release/air_examples/gptj_deepspeed_finetuning/):
  - layers stacked into single [L, ...] arrays + lax.scan: one compiled layer
    body regardless of depth (fast compiles, XLA-friendly).
  - jax.checkpoint on the layer body: rematerialize activations, keep HBM for
    params/optimizer (dots_with_no_batch_dims saveable policy).
  - GQA + RoPE + SwiGLU, RMSNorm pre-norm. bf16 compute, f32 master params.
  - every tensor dim carries a logical axis name; dp/fsdp/sp/tp placement is
    decided by rule tables in ray_tpu.parallel.sharding.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary, rotary_embedding
from ray_tpu.parallel import tp_products as tpp
from ray_tpu.parallel.pipeline import pipeline_apply, pipeline_stages
from ray_tpu.parallel.sharding import shard_constraint


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"  # compute dtype; master params stay f32
    remat: bool = True
    # "dots": save matmul outputs, recompute elementwise+attention (fast
    # bwd, ~0.6 GB/layer at b8x2048/350m). "nothing": full remat — only
    # the layer input survives (fits 2x the batch; bwd re-runs the fwd).
    remat_policy: str = "dots"
    use_flash: bool | None = None  # None = auto (flash on TPU)
    tie_embeddings: bool = False
    # RMS-normalise the whole q and the whole k projection (a learned
    # scale of n_heads * head_dim / n_kv_heads * head_dim each) before
    # the heads are split and rotated, as OLMoE's block does.
    qk_norm: bool = False
    # Mixture-of-experts MLP (0 = dense MLP). Three impls:
    # - "dropless": every (token, expert) assignment is computed at any
    #   load. The assignments are sorted by expert and go through one
    #   grouped matmul (ops/grouped_matmul.py) that skips experts nobody
    #   was routed to. What a server runs; one device's experts only (a
    #   mesh whose ep axis is larger than 1 is refused).
    # - "capacity" (default): GShard-style top-k token routing with a
    #   per-row capacity buffer — dispatch/combine einsums whose expert
    #   dim shards over the ep mesh axis, so GSPMD lowers the dispatch to
    #   an all-to-all over ICI and each device runs ONLY its experts
    #   (per-device expert FLOPs ~ top_k/E of dense).
    # - "dense": every expert computes every token, gates mask the sum —
    #   all-to-all-free, competitive at tiny E, and the parity oracle for
    #   the capacity path. (The reference has no MoE at all, SURVEY §2.7.)
    n_experts: int = 0
    top_k: int = 2
    moe_impl: str = "capacity"  # "capacity" | "dense" | "dropless"
    # renormalise the kept top-k router probabilities to sum to 1
    # (False: OLMoE's norm_topk_prob, the softmax's own values)
    norm_topk_prob: bool = True
    # Expert buffer size multiplier: capacity = ceil(top_k*T/E * factor).
    # Tokens routed past a full expert are dropped (their residual path
    # still carries them) — GShard semantics.
    capacity_factor: float = 1.25
    # GPipe microbatch count when the ambient mesh has a pp axis > 1
    # (parallel/pipeline.py). 0 = auto (4 microbatches per stage, capped at
    # the batch size). Ignored on pp=1 meshes.
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def slot_model(self):
        """This block's half of the serving engine (``models/slots.py``;
        imported here: ``models/llama_slots.py`` imports this module)."""
        from ray_tpu.models.llama_slots import SLOTS
        return SLOTS

    def num_params(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        if self.n_experts > 0:
            mlp = self.n_experts * 3 * d * f + d * self.n_experts
        else:
            mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        if self.qk_norm:
            per_layer += (self.n_heads + self.n_kv_heads) * hd
        head = 0 if self.tie_embeddings else d * v
        return v * d + l * per_layer + d + head

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-size config (runs on CPU in seconds)."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype="float32",
        )
        base.update(kw)
        return LlamaConfig(**base)


def llama2_size(name: str) -> LlamaConfig:
    """Named sizes for benchmarks: '125m', '350m', '1b', '7b'."""
    table = {
        "125m": dict(d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=2048),
        "moe-tiny": dict(d_model=128, n_layers=2, n_heads=4, n_kv_heads=4,
                         d_ff=256, vocab_size=512, max_seq_len=128,
                         n_experts=4, top_k=2),
        # 350m uses head_dim=128 (8 heads), not GPT-style 16x64: the MXU is
        # a 128x128 systolic array, so 128-wide attention contractions hit
        # native tiling and halve the VPU softmax rows. Identical param
        # count; measured +50% train MFU on v5e vs the 16-head layout.
        "350m": dict(d_model=1024, n_layers=24, n_heads=8, n_kv_heads=8, d_ff=2816),
        "1b": dict(d_model=2048, n_layers=22, n_heads=16, n_kv_heads=8, d_ff=5632),
        "7b": dict(d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32, d_ff=11008),
    }
    return LlamaConfig(**table[name])


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: LlamaConfig, key):
    """Initialize f32 master params. Layer params are stacked along axis 0."""
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    hq, hkv, l = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    k = iter(jax.random.split(key, 16))

    def dense(rng, shape, fan_in):
        return (jax.random.normal(rng, shape, jnp.float32) / math.sqrt(fan_in))

    params = {
        "embed": jax.random.normal(next(k), (cfg.vocab_size, d), jnp.float32),
        "layers": {
            "attn_norm": jnp.ones((l, d), jnp.float32),
            "wq": dense(next(k), (l, d, hq * hd), d),
            "wk": dense(next(k), (l, d, hkv * hd), d),
            "wv": dense(next(k), (l, d, hkv * hd), d),
            "wo": dense(next(k), (l, hq * hd, d), hq * hd),
            **({"q_norm": jnp.ones((l, hq * hd), jnp.float32),
                "k_norm": jnp.ones((l, hkv * hd), jnp.float32)}
               if cfg.qk_norm else {}),
            "mlp_norm": jnp.ones((l, d), jnp.float32),
            **(
                {
                    "router": dense(next(k), (l, d, cfg.n_experts), d),
                    "w_gate": dense(next(k), (l, cfg.n_experts, d, f), d),
                    "w_up": dense(next(k), (l, cfg.n_experts, d, f), d),
                    "w_down": dense(next(k), (l, cfg.n_experts, f, d), f),
                }
                if cfg.n_experts > 0 else
                {
                    "w_gate": dense(next(k), (l, d, f), d),
                    "w_up": dense(next(k), (l, d, f), d),
                    "w_down": dense(next(k), (l, f, d), f),
                }
            ),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)
    return params


def param_logical_axes(cfg: LlamaConfig):
    """Same structure as init_params, leaves = logical axis name tuples."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "norm"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            **({"q_norm": ("layers", "heads"),
                "k_norm": ("layers", "kv_heads")}
               if cfg.qk_norm else {}),
            "mlp_norm": ("layers", "norm"),
            **(
                {
                    "router": ("layers", "embed", None),
                    "w_gate": ("layers", "expert", "embed", "mlp"),
                    "w_up": ("layers", "expert", "embed", "mlp"),
                    "w_down": ("layers", "expert", "mlp", "embed"),
                }
                if cfg.n_experts > 0 else
                {
                    "w_gate": ("layers", "embed", "mlp"),
                    "w_up": ("layers", "embed", "mlp"),
                    "w_down": ("layers", "mlp", "embed"),
                }
            ),
        },
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _row_ways(cfg: LlamaConfig, t: int) -> int:
    """Over how many chips of the ambient mesh's tp group a layer keeps
    its ``t`` rows apart (``parallel/tp_products.py``: the residual
    stream sharded by sequence, the four products that meet the axis
    split, their transfers behind the products); 1: the layer as GSPMD
    partitions it (no mesh, tp = 1, a mesh with pipeline stages; a
    mixture of experts, whose expert layer is not written that way; a
    norm over the whole q / k projection, which needs every column)."""
    whole = cfg.n_experts > 0 or cfg.qk_norm or pipeline_stages() > 1
    n = 1 if whole else tpp.ways()
    if t % n:
        raise ValueError(
            f"a sequence of {t} rows cannot be split over tp={n}: the "
            "layer keeps each chip of a tp group its own rows (pad the "
            "sequence to a multiple of tp)")
    return n


def _qkv(cfg: LlamaConfig, p, h, sin, cos):
    """Shared pre-norm QKV projection + rotary for both the training layer
    and the cached-decode layer."""
    b, t, _ = h.shape
    hd = cfg.head_dim
    cdt = cfg.compute_dtype

    def projections(h, norm, wq, wk, wv):
        # (each chip of a tp group: its rows in, every row's products
        # with its columns out, by heads)
        rows = tpp.gather(rms_norm(h, norm, cfg.rms_eps))

        def heads(w, scale=None):
            y = tpp.in_order([x @ w.astype(cdt) for x in rows])
            if scale is not None:  # over the whole projection
                y = rms_norm(y, scale, cfg.rms_eps)
            return y.reshape(b, t, -1, hd)

        qk = (p["q_norm"], p["k_norm"]) if cfg.qk_norm else (None, None)
        return heads(wq, qk[0]), heads(wk, qk[1]), heads(wv)

    # named scopes (``program_parts.VOCABULARY``: qkv / attn / attn_out /
    # mlp / embed / lm_head here, cache, sample and optimizer at their
    # sites) are metadata: the compiled program does not change, and its
    # text keeps them in every instruction's ``op_name``. A TPU trace's
    # operations do NOT carry them: a capture is read through the map
    # ``program_parts.parts_of`` makes of that text
    with jax.named_scope("qkv"):
        q, k, v = tpp.over_tp(
            projections, _row_ways(cfg, t),
            in_specs=(tpp.ROWS, tpp.WHOLE) + (tpp.W_COLUMNS,) * 3,
            out_specs=(tpp.COLUMNS,) * 3,
        )(h, p["attn_norm"], p["wq"], p["wk"], p["wv"])
        return apply_rotary(q, sin, cos), apply_rotary(k, sin, cos), v


def moe_topk(cfg: LlamaConfig, router, x):
    """The router: x [..., D] -> (weights [..., top_k] f32, expert ids
    [..., top_k] int32). Logits in the compute type, softmax over ALL
    experts in float32, ``lax.top_k`` picks exactly ``top_k``; the kept
    probabilities are renormalised only with ``norm_topk_prob``."""
    logits = x @ router.astype(cfg.compute_dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids


def moe_gates(cfg: LlamaConfig, router, x):
    """:func:`moe_topk` as a dense [B, T, E] f32 matrix: exactly top_k
    nonzero entries a row (rows sum to 1 with ``norm_topk_prob``)."""
    weights, ids = moe_topk(cfg, router, x)
    return jnp.sum(jax.nn.one_hot(ids, cfg.n_experts, dtype=weights.dtype)
                   * weights[..., None], axis=-2)


def _moe_mlp_dense(cfg: LlamaConfig, p, x):
    """Top-k dense-dispatch MoE (all experts compute, gates mask).

    Expert weights [E, d, f] are sharded over the ep axis; the weighted
    combine sums over E, which XLA lowers to a psum across ep — expert
    parallelism with zero ragged communication. Burns E/top_k x the MLP
    FLOPs, so it only makes sense at tiny E; it doubles as the exact
    parity oracle for the capacity path (capacity routing with no drops
    computes the identical weighted sum).
    """
    cdt = cfg.compute_dtype
    gates = moe_gates(cfg, p["router"], x).astype(cdt)  # [B, T, E]
    gate = jnp.einsum("btd,edf->btef", x, p["w_gate"].astype(cdt))
    up = jnp.einsum("btd,edf->btef", x, p["w_up"].astype(cdt))
    y = jnp.einsum(
        "btef,efd->bted", jax.nn.silu(gate) * up, p["w_down"].astype(cdt)
    )
    out = jnp.einsum("bted,bte->btd", y, gates)
    return shard_constraint(out, ("batch", "seq", "embed"))


def _moe_mlp_capacity(cfg: LlamaConfig, p, x):
    """GShard-style top-k capacity routing (design-new; no reference
    counterpart — closest public pattern: GShard/Switch dispatch einsums).

    Per batch row, each expert owns a fixed buffer of
    capacity = ceil(top_k * T / E * capacity_factor) token slots. Slot
    positions come from a cumsum over the row; tokens that land past a
    full buffer are dropped (residual still carries them). The dispatch /
    combine one-hots make the whole layer three dense einsums:

        xe [B,E,C,D] = dispatch [B,T,E,C] . x [B,T,D]
        ye [B,E,C,D] = expert_mlp(xe)          (E sharded over ep)
        y  [B,T,D]   = combine  [B,T,E,C] . ye

    Static shapes, no ragged comms: with B on dp and E on ep, GSPMD
    lowers the dispatch/combine contractions to all-to-alls over ICI and
    each device computes only its E/|ep| experts — per-device expert
    FLOPs ~ top_k*capacity_factor/E of dense dispatch.
    """
    import math as _math

    cdt = cfg.compute_dtype
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    capacity = min(t * k, int(_math.ceil(k * t / e * cfg.capacity_factor)))

    gates = moe_gates(cfg, p["router"], x)  # [B, T, E] f32, top-k masked
    topv, topi = jax.lax.top_k(gates, k)  # [B, T, k]

    dispatch = jnp.zeros((b, t, e, capacity), cdt)
    combine = jnp.zeros((b, t, e, capacity), jnp.float32)
    counts = jnp.zeros((b, e), jnp.int32)
    for j in range(k):
        mask_j = jax.nn.one_hot(topi[..., j], e, dtype=jnp.int32)  # [B,T,E]
        # slot index within each expert's buffer: tokens in row order,
        # slot-major across the k choices (GShard ordering)
        pos = jnp.cumsum(mask_j, axis=1) - mask_j + counts[:, None, :]
        counts = counts + jnp.sum(mask_j, axis=1)
        pos_tok = jnp.sum(pos * mask_j, axis=-1)  # [B, T]
        keep = (pos_tok < capacity).astype(cdt)
        oh_c = jax.nn.one_hot(pos_tok, capacity, dtype=cdt) * keep[..., None]
        contrib = mask_j.astype(cdt)[..., None] * oh_c[..., None, :]
        dispatch = dispatch + contrib
        combine = combine + (contrib.astype(jnp.float32)
                             * topv[..., j][..., None, None])

    xe = jnp.einsum("btec,btd->becd", dispatch, x.astype(cdt))
    xe = shard_constraint(xe, ("batch", "expert", None, "embed"))
    gate = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(cdt))
    up = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(cdt))
    ye = jnp.einsum(
        "becf,efd->becd", jax.nn.silu(gate) * up, p["w_down"].astype(cdt)
    )
    y = jnp.einsum("btec,becd->btd", combine.astype(cdt), ye)
    return shard_constraint(y, ("batch", "seq", "embed"))


def reports_routing(cfg: LlamaConfig) -> bool:
    """Whether the MLP leaves its chosen expert ids in a caller's
    ``aux`` (the dropless expert layer does; the serving engine's
    programs then return routing counters beside their tokens)."""
    return cfg.n_experts > 0 and cfg.moe_impl == "dropless"


# the vectors the model paths consume in float32 (rms_norm's weights)
_F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_leaves(leaves, dtype):
    return [a.astype(dtype) for a in leaves]


def serving_params(cfg, params, f32_leaves=_F32_LEAVES):
    """The tree a SERVING process holds: every leaf in the type the
    cached model paths consume it in. The matrices (embedding, head,
    projections, MLP or experts, router), which those paths round to
    ``cfg.compute_dtype`` before each product, are rounded to it here,
    once, in one jitted call; the norm vectors, consumed in float32,
    stay as they are. The products see the operand values they saw from
    the f32 masters, and the ``.astype`` in front of each is then a
    no-op: the same model code runs from either tree (the trainer feeds
    it masters). A tree already in those types comes back itself, no
    copy; with ``dtype="float32"`` that is every f32 tree. Called where
    a replica adopts weights (``decode_engine.adopt_weights``), never
    on the request path. ``cfg`` is any configuration with a
    ``compute_dtype``; ``f32_leaves`` names the leaves ITS model paths
    consume in float32 (another block's own: ``models/ling.py``)."""
    cdt = cfg.compute_dtype
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    todo = [i for i, (path, leaf) in enumerate(flat)
            if getattr(path[-1], "key", None) not in f32_leaves
            and leaf.dtype != cdt]
    if not todo:
        return params
    leaves = [leaf for _, leaf in flat]
    for i, cast in zip(todo, _cast_leaves([leaves[i] for i in todo], cdt)):
        leaves[i] = cast
    return jax.tree_util.tree_unflatten(treedef, leaves)


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def split_layers(cfg: LlamaConfig, layers):
    """How a SERVING program scans the layer stack: -> (xs, attach), the
    scan's input and the function that makes a layer's parameters from
    one slice of it. For every model but a dropless mixture of experts
    that is the stack itself and the identity (the programs of today).
    A dropless model's expert matrices stay OUT of the scanned input: a
    scan slices its input, and a slice of [L, E, K, N] handed to the
    grouped matmul kernel is a copy of every expert of the layer, read
    or not, in every decode step. They ride along whole beside the
    layer's index (in the compute type: as they are from a serving
    tree, :func:`serving_params`; f32 masters are cast here, once a
    program), and the kernel reads ``stack[layer]``'s blocks in
    place."""
    if not reports_routing(cfg):
        return layers, lambda p: p
    cdt = cfg.compute_dtype
    experts = {w: layers[w].astype(cdt) for w in _EXPERT_WEIGHTS}
    xs = {k: v for k, v in layers.items() if k not in experts}
    xs["layer"] = jnp.arange(layers["router"].shape[0], dtype=jnp.int32)
    return xs, lambda p: {**p, **experts}


def _moe_mlp_dropless(cfg: LlamaConfig, p, x, aux: dict | None = None):
    """Dropless top-k routing: every (token, expert) assignment is
    computed, at any load. The N x top_k assignments are sorted by
    expert (stable), the tokens' rows gathered in that order, and the
    experts applied by one grouped matmul each for gate, up and down
    (ops/grouped_matmul.py, which never reads an expert without rows);
    then unsorted, weighted and summed over top_k. Static shapes
    (N x top_k rows always), f32 router, compute type elsewhere. ``p``
    holds one layer's expert matrices [E, ...] or, from a serving
    program's scan, the stack and the layer's index (``split_layers``).
    With ``aux`` the chosen expert ids [B, T, top_k] are left in
    ``aux["expert_ids"]`` (the serving engine's routing counters)."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    if dict(jax.sharding.get_abstract_mesh().shape).get("ep", 1) > 1:
        raise ValueError(
            "moe_impl='dropless' keeps every expert on one device; a mesh "
            "with an ep axis larger than 1 needs moe_impl='capacity'")
    cdt = cfg.compute_dtype
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe_router"):
        weights, ids = moe_topk(cfg, p["router"], xf)  # [N, k]
        if aux is not None:
            aux["expert_ids"] = ids.reshape(b, t, k)
    with jax.named_scope("moe_experts"):
        flat = ids.reshape(-1)  # assignment j belongs to token j // k
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.sum(
            jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
        rows = xf[order // k]  # [N * k, D], expert by expert
        experts = functools.partial(grouped_matmul, group_sizes=group_sizes,
                                    layer=p.get("layer"))
        gate = experts(rows, p["w_gate"].astype(cdt))
        up = experts(rows, p["w_up"].astype(cdt))
        y = experts(jax.nn.silu(gate) * up, p["w_down"].astype(cdt))
        unsort = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = y[unsort].reshape(b * t, k, d).astype(jnp.float32)
        out = jnp.sum(y * weights[..., None], axis=1).astype(cdt)
    return shard_constraint(out.reshape(b, t, d), ("batch", "seq", "embed"))


def _moe_mlp(cfg: LlamaConfig, p, x, aux: dict | None = None):
    """What each ``moe_impl`` promises: "dropless" computes every
    assignment (serving); "capacity" shards experts over ep and drops
    past a buffer; "dense" is the every-expert oracle at tiny sizes."""
    if cfg.moe_impl == "dropless":
        return _moe_mlp_dropless(cfg, p, x, aux)
    # (the two training forms: their router is charged with them)
    with jax.named_scope("moe_experts"):
        if cfg.moe_impl == "dense":
            return _moe_mlp_dense(cfg, p, x)
        if cfg.moe_impl == "capacity":
            return _moe_mlp_capacity(cfg, p, x)
    raise ValueError(
        f"unknown moe_impl {cfg.moe_impl!r}; expected 'dropless', "
        "'capacity' or 'dense'")


def _attn_out_and_mlp(cfg: LlamaConfig, p, h, o, aux: dict | None = None):
    """Shared wo projection + residual + MLP (SwiGLU dense or MoE).
    ``aux``, where a caller passes one, receives what the MLP leaves for
    it (a dropless MoE: ``expert_ids``); a dense model leaves nothing."""
    b, t, _ = h.shape
    cdt = cfg.compute_dtype
    o = o.reshape(b, t, cfg.n_heads * cfg.head_dim)

    def attn_out(h, o, wo):
        with jax.named_scope("attn_out"):
            return h + shard_constraint(
                tpp.scattered(tpp.pieces(o), wo.astype(cdt)),
                ("batch", "seq", "embed"),
            )

    if cfg.n_experts > 0:
        h = attn_out(h, o, p["wo"])
        with jax.named_scope("moe_router"):  # (the router's input)
            x = rms_norm(h, p["mlp_norm"], cfg.rms_eps)
        y = _moe_mlp(cfg, p, x, aux)
        with jax.named_scope("moe_experts"):  # (the residual: their sum's)
            return h + y

    def dense(h, o, wo, norm, w_gate, w_up, w_down):
        # (each chip of a tp group: its rows of h and every row of o's
        # columns in, its rows out)
        from jax.ad_checkpoint import checkpoint_name

        h = attn_out(h, o, wo)
        with jax.named_scope("mlp"):
            x = rms_norm(h, norm, cfg.rms_eps)
            # policy-addressable: "dots_flash_qkv_mlp" saves the two widest
            # activations so the backward skips the gate/up matmul recomputes
            y = tpp.scattered(
                [jax.nn.silu(checkpoint_name(x @ w_gate.astype(cdt),
                                             "mlp_gate"))
                 * checkpoint_name(x @ w_up.astype(cdt), "mlp_up")
                 for x in tpp.gather(x)],
                w_down.astype(cdt))
            return h + shard_constraint(y, ("batch", "seq", "embed"))

    return tpp.over_tp(
        dense, _row_ways(cfg, t),
        in_specs=(tpp.ROWS, tpp.COLUMNS, tpp.W_ROWS, tpp.WHOLE,
                  tpp.W_COLUMNS, tpp.W_COLUMNS, tpp.W_ROWS),
        out_specs=tpp.ROWS,
    )(h, o, p["wo"], p["mlp_norm"], p["w_gate"], p["w_up"], p["w_down"])


def _layer(cfg: LlamaConfig, h, layer_params, sin, cos):
    """One pre-norm transformer block. h: [B, T, D] in compute dtype."""
    from jax.ad_checkpoint import checkpoint_name

    p = layer_params
    q, k, v = _qkv(cfg, p, h, sin, cos)
    q = shard_constraint(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
    # policy-addressable: "dots_flash_qkv" saves these so the flash
    # backward's q/k/v inputs skip the qkv-projection recompute
    q = checkpoint_name(q, "qkv_q")
    k = checkpoint_name(k, "qkv_k")
    v = checkpoint_name(v, "qkv_v")
    with jax.named_scope("attn"):
        o = attention(q, k, v, causal=True, use_flash=cfg.use_flash)
    return _attn_out_and_mlp(cfg, p, h, o)


def forward(params, tokens, cfg: LlamaConfig, *, positions=None):
    """tokens [B, T] int32 -> logits [B, T, V] in cfg.compute_dtype.

    Consumers needing f32 softmax statistics must upcast (the in-tree
    loss does); no f32 copy of [B, T, V] ever materializes here."""
    b, t = tokens.shape
    cdt = cfg.compute_dtype
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    with jax.named_scope("qkv"):
        sin, cos = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)

    # Embedding lookup: gather from a fully-replicated view of the table.
    # With vocab/embed sharded at rest and seq sharded (sp), XLA's
    # gather+jvp fall back to "involuntary full rematerialization" when
    # resharding the gather output; one explicit all-gather of the table
    # (V x D in compute dtype, the fsdp weights-gather pattern) makes the
    # gather local and its scatter-add transpose a clean reduce-scatter.
    with jax.named_scope("embed"):
        w_embed = shard_constraint(
            params["embed"].astype(cdt), (None, None)
        )
        h = w_embed[tokens]
        # (a tp group's chips each take their rows here, once, and the
        # final norm gathers them, once: ``_row_ways``)
        row_ways = _row_ways(cfg, t)
        h = shard_constraint(
            h, ("batch", "seq" if row_ways == 1 else "rows", "embed"))

    layer_fn = lambda h_, p_: (_layer(cfg, h_, p_, sin, cos), None)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "dots_flash":
            # dots + the flash kernel's named (out, lse) residuals: the
            # backward reuses them instead of re-running the forward
            # attention kernel — costs ~B*T*H*(D+1) extra saved floats
            # per layer, so use when HBM headroom allows.
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"
                ),
            )
        elif cfg.remat_policy == "dots_flash_qkv":
            # + the rotary'd q/k/v: the flash backward consumes them
            # directly, so saving them skips the qkv-projection recompute
            # (~3/12 of the per-layer matmul FLOPs) for ~3*B*T*D*H bytes
            # per layer.
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse", "qkv_q", "qkv_k", "qkv_v"
                ),
            )
        elif cfg.remat_policy == "dots_flash_qkv_mlp":
            # + the two widest MLP activations: skips the gate/up matmul
            # recomputes too (~8.5/12 of per-layer matmul FLOPs saved
            # overall) — the max-HBM, min-recompute point short of
            # remat=False.
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse", "qkv_q", "qkv_k", "qkv_v",
                    "mlp_gate", "mlp_up"
                ),
            )
        elif cfg.remat_policy == "flash_qkv":
            # memory-lean point for 1B-class states on one chip: save
            # ONLY the flash residuals + rotary'd q/k/v (attention never
            # re-runs) and recompute every projection/MLP dot in the
            # backward (~40% of fwd FLOPs re-done for ~3x less saved
            # activation bytes than 'dots').
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse", "qkv_q", "qkv_k", "qkv_v"
            )
        elif cfg.remat_policy == "nothing":
            policy = None  # full remat: only layer inputs survive
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}; expected "
                "'dots', 'dots_flash', 'dots_flash_qkv', "
                "'dots_flash_qkv_mlp', 'flash_qkv', or 'nothing'"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    pp = pipeline_stages()
    if pp > 1:
        # Layer stack sharded over pp (rule "layers" -> "pp"): stream
        # microbatches through the stages instead of scanning a stack that
        # GSPMD would have to all-gather every iteration.
        mb = cfg.pipeline_microbatches
        if not mb:  # auto: largest divisor of the batch <= 4 stages' worth
            mb = max(d_ for d_ in range(1, min(b, 4 * pp) + 1) if b % d_ == 0)
        h = pipeline_apply(
            lambda c, p_: layer_fn(c, p_)[0],
            params["layers"],
            h,
            num_microbatches=mb,
        )
    else:
        h, _ = jax.lax.scan(layer_fn, h, params["layers"])

    with jax.named_scope("lm_head"):
        if row_ways > 1:  # (a tp group's rows, gathered)
            h = shard_constraint(h, ("batch", "seq", "embed"))
        h = rms_norm(h, params["final_norm"], cfg.rms_eps)
        w_out = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cdt)
        # logits stay in COMPUTE dtype: materializing an f32 copy of
        # [B, T, V] costs ~2 GB of extra HBM traffic per step at the
        # bench shape; the loss upcasts to f32 inside its fused
        # reductions instead
        logits = h @ w_out
        return shard_constraint(logits, ("batch", "seq", "vocab"))


def loss_fn(params, batch, cfg: LlamaConfig):
    """batch: {'tokens': [B, T+1] or ('inputs','targets')} -> (loss, metrics)."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
        mask = None
    logits = forward(params, inputs, cfg)
    with jax.named_scope("loss"):
        loss, n = softmax_cross_entropy(logits, targets, mask=mask)
    return loss, {"loss": loss, "tokens": n}


# --------------------------------------------------------------------------
# Serving prefill (the decode steps are models/decode_engine.py's)
# --------------------------------------------------------------------------
#
# The reference serves models through torch (no in-tree decode path); this
# is the framework-native equivalent that ray_tpu.serve replicas jit. A
# prefill is sized by the rows it is given: a cold one by its prompts'
# bucket, never by the slot that will hold them.

def _attend_behind(q, k, v, pos):
    """The T rows of ``q`` at positions pos .. pos + T - 1 over S rows of
    which the first pos + T hold something (``k`` / ``v`` [B, S, Hkv, hd]:
    given rows, then the call's own): query i sees rows <= pos + i, the
    rest is masked. The warm path's attention alone: ``pos`` is traced
    there and the kernel's mask is static. -> [B, T, Hq, hd]."""
    from ray_tpu.ops.attention import _repeat_kv

    t, hq, hd = q.shape[1:]
    s = k.shape[1]
    kk = _repeat_kv(k, hq // k.shape[2])
    vv = _repeat_kv(v, hq // k.shape[2])
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, kk, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    q_pos = pos + jnp.arange(t, dtype=jnp.int32)[:, None]  # [T, 1]
    k_pos = jnp.arange(s, dtype=jnp.int32)[None, :]  # [1, S]
    logits = jnp.where((k_pos <= q_pos)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(
        "bhts,bshd->bthd", probs, vv, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def _prefill_layer(cfg: LlamaConfig, h, p, sin, cos, given,
                   aux: dict | None = None):
    """_layer variant that hands its k/v rows out. h: [B, T, D]. Its keys
    are the rows the call was given plus the rows it makes: ``given`` is
    ``None``, and the T rows (whole prompts from position 0) attend among
    themselves exactly as :func:`_layer`'s do (``ops.attention``: the
    flash kernel on a TPU, the reference product elsewhere; nothing
    wider than T x T exists), or ``(k, v, pos)``, rows [B, S, Hkv * hd]
    that hold something up to the scalar ``pos``, behind which this
    layer's are written (:func:`_attend_behind`). Returns (h, k, v), the
    rows [B, T or S, Hkv * hd] as a slot holds them: a position's kv
    heads end to end."""
    b, t, _ = h.shape
    q, k, v = _qkv(cfg, p, h, sin, cos)  # [B, T, H*, hd]
    if given is None:
        with jax.named_scope("attn"):
            o = attention(q, k, v, causal=True, use_flash=cfg.use_flash)
        k, v = k.reshape(b, t, -1), v.reshape(b, t, -1)
    else:
        gk, gv, pos = given
        heads = (b, gk.shape[1], *k.shape[2:])
        with jax.named_scope("cache"):
            k = jax.lax.dynamic_update_slice(
                gk, k.reshape(b, t, -1), (0, pos, 0))
            v = jax.lax.dynamic_update_slice(
                gv, v.reshape(b, t, -1), (0, pos, 0))
        with jax.named_scope("attn"):
            o = _attend_behind(q, k.reshape(heads), v.reshape(heads), pos)
    return _attn_out_and_mlp(cfg, p, h, o, aux), k, v


def prefill(params, tokens, last, cfg: LlamaConfig, given=None,
            aux: dict | None = None):
    """tokens [B, T], RIGHT-padded, from position 0, or with ``given`` =
    (k, v [L, B, S, Hkv * hd], pos) from the scalar ``pos`` behind the
    rows given. Returns (float32 logits [B, V] of row ``last`` [B] of the
    T alone — the final norm and the head see one row a stream —, k, v
    [L, B, T or S, Hkv * hd]: every layer's rows). With ``aux`` and a
    model that reports its routing, every layer's expert ids
    [L, B, T, top_k] are left in ``aux["expert_ids"]``."""
    b, t = tokens.shape
    cdt = cfg.compute_dtype
    pos = 0 if given is None else given[2]
    positions = pos + jnp.arange(t, dtype=jnp.int32)[None, :]
    with jax.named_scope("qkv"):
        sin, cos = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
    with jax.named_scope("embed"):
        h = params["embed"].astype(cdt)[tokens]

    routed = aux is not None and reports_routing(cfg)
    layers, attach = split_layers(cfg, params["layers"])

    def body(h_, xs):
        p_, *rows = xs
        layer_aux = {} if routed else None
        h_, k, v = _prefill_layer(
            cfg, h_, attach(p_), sin, cos, (*rows, pos) if rows else None,
            layer_aux)
        return h_, (k, v, *((layer_aux["expert_ids"],) if routed else ()))

    h, (k, v, *ids) = jax.lax.scan(
        body, h, (layers,) if given is None else (layers, *given[:2]))
    if routed:
        aux["expert_ids"] = ids[0]
    with jax.named_scope("lm_head"):
        h = rms_norm(h[jnp.arange(b), last], params["final_norm"],
                     cfg.rms_eps)
        w_out = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cdt)
        return (h @ w_out).astype(jnp.float32), k, v
