"""A decoder whose every layer attends through a latent (MLA) with a
gate a head, in two kinds: FULL layers, where a learned indexer chooses
the ``index_topk`` rows a query reads (DeepSeek-V3.2-Exp's sparse
attention), and WINDOW layers with latents, heads and theta of their
own that see the last ``sliding_window`` rows; a dense MLP first, then a
sigmoid router over experts of which this device holds a part beside a
shared one. The language model of dots3-note-prev (``model_type``
``dots3_note``) as its ``config.json`` gives it; the eighth block.

``layer_pattern[i]`` is 1 for a window layer and 0 for a full one
(published: layers 0 and 1 full, then three window layers to one full).
``x`` is a layer's normed input, ``N`` a learned RMS norm:

- **A layer's MLA** (both kinds, each with its own widths): ``c_q = r_q
  N(x W_qa)``, ``q = c_q W_qb`` as heads of ``[q_n | q_r]``; ``[c | k_r]
  = x W_kva``, ``c <- r_kv N(c)``; ``q_r``, ``k_r`` rotated (interleaved
  pairs, the kind's theta), ``k_r`` one for all heads; ``[k_n | v]_h = c
  W_kvb,h``; scores ``(q_n k_n + q_r k_r) (dn + dr)^-1/2``, float32
  softmax; ``o_h <- sigmoid(x W_g)_h o_h`` (one gate a head); ``W_o``.
  ``r_q = (d / q_lora)^1/2`` and ``r_kv = (d / kv_lora)^1/2`` where
  ``lora_rescale``, carried in the norms' float32 scales.
- **The indexer** (full layers): ``q_I = c_q W_Iq`` as ``index_heads``
  heads, ``k_I = LayerNorm(x W_Ik)`` one for all heads, the leading
  ``qk_rope_head_dim`` numbers of each rotated; ``w = x W_Iw *
  index_heads^-1/2 * index_head_dim^-1/2``; ``I[t, s] = sum_j w[t, j]
  relu(q_I[t, j] . k_I[s])`` in float32; query t reads the ``min(index_
  topk, t + 1)`` positions ``s <= t`` of largest ``I`` (a tie to the
  earlier): exactly (``ops/dsa.py: select``), no approximation. Until a
  stream holds more rows than ``index_topk`` the layer is plain causal
  MLA.
- **A window layer**: key ``s`` is seen by query ``t`` iff ``0 <= t - s
  < sliding_window``; no indexer.
- **MLP**: the dense SwiGLU in the first ``first_k_dense`` layers, then
  ``models/moe.py``'s expert layer (sigmoid scores, a selection-only
  bias, one group, ``top_k`` renormalised; the shared expert whole).

A slot's state (:data:`SLOTS`), three stacks of rows: ``lat [L_full,
slots, max_len, 640]``, a full layer's ``[c | k_r | zeros]`` as
Instella-MoE's; ``idx [L_full, slots, max_len, index_head_dim]``, the
indexer's keys, which an indexer reads and nobody attends; ``ring
[L_win, slots, sliding_window, 1152]``, a window layer's ``[c | k_r |
zeros]`` written at ``pos % sliding_window`` (the ring holds exactly
the window: attention over a set of rows does not care for their
order). A decode step attends ABSORBED: a full layer scores the slot's
index keys, selects, and attends over the slot's live latent rows with
the unchosen masked (``ops/dsa.py: decode_attention_masked``, the
kernel ``dsa_decode_attn``; a gather of the chosen rows was measured
first and is not the form: ``ops/dsa.py`` says why); a window layer over
its ring (``decode_attention.attend_latent``).

**Prefill** is one call a cold prompt and every layer one ``lax.scan``
over segments of ``moe.SEGMENT_ROWS`` rows (``moe.in_segments``; dead
segments are not run). A full layer's segment writes its latent rows
and index keys into the layer's rows so far, scores its rows against
every index key (``dsa.index_scores``), selects, and attends UNABSORBED
over every earlier key with the unchosen masked (``dsa.
masked_attention``), ``prefill_head_groups`` groups of heads one after
another, each group's k and v made for that call alone (128 heads' k and
v of 32,768 rows would be 2.7 GB) and of the rows the segment can see
alone (:func:`_live_kv`: the rows behind them are not made). A window layer's
segment makes k and v of the rows its band can reach (the segment and
the one before it) and attends through ``flash_fwd``'s band. Nothing
``[P, P]`` exists; a segment's ``[rows, P]`` float32 scores do.

Types: matrices in ``dtype`` (bf16), products accumulated in float32;
norm vectors, the router's bias, the index scores and their selection,
router scores and softmax statistics float32.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.models import moe
from ray_tpu.models.exaone import ring_rows
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops import dsa
from ray_tpu.ops.attention import attend_rows
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary_interleaved, rotary_embedding

_LANES = 128
_WINDOW_BLOCK = 512  # the band's flash blocks: 513 is no whole block


@dataclasses.dataclass(frozen=True)
class DotsConfig(moe.HeldExperts):
    vocab_size: int = 152064
    d_model: int = 5120
    n_layers: int = 46
    # 1 = a window layer, 0 = a full one; () = the published pattern
    layer_pattern: tuple = ()
    first_k_dense: int = 1
    dense_d_ff: int = 13824
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 1536
    shared_d_ff: int = 1536
    n_experts: int = 256
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    # a full layer's MLA
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # its indexer
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6
    # a window layer's MLA
    window_heads: int = 64
    window_q_lora_rank: int = 1024
    window_kv_lora_rank: int = 1024
    window_qk_nope_head_dim: int = 192
    window_qk_rope_head_dim: int = 64
    window_v_head_dim: int = 128
    window_rope_theta: float = 5e4
    sliding_window: int = 513
    lora_rescale: bool = True
    gated_attention: bool = True  # one sigmoid gate a head
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: the backend's choice (the kernels on a TPU)
    use_flash: bool | None = None
    # groups of heads a full layer's prefill attends one after another
    prefill_head_groups: int = 4
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        n = self.n_layers
        attn = tuple(self.layer_pattern) or tuple(
            int(i > 0 and i % 4 != 1) for i in range(n))
        if len(attn) != n or set(attn) - {0, 1}:
            raise ValueError(f"{n} layers need {n} entries of 0 / 1 in "
                             f"layer_pattern, not {attn}")
        object.__setattr__(self, "layer_pattern", attn)

    def windowed(self, i: int) -> bool:
        return bool(self.layer_pattern[i])

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense

    def kind(self, windowed: bool) -> "Kind":
        """The widths of a layer of that kind."""
        own = (self.window_heads, self.window_q_lora_rank,
               self.window_kv_lora_rank, self.window_qk_nope_head_dim,
               self.window_qk_rope_head_dim, self.window_v_head_dim,
               self.window_rope_theta) if windowed else (
            self.n_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta)
        return Kind(*own, self.lora_rescale, self.gated_attention)

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place in the stack of its kind."""
        return self.layer_pattern[:i].count(self.layer_pattern[i])

    @property
    def window_layers(self) -> int:
        return sum(self.layer_pattern)

    @property
    def full_layers(self) -> int:
        return self.n_layers - self.window_layers

    @property
    def moe_layers(self) -> int:
        return self.n_layers - min(self.first_k_dense, self.n_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "DotsConfig":
        """Test-size config: both kinds of layer and of MLP, a selection
        that bites (16 rows of the sequences' hundred) and a window
        smaller than them; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=64, n_layers=5,
            layer_pattern=(0, 0, 1, 1, 1), dense_d_ff=160, d_ff=32,
            shared_d_ff=32, n_experts=16, top_k=4, n_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4,
            index_heads=4, index_head_dim=16, index_topk=16,
            window_heads=2, window_q_lora_rank=32, window_kv_lora_rank=32,
            window_qk_nope_head_dim=24, window_qk_rope_head_dim=8,
            window_v_head_dim=16, window_rope_theta=1e3, sliding_window=9,
            prefill_head_groups=2, max_seq_len=256, dtype="float32")
        base.update(kw)
        return DotsConfig(**base)


@dataclasses.dataclass(frozen=True)
class Kind:
    """A latent-attention layer's widths and what is done to its latents
    and heads: all the functions below ask of a LAYER beside the
    configuration's ``rms_eps``, types, ``use_flash``, indexer widths and
    ``prefill_head_groups`` (``models/glm_dsa.py``'s run through them)."""
    heads: int
    q_lora: int
    kv_lora: int
    dn: int
    dr: int
    dv: int
    theta: float
    rescale: bool  # r_q, r_kv in the norms' scales (module docstring)
    gated: bool  # one sigmoid gate a head

    @property
    def row_width(self) -> int:
        """What a cache row holds: latent | rotated key, to whole lanes."""
        return -(-(self.kv_lora + self.dr) // _LANES) * _LANES

    def rotation(self, positions):
        """(sin, cos) of ``positions`` [B, T] for the rotated part; None
        for heads that have none (``dr`` 0: no position encoding)."""
        if not self.dr:
            return None
        return rotary_embedding(positions, self.dr, self.theta)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_layers(cfg, key, kinds):
    """The tree in the SERVING types, leaf by leaf in blocks
    (``moe.draw``); ``kinds`` every layer's (:class:`Kind`, whether it
    owns an indexer). K-EXAONE's initialisation and for its reasons
    (``exaone.init_params``): matrices normal / sqrt(fan_in), every
    ``w_down`` scaled by (2 x depth)^-1/2 besides (depth is
    ``published_layers``), the attention's ``wo`` not. The norm scales
    are drawn around 1, the index key's bias and the router's bias away
    from 0 and ``w_iw`` as any matrix (its weights of both signs), so
    that a part left out of a path shows against the reference. The
    matrices that READ a rescaled latent (``w_qb``, ``w_iq``, ``w_kvb``)
    are drawn for an input of that RMS, normal / (r sqrt(fan_in)): what
    they give has the spread every other product's has, as a trained
    model's would (drawn for a unit input, a full layer's attention
    logits spread by 6 and its softmax is one row's)."""
    d = cfg.d_model
    keys = iter(jax.random.split(key, 32 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def attention(k: Kind, indexes: bool):
        def reads(rank: int, width: int):  # (a rescaled latent's reader)
            r2 = d / rank if k.rescale else 1.0
            return moe.draw(next(keys), (rank, width), (rank * r2) ** -0.5,
                            cfg.compute_dtype)

        p = {"w_qa": mat(d, k.q_lora), "q_norm": around_one(k.q_lora),
             "w_qb": reads(k.q_lora, k.heads * (k.dn + k.dr)),
             "w_kva": mat(d, k.kv_lora + k.dr),
             "kv_norm": around_one(k.kv_lora),
             "w_kvb": reads(k.kv_lora, k.heads * (k.dn + k.dv)),
             "wo": mat(k.heads * k.dv, d)}
        if k.gated:
            p["w_gate"] = mat(d, k.heads)
        if indexes:
            di = cfg.index_head_dim
            p.update({
                "w_iq": reads(k.q_lora, cfg.index_heads * di),
                "w_ik": mat(d, di), "ik_norm": around_one(di),
                "ik_bias": 0.1 * jax.random.normal(
                    next(keys), (di,), jnp.float32),
                "w_iw": mat(d, cfg.index_heads)})
        return p

    layers = [{
        "attn_norm": around_one(d), "attn": attention(*kinds[i]),
        "mlp_norm": around_one(d),
        "mlp": moe.init_experts(cfg, mat, keys) if cfg.sparse(i)
        else moe.init_dense(cfg, mat),
    } for i in range(cfg.n_layers)]
    return moe.init_model(cfg, mat, around_one, keys, layers)


def init_params(cfg: DotsConfig, key):
    """:func:`init_layers`: an indexer in every full layer."""
    return init_layers(cfg, key, [(cfg.kind(bool(w)), not w)
                                  for w in cfg.layer_pattern])


# --------------------------------------------------------------------------
# A layer's inputs and output
# --------------------------------------------------------------------------

def segment_positions(x, start):
    """The positions [B, seg] of a segment's rows x [B, seg, D]."""
    return jnp.broadcast_to(
        start + jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])


@jax.named_scope("qkv")
def _mla_inputs(cfg, k: Kind, p, x, rotation):
    """x [B, T, D] (normed) -> (q_nope [B, T, H, dn], q_rope [B, T, H,
    dr] rotated, the latent [B, T, r] normalised and rescaled, k_rope
    [B, T, dr] rotated, the gate [B, T, H] float32 or None, c_q [B, T,
    q_lora]: what the indexer's queries are made of). A kind without a
    rotated part (``dr`` 0): q_rope and k_rope None."""
    b, t, d = x.shape
    r_q = (d / k.q_lora) ** 0.5 if k.rescale else 1.0
    r_kv = (d / k.kv_lora) ** 0.5 if k.rescale else 1.0
    c_q = rms_norm(x @ p["w_qa"], p["q_norm"] * r_q, cfg.rms_eps)
    q = (c_q @ p["w_qb"]).reshape(b, t, k.heads, k.dn + k.dr)
    q_rope = apply_rotary_interleaved(q[..., k.dn:], *rotation) \
        if k.dr else None
    kva = x @ p["w_kva"]
    latent = rms_norm(kva[..., :k.kv_lora], p["kv_norm"] * r_kv,
                      cfg.rms_eps)
    k_rope = apply_rotary_interleaved(
        kva[..., None, k.kv_lora:], *rotation) if k.dr else None
    gate = jax.nn.sigmoid(jnp.dot(
        x, p["w_gate"], preferred_element_type=jnp.float32)) \
        if k.gated else None
    return (q[..., :k.dn], q_rope, latent,
            k_rope[..., 0, :] if k.dr else None, gate, c_q)


@jax.named_scope("qkv")
def _index_inputs(cfg, p, x, c_q, rotation):
    """-> (q_I [B, T, Hi, di], k_I [B, T, di], w [B, T, Hi] float32):
    the leading ``qk_rope_head_dim`` numbers of every index query and of
    the key rotated, the key through a LayerNorm with a bias."""
    b, t, _ = x.shape
    hi, di, dr = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    f32 = jnp.float32

    def rotated(a):  # [B, T, H, di]
        if not dr:  # (no rotated part anywhere in the model)
            return a
        return jnp.concatenate([apply_rotary_interleaved(
            a[..., :dr], *rotation), a[..., dr:]], axis=-1)

    q_i = rotated((c_q @ p["w_iq"]).reshape(b, t, hi, di))
    k = jnp.dot(x, p["w_ik"], preferred_element_type=f32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.index_norm_eps)
    k = (k * p["ik_norm"] + p["ik_bias"]).astype(x.dtype)
    k_i = rotated(k[..., None, :])[..., 0, :]
    w = jnp.dot(x, p["w_iw"], preferred_element_type=f32) \
        * (hi ** -0.5 * di ** -0.5)
    return q_i, k_i, w


def _cache_rows(k: Kind, latent, k_rope):
    """[..., r] and [..., dr] -> the rows a slot keeps [..., row_width]."""
    pad = k.row_width - k.kv_lora - k.dr
    if k_rope is None and not pad:  # (the latent alone, whole lanes)
        return latent
    return jnp.concatenate(
        [latent, *([] if k_rope is None else [k_rope]),
         jnp.zeros((*latent.shape[:-1], pad), latent.dtype)], axis=-1)


@jax.named_scope("attn_out")
def _mla_out(cfg, p, o, gate):
    """o [B, T, H, dv], gate [B, T, H] -> [B, T, D]: the gate a head,
    then ``W_o``."""
    if gate is not None:
        o = (o.astype(jnp.float32) * gate[..., None]).astype(
            cfg.compute_dtype)
    return o.reshape(*o.shape[:2], -1) @ p["wo"]


def _unabsorbed(k: Kind, p, rows):
    """Cache rows [B, S, row_width] -> (k [B, H, S, dn + dr], v [B, H, S,
    dv]) in the flash kernels' layout: every head's k_nope and v out of
    the latent, the one rotated key beside each head's k_nope."""
    b, s, _ = rows.shape
    w_kvb = p["w_kvb"].reshape(k.kv_lora, k.heads, k.dn + k.dv)
    kv = jnp.einsum("bsr,rhd->bhsd", rows[..., :k.kv_lora], w_kvb,
                    preferred_element_type=jnp.float32).astype(rows.dtype)
    k_rope = jnp.broadcast_to(
        rows[:, None, :, k.kv_lora:k.kv_lora + k.dr], (b, k.heads, s, k.dr))
    return jnp.concatenate([kv[..., :k.dn], k_rope], axis=-1), kv[..., k.dn:]


def _kv_buffers(k: Kind, b: int, heads: int, t: int, dtype):
    """The pair :func:`_live_kv` writes a group of ``heads`` heads' k_nope
    [B, heads, T, dn] and v [B, heads, T, dv] into: zeros, made once a
    layer's segment and carried through its groups of heads."""
    return (jnp.zeros((b, heads, t, k.dn), dtype),
            jnp.zeros((b, heads, t, k.dv), dtype))


def _live_kv(k: Kind, lat_all, w_g, start, seg: int, bufs):
    """A group of heads' k_nope and v for the rows a segment at ``start``
    .. can see: the latents of rows ``0 .. start + seg - 1`` of lat_all
    [B, T, row_width], ``seg`` rows at a time (a loop whose trip count is
    the traced ``start // seg + 1``), through the group's w_kvb w_g [r,
    hg, dn + dv] into ``bufs`` (:func:`_kv_buffers`) at their rows ->
    the pair, which is what ``dsa.masked_attention`` takes as k and v.

    The rows behind ``start + seg`` are NOT made, and what the pair holds
    there is stale: the segment's zeros, or the finite k and v an earlier
    group of heads left. That is harmless: ``dsa_attn`` fetches no block
    past ``_last_block``, and inside the last block and in
    ``masked_attention_xla`` every key past ``t + start`` carries
    ``dsa.NEG``, so its weight is exactly 0 in float32 and 0 x finite
    adds nothing.

    The pair is held row-major, the layout ``dsa_attn`` takes (left to
    itself the compiler lays a loop's carry out as the product inside
    likes it, rows minor, and turns all of it before every kernel call),
    and the loop sees it as ``[B, hg, chunks, seg, d]``: a chunk is then
    one index of an axis and lands at a place the compiler knows to be
    whole tiles, so the write is part of the product's fusion (at a
    traced ROW it was an operation of its own for keys 192 wide, slower
    than the product: ``PERF.md`` §6 PR 61)."""
    b, hg, t = bufs[0].shape[:3]

    def products(rows):  # [B, S, r] -> k_nope [B, hg, S, dn], v [.., dv]
        return (jnp.einsum(
            "bsr,rhd->bhsd", rows, w, preferred_element_type=jnp.float32
        ).astype(bufs[0].dtype) for w in (w_g[..., :k.dn], w_g[..., k.dn:]))

    if t == seg:  # one segment: one chunk, no loop
        return tuple(products(lat_all[..., :k.kv_lora]))
    row_major = Layout(major_to_minor=(0, 1, 2, 3, 4))

    def chunk(c, bufs):
        rows = jax.lax.dynamic_slice(lat_all, (0, c * seg, 0),
                                     (b, seg, k.kv_lora))
        return tuple(with_layout_constraint(jax.lax.dynamic_update_slice(
            buf, new[:, :, None], (0, 0, c, 0, 0)), row_major)
            for buf, new in zip(bufs, products(rows)))

    bufs = jax.lax.fori_loop(0, start // seg + 1, chunk, tuple(
        buf.reshape(b, hg, t // seg, seg, -1) for buf in bufs))
    return tuple(buf.reshape(b, hg, t, -1) for buf in bufs)


def _window_attend(cfg: DotsConfig, q, k, v, offset):
    """The band: q [B, H, T, d] at positions ``offset`` .. of the keys'
    own numbering, k / v [B, H, S, .]. On a TPU ``flash_fwd``'s band
    with blocks given (the window is no whole block), else the XLA
    body."""
    use_flash = cfg.use_flash
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash and q.shape[2] % _WINDOW_BLOCK == 0 \
            and k.shape[2] % _WINDOW_BLOCK == 0:
        from ray_tpu.ops.flash_attention import flash_fwd

        return flash_fwd(q, k, v, offset=offset, window=cfg.sliding_window,
                         block_q=_WINDOW_BLOCK, block_k=_WINDOW_BLOCK)
    return attend_rows(q, k, v, offset=offset, window=cfg.sliding_window,
                       use_flash=False)


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def index_pool(cfg) -> int:
    """How many positions one key of the indexer stands for: 1, or a
    configuration's own ``index_pool`` (blocks of that many rows are
    chosen by the mean of their keys)."""
    return getattr(cfg, "index_pool", 1)


def pooled_keys(k_i, pool: int):
    """Index keys [B, T, di] -> [B, ceil(T / pool), di]: block ``j``'s
    key is the mean of the keys of positions ``pool * j ..``, taken in
    float32 and rounded to the keys' type (the sum of four bfloat16
    numbers is exact in float32 whatever its order: a decode step that
    closes a block from the raw keys it kept gives the same bits). A
    last block short of ``pool`` rows is padded with zeros: it is open
    and nobody reads its key."""
    b, t, di = k_i.shape
    k_i = jnp.pad(k_i, ((0, 0), (0, -t % pool), (0, 0)))
    return jnp.mean(k_i.reshape(b, -1, pool, di).astype(jnp.float32),
                    axis=2).astype(k_i.dtype)


def pooled_bias(chosen, at, pool: int, rows: int):
    """The rows a query reads where its indexer chooses BLOCKS: chosen
    [..., S] bool over pooled keys (whole blocks alone) and the query's
    position ``at`` [..., 1] -> (bias [..., rows] bfloat16: 0 on the rows
    of the chosen blocks and on the TAIL, the open block's rows ``pool *
    ((at + 1) // pool) .. at``, which is always read; ``dsa.NEG``
    elsewhere, whether a row is read [..., rows] bool)."""
    s = jnp.arange(rows, dtype=jnp.int32)
    tail = (s >= (at + 1) // pool * pool) & (s <= at)
    reads = jnp.repeat(chosen, pool, axis=-1)[..., :rows] | tail
    return jnp.where(reads, 0.0, dsa.NEG).astype(jnp.bfloat16), reads


def _selection(cfg, p, x, c_q, rotation, start, idx_all):
    """An indexer's choice for a segment's rows x [B, seg, D] at
    positions ``start`` .. -> (the bias [B, seg, T] bfloat16: 0 where
    the row reads the key, ``dsa.NEG`` where not, the layer's index keys
    so far with the segment's written). With ``index_pool`` > 1 the
    scores are taken against the mean key of each block of that many
    rows, the ``index_topk / pool`` best WHOLE blocks (those that end at
    or before the row) are chosen and the open block behind them is read
    besides (:func:`pooled_bias`)."""
    seg, t = x.shape[1], idx_all.shape[1]
    at = start + jnp.arange(seg, dtype=jnp.int32)
    q_i, k_i, w = _index_inputs(cfg, p, x, c_q, rotation)
    with jax.named_scope("cache"):
        idx_all = jax.lax.dynamic_update_slice(idx_all, k_i, (0, start, 0))
    pool = index_pool(cfg)
    if pool > 1:
        with jax.named_scope("attn/attn_index"):
            keys = pooled_keys(idx_all, pool)
            scores = dsa.index_scores(q_i, w, keys, start, pool=pool,
                                      use_kernel=cfg.use_flash)
            whole = pool * jnp.arange(keys.shape[1], dtype=jnp.int32)[
                None, :] + pool - 1 <= at[:, None]
            chosen = dsa.select(scores, whole[None], min(
                cfg.index_topk // pool, keys.shape[1]),
                use_kernel=cfg.use_flash)
            return pooled_bias(chosen, at[:, None], pool, t)[0], idx_all
    with jax.named_scope("attn/attn_index"):
        scores = dsa.index_scores(q_i, w, idx_all, start,
                                  use_kernel=cfg.use_flash)
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] <= at[:, None]
        chosen = dsa.select(scores, valid[None], min(cfg.index_topk, t),
                            use_kernel=cfg.use_flash)
        bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    return bias, idx_all


def sparse_segment(cfg, k: Kind, p, x, rotation, start, lat_all, idx_all,
                   bias):
    """A sparse layer's attention on a segment's normed rows x [B, seg,
    D] at positions ``start`` .., ``rotation`` theirs; ``idx_all`` the
    layer's index keys so far (it owns an indexer: it selects, ``bias``
    is not read) or None (it owns none: it attends over ``bias``, the
    selection an indexer layer before it made for these rows). -> ([B,
    seg, D], the layer's latent rows so far with the segment's written,
    ``idx_all``, the bias the layer attended over)."""
    b, seg, _ = x.shape
    q_nope, q_rope, latent, k_rope, gate, c_q = _mla_inputs(
        cfg, k, p, x, rotation)
    with jax.named_scope("cache"):
        lat_all = jax.lax.dynamic_update_slice(
            lat_all, _cache_rows(k, latent, k_rope), (0, start, 0))
    if idx_all is not None:
        bias, idx_all = _selection(cfg, p, x, c_q, rotation, start, idx_all)
    groups = math.gcd(cfg.prefill_head_groups, k.heads)
    hg = k.heads // groups
    with jax.named_scope("qkv"):  # (groups of heads first, heads outermost)
        def grouped(q):  # [B, seg, H, d] -> [G, B, hg, seg, d]
            if q is None:  # (no rotated part)
                return None
            return q.reshape(b, seg, groups, hg, -1).transpose(2, 0, 3, 1, 4)

        w_kvb = jnp.moveaxis(p["w_kvb"].reshape(
            k.kv_lora, groups, hg, k.dn + k.dv), 1, 0)
        k_r = lat_all[..., k.kv_lora:k.kv_lora + k.dr] if k.dr else None
        bufs = _kv_buffers(k, b, hg, lat_all.shape[1], lat_all.dtype)

    def group(bufs, xs):
        qn_g, qr_g, w_g = xs
        with jax.named_scope("qkv"):  # (k and v out of the live latents)
            k_g, v_g = bufs = _live_kv(k, lat_all, w_g, start, seg, bufs)
        with jax.named_scope("attn/attn_sparse"):
            return bufs, dsa.masked_attention(
                qn_g, qr_g, k_g, k_r, v_g, bias, start,
                scale=(k.dn + k.dr) ** -0.5, use_kernel=cfg.use_flash)

    _, o = jax.lax.scan(group, bufs, (grouped(q_nope), grouped(q_rope),
                                      w_kvb))
    with jax.named_scope("attn_out"):  # [G, B, hg, seg, dv] -> [B, seg, H, dv]
        o = o.transpose(1, 3, 0, 2, 4).reshape(b, seg, k.heads, k.dv)
    return _mla_out(cfg, p, o, gate), lat_all, idx_all, bias


def _window_segment(cfg: DotsConfig, p, x, start, lat_all):
    """A window layer's attention on a segment's normed rows: k and v of
    the rows its band can reach (this segment and what the window needs
    of those before it), attended through the band."""
    k = cfg.kind(True)
    b, seg, _ = x.shape
    t = lat_all.shape[1]
    q_nope, q_rope, latent, k_rope, gate, _ = _mla_inputs(
        cfg, k, p, x, k.rotation(segment_positions(x, start)))
    with jax.named_scope("cache"):
        lat_all = jax.lax.dynamic_update_slice(
            lat_all, _cache_rows(k, latent, k_rope), (0, start, 0))
    back = -(-(cfg.sliding_window - 1) // seg) * seg
    reach = min(t, seg + back)
    with jax.named_scope("qkv"):
        lo = jnp.clip(start - back, 0, t - reach)
        k_b, v_b = _unabsorbed(k, p, jax.lax.dynamic_slice(
            lat_all, (0, lo, 0), (b, reach, lat_all.shape[2])))
        q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    with jax.named_scope("attn/attn_window"):
        o = _window_attend(cfg, q, k_b, v_b, start - lo)
    return _mla_out(cfg, p, o.transpose(0, 2, 1, 3), gate), lat_all


def prefill(params, tokens, true_lens, cfg: DotsConfig, loads: bool = False,
            live=None):
    """tokens [B, T] from position 0 (right-padded, ``true_lens`` [B]
    real), every layer in segments of ``moe.segment_rows`` rows (module
    docstring) -> (h [B, T, D] before the final norm, every layer's rows
    as the cache keeps them: a full layer's (latent rows [B, T, 640],
    index keys [B, T, di]), a window layer's (ring [B, window, 1152] of
    the last real rows,), and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count], the expert
    layer's calls and compact calls [2]), else None). ``live`` as
    ``mimo.prefill``'s: the dead segments are not run."""
    b, t = tokens.shape
    seg = moe.segment_rows(t)
    cdt = cfg.compute_dtype
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    rows, counts = [], []
    for i, p in enumerate(params["layers"]):
        windowed, sparse = cfg.windowed(i), cfg.sparse(i)
        count_loads = loads and sparse

        def layer(carry, xs, p=p, windowed=windowed, sparse=sparse,
                  count_loads=count_loads):
            *kept, count = carry
            start, h_seg = xs
            with jax.named_scope("qkv"):
                x = rms_norm(h_seg, p["attn_norm"], cfg.rms_eps)
            if windowed:
                a, *kept = _window_segment(cfg, p["attn"], x, start, *kept)
            else:  # (every full layer selects: no bias is handed in)
                k = cfg.kind(False)
                a, *kept, _ = sparse_segment(
                    cfg, k, p["attn"], x, k.rotation(segment_positions(
                        x, start)), start, *kept, None)
            with jax.named_scope("attn_out"):
                h_seg = h_seg + a
            aux = {} if count_loads else None
            h_seg = moe.mlp_layer(cfg, sparse, p, h_seg, aux)
            if count_loads:
                count = jax.tree_util.tree_map(jnp.add, count, (
                    moe.prefill_loads(cfg, aux["expert_ids"][None],
                                      true_lens - start)[0],
                    moe.compact_calls([aux])))
            return (*kept, count), h_seg

        widths = (cfg.kind(True).row_width,) if windowed \
            else (cfg.kind(False).row_width, cfg.index_head_dim)
        empty = (*(jnp.zeros((b, t, w), cdt) for w in widths),
                 (jnp.zeros((cfg.held[1],), jnp.int32),
                  jnp.zeros((2,), jnp.int32)) if count_loads else ())
        (*kept, count), h = moe.in_segments(layer, empty, h, seg, live)
        with jax.named_scope("cache"):
            rows.append((ring_rows(kept[0], true_lens,
                                   cfg.sliding_window),)
                        if windowed else tuple(kept))
        if count_loads:
            counts.append(count)
    return h, rows, moe.prefill_counts(counts) if counts else None


def forward(params, tokens, cfg: DotsConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def _step_inputs(cfg, k: Kind, p, x, rotation):
    """A step's normed x [B, 1, D] -> (its queries carried into the
    row's space q_row [B, H, row_width], the value half of ``w_kvb`` [r,
    H, dv], the row the slot keeps [B, row_width], the gate, c_q)."""
    q_nope, q_rope, latent, k_rope, gate, c_q = _mla_inputs(
        cfg, k, p, x, rotation)
    with jax.named_scope("qkv"):  # (q into the row's space)
        w_kvb = p["w_kvb"].reshape(k.kv_lora, k.heads, k.dn + k.dv)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :k.dn],
                           preferred_element_type=jnp.float32)
        q_row = _cache_rows(k, q_lat.astype(x.dtype),
                            None if q_rope is None else q_rope[:, 0])
    return (q_row, w_kvb[..., k.dn:],
            _cache_rows(k, latent[:, 0],
                        None if k_rope is None else k_rope[:, 0]), gate, c_q)


def _step_out(cfg, p, o_lat, w_v, gate):
    """What the heads read in the row's space [B, H, r] -> [B, 1, D]."""
    with jax.named_scope("attn_out"):  # (and back out of it)
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w_v,
                       preferred_element_type=jnp.float32).astype(w_v.dtype)
        return _mla_out(cfg, p, o[:, None], gate)


StepPlan = collections.namedtuple(
    "StepPlan", "slots pos lengths valid block visits")


def step_plan(rows: int, pos, active) -> StepPlan:
    """What a decode step knows before its layers, of pos, active [B]:
    slots [B] 0 .. B - 1; pos; lengths [B] int32, 0 if inactive; valid [B,
    rows]: row < lengths; the masked read's rows a visit; its visits."""
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        valid = jnp.arange(rows, dtype=jnp.int32)[None, :] < lengths[:, None]
        # the masked read's visits, made once a step, before the layers
        # (whole lanes of the bias: 34,832 rows are 34 blocks and 16 rows)
        block = min(_da.LATENT_BLOCK_ROWS, -(-rows // 128) * 128)
        return StepPlan(jnp.arange(pos.shape[0]), pos, lengths, valid, block,
                        _da.visits(lengths, rows, block))


def _pooled_step_selection(cfg, plan: StepPlan, state, index_layer, q_i,
                           k_i, w_i):
    """A decode step's selection over POOLED keys (``index_pool`` > 1).
    ``state["idx"]`` [L, slots, ceil(max_len / pool), di] holds the mean
    key of every whole block, ``state["tail"]`` [L, slots, pool - 1, di]
    the raw keys of the open block. The step's key k_i [B, 1, di] at
    ``pos`` either joins the tail (``pos % pool < pool - 1``) or closes
    its block, whose mean is written (:func:`pooled_keys`' arithmetic).
    The query scores the ``(pos + 1) // pool`` whole blocks, chooses
    ``index_topk / pool`` of them and reads the open block besides. ->
    (state, bias [B, rows] bfloat16, the rows read, int32)."""
    pool, rows = index_pool(cfg), plan.valid.shape[1]
    at, part = plan.pos // pool, plan.pos % pool
    with jax.named_scope("cache"):
        tail = state["tail"][index_layer]  # [B, pool - 1, di]
        block = pooled_keys(jnp.concatenate([tail, k_i], axis=1), pool)
        closes = (part == pool - 1)[:, None]
        keys = state["idx"][index_layer]
        state["idx"] = state["idx"].at[index_layer, plan.slots, at].set(
            jnp.where(closes, block[:, 0], keys[plan.slots, at]))
        place = jnp.minimum(part, pool - 2)
        state["tail"] = state["tail"].at[
            index_layer, plan.slots, place].set(
            jnp.where(closes, tail[plan.slots, place], k_i[:, 0]))
    with jax.named_scope("attn/attn_index"):
        keys = state["idx"][index_layer]
        scores = dsa.index_scores_xla(q_i, w_i, keys)
        whole = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :] \
            < (plan.lengths // pool)[:, None]
        chosen = dsa.select(scores[:, 0], whole, min(
            cfg.index_topk // pool, keys.shape[1]), use_kernel=cfg.use_flash)
        bias, reads = pooled_bias(chosen, (plan.lengths - 1)[:, None], pool,
                                  rows)
        return state, bias, jnp.sum(reads, dtype=jnp.int32)


def sparse_step_layer(cfg, k: Kind, p, x, rotation, plan: StepPlan, state,
                      layer: int, index_layer, bias):
    """A sparse layer's attention in a decode step on x [B, 1, D] normed:
    it writes its latent row at ``state["lat"][layer, slot, pos]``; with
    an indexer (``index_layer``: its place in ``state["idx"]``) it writes
    its index key beside it, scores the slot's ``pos + 1`` index keys and
    selects ``min(index_topk, pos + 1)`` of them, without (None) it reads
    ``bias``, the newest selection before it; it attends ABSORBED over
    the slot's latent rows, the unchosen masked. -> ([B, 1, D], the state
    with the rows written, the bias [B, rows] bfloat16 it attended over (0
    chosen, ``dsa.NEG`` not), the rows it selected, int32, or None)."""
    state, selected = dict(state), None
    q_row, w_v, row, gate, c_q = _step_inputs(cfg, k, p, x, rotation)
    with jax.named_scope("cache"):
        state["lat"] = state["lat"].at[layer, plan.slots, plan.pos].set(row)
    if index_layer is not None:
        q_i, k_i, w_i = _index_inputs(cfg, p, x, c_q, rotation)
    if index_layer is not None and index_pool(cfg) > 1:
        state, bias, selected = _pooled_step_selection(
            cfg, plan, state, index_layer, q_i, k_i, w_i)
    elif index_layer is not None:
        with jax.named_scope("cache"):
            state["idx"] = state["idx"].at[
                index_layer, plan.slots, plan.pos].set(k_i[:, 0])
        with jax.named_scope("attn/attn_index"):
            scores = dsa.index_scores_xla(q_i, w_i, state["idx"][index_layer])
            chosen = dsa.select(scores[:, 0], plan.valid, min(
                cfg.index_topk, plan.valid.shape[1]), use_kernel=cfg.use_flash)
            selected = jnp.sum(chosen, dtype=jnp.int32)
            bias = jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)
    with jax.named_scope("attn/attn_sparse"):
        o_lat = dsa.decode_attention_masked(
            q_row, state["lat"], layer, plan.lengths, bias, dv=k.kv_lora,
            scale=(k.dn + k.dr) ** -0.5, plan=plan.visits, block=plan.block,
            use_kernel=cfg.use_flash)
    return _step_out(cfg, p, o_lat, w_v, gate), state, bias, selected


def step(cfg: DotsConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` the three stacks (:meth:`_Slots.init_state`, without
    ``pos``). A full layer is :func:`sparse_step_layer` with an indexer
    of its own; a window layer writes at ``[layer, slot, pos % window]``
    and attends over the ring's ``min(pos + 1, window)`` rows; an
    inactive slot attends over nothing. -> (float32 logits [B, V], the
    state updated, three [L_moe] int32 counters of the ACTIVE slots'
    routing, and [1] int32: the rows the full layers selected, summed
    over active slots and layers)."""
    w = cfg.sliding_window
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    plan = step_plan(state["lat"].shape[2], pos, active)
    with jax.named_scope("attn"):
        ring_lengths = jnp.minimum(plan.lengths, w)
    with jax.named_scope("qkv"):
        rotations = {windowed: cfg.kind(windowed).rotation(pos[:, None])
                     for windowed in (False, True)}
    state = dict(state)
    counts, selected = [], jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        windowed = cfg.windowed(i)
        k, a, layer = cfg.kind(windowed), p["attn"], cfg.stack_index(i)
        with jax.named_scope("qkv"):
            x = rms_norm(h, p["attn_norm"], cfg.rms_eps)
        if windowed:
            q_row, w_v, row, gate, _ = _step_inputs(
                cfg, k, a, x, rotations[True])
            with jax.named_scope("cache"):
                state["ring"] = state["ring"].at[
                    layer, plan.slots, pos % w].set(row)
            with jax.named_scope("attn/attn_window"):
                o_lat = _da.attend_latent(
                    q_row, state["ring"][layer], ring_lengths, k.kv_lora,
                    (k.dn + k.dr) ** -0.5)
            out = _step_out(cfg, a, o_lat, w_v, gate)
        else:  # (an indexer of its own: no selection is handed in)
            out, state, _, rows = sparse_step_layer(
                cfg, k, a, x, rotations[False], plan, state, layer, layer,
                None)
            with jax.named_scope("attn/attn_index"):
                selected = selected + rows
        with jax.named_scope("attn_out"):
            h = h + out
        aux = {} if cfg.sparse(i) else None
        h = moe.mlp_layer(cfg, cfg.sparse(i), p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    return (moe.logits(cfg, params, h)[:, 0], state, *counters,
            selected[None])


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class SparseSlots(Slots):
    """What the slots of a block of sparse latent layers do whatever
    their stacks (this block's, ``models/glm_dsa.py``'s): a state of named
    stacks ``[layers, slots, rows, width]`` beside ``pos``, ``lat`` of
    ``max_len`` rows; segments; float32 leaves; the selected rows."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "kv_norm",
                  "ik_norm", "ik_bias", "router_bias")
    step_counters = (*Slots.step_counters, "selected_rows")
    STACKS: dict = {}  # a kind of row (``row_kinds``') -> the stack of them

    @staticmethod
    def prefill_segments(cfg, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket)

    @staticmethod
    def max_len(state: dict) -> int:
        return state["lat"].shape[2]

    @classmethod
    def state_bytes(cls, state: dict) -> dict:
        # (by shape: the state may be described only)
        return {kind: state[name].size * state[name].dtype.itemsize
                for kind, name in cls.STACKS.items()}

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' rows into their slots, stack by stack:
        a stream's P rows onto the first P rows of the slot (a ring is
        all of its rows: replaced whole, and a prompt shorter than the
        window leaves zeros). What the slot's last stream wrote behind
        them stays: no reader looks past a slot's own length (the
        selection's ``valid``, the ring's length)."""
        def put(all_, layers):  # [L, slots, S, C] <- L x [F, P <= S, C]
            # a layer and a stream at a time, each an update in place
            # (``mimo._Slots.scatter`` says why)
            for layer, new in enumerate(layers):
                for f in range(new.shape[0]):
                    all_ = jax.lax.dynamic_update_slice(
                        all_, new[None, f:f + 1].astype(all_.dtype),
                        (layer, slots[f], 0, 0))
            return all_

        return {**{name: put(state[name], new)
                   for name, new in streams.items()},
                "pos": state["pos"].at[slots].set(full_lens)}


class _Slots(SparseSlots):
    """Three stacks of rows: the full layers' latent rows, the indexer's
    keys beside them (read by the indexer, attended by nobody) and the
    window layers' rings of latent rows, which cannot be cut or rewound
    at a position."""

    STACKS = {"full": "lat", "index": "idx", "ring": "ring"}

    @staticmethod
    def row_kinds(cfg: DotsConfig) -> dict:
        return {"full": (cfg.full_layers, None),
                "index": (cfg.full_layers, None),
                "ring": (cfg.window_layers, cfg.sliding_window)}

    @staticmethod
    def init_state(cfg: DotsConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        lf, lw = cfg.full_layers, cfg.window_layers
        return {
            "lat": jnp.zeros((lf, slots, max_len,
                              cfg.kind(False).row_width), cdt),
            "idx": jnp.zeros((lf, slots, max_len, cfg.index_head_dim), cdt),
            "ring": jnp.zeros((lw, slots, cfg.sliding_window,
                               cfg.kind(True).row_width), cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def step(cfg: DotsConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: DotsConfig, slot_len: int, prefix=None):
        """Whole prompts from position 0. Of a prompt's rows the full
        layers keep all, latent rows and index keys (the bucket's
        padding among them: a decode step overwrites a pad row at its
        position before a length can expose it), the window layers the
        last ``window`` real ones at their ring offsets. -> (the
        streams' rows by stack, [F] prompt lengths, [F] first tokens,
        [F] their logprobs, the held experts' assignments from the real
        positions [L_moe, count], the expert layer's calls and compact
        calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, rows, loads = prefill(params, prompts, true_lens, cfg,
                                 loads=cfg.moe_layers > 0,
                                 live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        # a list a stack, one entry a layer of its kind
        full = [r for i, r in enumerate(rows) if not cfg.windowed(i)]
        streams = {"lat": [r[0] for r in full], "idx": [r[1] for r in full],
                   "ring": [r[0] for i, r in enumerate(rows)
                            if cfg.windowed(i)]}
        return streams, true_lens, toks0, logp0, *(loads or ())


SLOTS = _Slots
