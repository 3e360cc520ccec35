"""A hybrid decoder without positions: delta-rule linear attention (KDA)
three layers in four beside gated softmax GQA layers, every layer a
mixture of experts of which this device holds a part. The language
model of Solar-Open2-250B (``model_type`` ``solar_open2``) as its
``config.json`` gives it; the fifth block beside ``llama.py``,
``ling.py``, ``exaone.py`` and ``instella.py``.

Layer ``i`` attends with **GQA** if ``i`` is in ``gqa_layers``
(published: 0, 4, 8, ...: ``gqa_interval`` 3 linear layers after each)
and with **KDA** otherwise. Pre-norm, both halves added to the stream.
The layers are NOT a stack scanned by one loop: each is its own dict of
leaves and the programs unroll them.

- **KDA** (Kimi Delta Attention, arXiv:2510.26692; ``fla/layers/kda.py``):
  what ``models/ling.py`` runs, shared with it (the convolution, the
  norms and the decay ``ops/kda_inputs.py``, the chunkwise form
  ``ops/kda_chunk.py``, the output ``ling._kda_out``, the decode step
  ``ops/kda_step.py``), in
  Kimi Linear's own parametrisation: log decay a channel ``g =
  -exp(A_log_h) * softplus(x W_f_down W_f_up + dt_bias)``, unbounded
  below (Ling's is bounded by its ``kda_lower_bound``: no such key
  here), its projection and the output gate's of low rank (the head's
  width: ``kda_use_full_proj`` false), and ``beta = 2 sigmoid(x
  W_beta)`` in (0, 2) (``kda_allow_neg_eigval``: the eigenvalue ``1 -
  beta`` of ``I - beta k k^T`` may be negative). State float32 ``S [H,
  dk, dv]`` a stream and the last ``conv_kernel - 1`` projection rows.
- **GQA**: q of ``n_heads`` x ``head_dim``, k and v of ``n_kv_heads`` x
  ``head_dim``, NO rotary and no q / k norm (``use_rope`` false: no
  position encoding anywhere in the model), causal softmax over q k^T /
  sqrt(head_dim), the output times ``sigmoid(x W_gate)`` elementwise
  before ``W_o`` (``use_gqa_gate``; arXiv:2505.06708). Prefill attends
  through ``ops.attention`` (the flash kernel on a TPU), a decode step
  through ``ops.decode_attention`` over the slot's rows.
- **MoE**, every layer (``models/moe.py``, shared with the three blocks
  above): sigmoid scores in float32 over all ``n_experts``, a bias for
  the selection only, one group, ``top_k`` chosen, weights ``s / sum s``
  scaled; one shared expert. ``held_experts = (first, count)``: the part
  this device computes.

**Prefill runs in row segments.** A prompt of 32,768 rows at 64 heads
does not fit as whole arrays (q, k, v, g of ``[T, 64, 128]`` float32 are
1.07 GB each, the expert layer's gather of ``T x top_k`` rows 2.1 GB).
So everything that is a function of a row and a carried state (norms,
projections, the convolution, the chunkwise delta rule, gates and
``W_o``, router and experts) runs over segments of at most
``moe.SEGMENT_ROWS`` rows under one ``lax.scan`` a layer (``moe.in_segments``),
a KDA layer's ``S`` and last three projection rows carried from segment
to segment; only what needs the whole prompt is whole: the GQA layer's
q, k and v (bf16) and one flash call over them. The bucket decides: a
bucket of at most ``moe.SEGMENT_ROWS`` is one segment
(``moe.segment_rows``). A serving call's scans skip the segments behind
the last one that holds a real row of its longest prompt: they are dead
and are not run. No option chooses either.

A slot's state in the serving engine is this model's own
(:data:`SLOTS`, found through ``SolarConfig.slot_model``), of two kinds
side by side: for each KDA layer ``S [slots, H, dk, dv]`` float32 and
``conv [slots, K-1, 3 H dk]`` (each layer its own array: the step
kernel writes ``S`` into the buffer it came from), for the GQA layers k
and v stacks ``[L_full, slots, max_len, Hkv * hd]`` in the Llama
block's layout, read in place by ``decode_attn`` up to each slot's own
length. A recurrent state cannot be cut at a position, so the prefix
cache, speculative decoding and the prefill workers refuse this model
by name (``rows_state``).

Types: matrices in ``dtype`` (bf16), products accumulated in float32;
norm vectors, ``a_log``, ``dt_bias`` and the router's bias float32;
router scores, softmax statistics and ``S`` float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import ling, moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_bucket, attention
from ray_tpu.ops.kda_chunk import kda_chunk as _kda_chunk
from ray_tpu.ops.kda_inputs import kda_inputs as _kda_qkvg, kept_rows
from ray_tpu.ops.kda_step import kda_step as _kda_step
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class SolarConfig(moe.HeldExperts):
    vocab_size: int = 196608
    d_model: int = 4096
    n_layers: int = 48
    # the GQA layers' query heads AND the KDA layers' heads (published:
    # 64 and 64; the family file refuses a configuration where they part)
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    # the layers that attend with softmax GQA; () = every
    # (gqa_interval + 1)-th from layer 0
    gqa_layers: tuple = ()
    gqa_interval: int = 3
    # KDA
    kda_head_dim: int = 128  # key and value width of a head
    conv_kernel: int = 4
    kda_rank: int = 128  # of the decay's and the gate's projections
    kda_chunk: int = 64
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 1280
    shared_d_ff: int = 1280
    n_experts: int = 320
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: ``ops.attention``'s own choice (flash on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        full = tuple(self.gqa_layers) or tuple(
            range(0, self.n_layers, self.gqa_interval + 1))
        if any(not 0 <= i < self.n_layers for i in full):
            raise ValueError(f"gqa_layers {full} name a layer that "
                             f"{self.n_layers} layers do not have")
        object.__setattr__(self, "gqa_layers", full)

    @property
    def kv_width(self) -> int:
        """What a cache row holds: the position's kv heads end to end."""
        return self.n_kv_heads * self.head_dim

    def full(self, i: int) -> bool:
        return i in self.gqa_layers

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind."""
        return sum(self.full(j) == self.full(i) for j in range(i))

    @property
    def full_layers(self) -> int:
        return len(self.gqa_layers)

    @property
    def kda_layers(self) -> int:
        return self.n_layers - self.full_layers

    @property
    def moe_layers(self) -> int:
        return self.n_layers

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "SolarConfig":
        """Test-size config: one period and a layer of the next, heads x
        head_dim unequal to the hidden size; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=5, n_heads=4, n_kv_heads=2,
            head_dim=16, kda_head_dim=16, kda_rank=8, kda_chunk=8, d_ff=32,
            shared_d_ff=32, n_experts=16, top_k=4, max_seq_len=128,
            dtype="float32")
        base.update(kw)
        return SolarConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: SolarConfig, key):
    """The tree in the SERVING types (module docstring), leaf by leaf in
    blocks (``moe.draw``). Matrices are normal / sqrt(fan_in), and those
    that write into the residual stream (``wo``, every ``w_down``) are
    scaled by (2 x depth)^-1/2 besides (depth is ``published_layers``,
    the model's own where the configuration is cut in depth;
    ``ling.init_params`` says what the scaling is for). The norm scales
    are drawn around 1, the decay parameters and the router's bias away
    from 0, so that a part left out of a path shows against the
    reference."""
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)

    def gqa():
        return {
            "w_qkv": mat(d, (h + 2 * cfg.n_kv_heads) * hd),
            "w_gate": mat(d, h * hd),
            "wo": mat(h * hd, d, out=True),
        }

    layers = [{
        "attn_norm": around_one(d),
        "attn": gqa() if cfg.full(i) else init_kda(cfg, mat, around_one,
                                                   keys),
        "mlp_norm": around_one(d), "mlp": moe.init_experts(cfg, mat, keys),
    } for i in range(cfg.n_layers)]
    return moe.init_model(cfg, mat, around_one, keys, layers)


def init_kda(cfg, mat, around_one, keys) -> dict:
    """A KDA layer's attention leaves (low-rank decay and gate), drawn
    with a block's own makers (``moe.makers``) from its iterator of
    keys: this block's and ``models/glm_next.py``'s."""
    d, h, dk, r = cfg.d_model, cfg.n_heads, cfg.kda_head_dim, cfg.kda_rank
    return {
        "w_qkv": mat(d, 3 * h * dk),
        "conv": moe.draw(next(keys), (cfg.conv_kernel, 3 * h * dk),
                         cfg.conv_kernel ** -0.5, cfg.compute_dtype),
        "w_f_down": mat(d, r), "w_f_up": mat(r, h * dk),
        "dt_bias": jax.random.normal(next(keys), (h * dk,), jnp.float32),
        "a_log": jnp.log(jax.random.uniform(
            next(keys), (h,), jnp.float32, 0.5, 4.0)),
        "w_beta": mat(d, h),
        "w_g_down": mat(d, r), "w_g_up": mat(r, h * dk),
        "o_norm": around_one(dk),
        "wo": mat(h * dk, d, out=True),
    }


# --------------------------------------------------------------------------
# KDA
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _kda_inputs(cfg: SolarConfig, p, x, conv_rows, real_rows=None):
    """What both forms of KDA start from. x [B, T, D] (normed);
    ``conv_rows`` [B, K-1, 3*H*dk]: the projection rows before x's
    first; ``real_rows`` [B]: rows from that index on are padding (g
    0). -> (q, k, v [B, T, H, dk] float32, log decay g [B, T, H, dk]
    (Kimi Linear's: no lower bound), beta [B, T, H] in (0, 2), the
    projection's rows [B, T, 3*H*dk]: behind ``conv_rows`` they hold
    the next ``conv_rows``). The convolution, the norms and the decay
    are ``ops.kda_inputs``, shared with Ling's block: one kernel over a
    segment's rows on a TPU, the XLA body elsewhere and for a step."""
    f32 = jnp.float32
    proj = x @ p["w_qkv"]

    def f():
        return jnp.dot(x @ p["w_f_down"], p["w_f_up"],
                       preferred_element_type=f32) + p["dt_bias"]

    q, k, v, g = _kda_qkvg(proj, conv_rows, p["conv"], f, p["a_log"],
                           real_rows=real_rows)
    beta = 2.0 * jax.nn.sigmoid(jnp.dot(x, p["w_beta"],
                                        preferred_element_type=f32))
    return q, k, v, g, beta, proj


def _kda_out(cfg: SolarConfig, p, x, o):
    """o [B, T, H, dk] float32 -> [B, T, D]: the low-rank output gate of
    the layer's input x, then Ling's head-wise norm, gate and ``W_o``."""
    with jax.named_scope("attn_out"):
        gate = jax.nn.sigmoid(jnp.dot(
            x @ p["w_g_down"], p["w_g_up"],
            preferred_element_type=jnp.float32)).reshape(o.shape)
    return ling._kda_out(cfg, p, o, gate)


def kda_empty(cfg: SolarConfig, b: int) -> dict:
    """The state of ``b`` streams before their first token."""
    h, dk = cfg.n_heads, cfg.kda_head_dim
    return {"s": jnp.zeros((b, h, dk, dk), jnp.float32),
            "conv": jnp.zeros((b, cfg.conv_kernel - 1, 3 * h * dk),
                              cfg.compute_dtype)}


def kda_step(cfg, p, x, state, active, inputs=None):
    """A decode step of a KDA layer. x [B, 1, D] (normed); ``state``
    {"s" [B, H, dk, dv] float32, "conv" [B, K-1, 3*H*dk]}. A slot that
    is not ``active`` keeps its state. ``inputs``: another block's
    ``_kda_inputs`` (its decay's and beta's form; this block's where
    None). -> ([B, 1, D], state)."""
    q, k, v, g, beta, proj = (inputs or _kda_inputs)(
        cfg, p, x, state["conv"])
    with jax.named_scope("attn/attn_linear"):
        s, o = _kda_step(state["s"], q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                         beta[:, 0], active)
    with jax.named_scope("cache"):
        rows = jnp.concatenate([state["conv"], proj], axis=1)[:, 1:]
        new = {"s": s, "conv": jnp.where(active[:, None, None], rows,
                                         state["conv"])}
    return _kda_out(cfg, p, x, o[:, None]), new


def kda_segment(cfg, p, x, state, start, true_lens, inputs=None):
    """A KDA layer over one segment of whole prompts: rows ``start`` ..
    ``start + T - 1`` of x [B, T, D] (normed, right-padded: ``true_lens``
    [B] rows of each prompt are real), from the ``state`` the rows
    before them left ({"s", "conv"}: zeros at a prompt's start). A
    padding row has beta 0 and g 0 and leaves ``S`` as it was, and the
    convolution rows kept are the last K-1 REAL ones: the state after a
    prompt's last segment is the state after its last real token. The
    chunkwise delta rule is ``ops.kda_chunk``: on a TPU one kernel call
    a segment that carries ``S`` on the chip from the segment's first
    chunk to its last; the XLA body ``kda_chunked`` elsewhere.
    ``inputs`` as :func:`kda_step`'s. -> ([B, T, D], state)."""
    t = x.shape[1]
    q, k, v, g, beta, proj = (inputs or _kda_inputs)(
        cfg, p, x, state["conv"], true_lens - start)
    with jax.named_scope("attn/attn_linear"):
        real = start + jnp.arange(t)[None, :] < true_lens[:, None]  # [B, T]
        beta = jnp.where(real[..., None], beta, 0.0)
        pad = -t % cfg.kda_chunk
        if pad:  # (a bucket narrower than a chunk: the CPU rehearsal's)
            q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                        + ((0, 0),) * (a.ndim - 2))
                                for a in (q, k, v, g, beta))
        o, s = _kda_chunk(q, k, v, g, beta, state["s"],
                          chunk=cfg.kda_chunk)
    with jax.named_scope("cache"):
        # row j of the K-1 rows before the segment and its own is
        # position start - (K-1) + j: the last K-1 real rows are j = n
        # .. n + K-2 for n = the real rows in or before this segment; a
        # prompt that ended earlier keeps what it had
        conv = kept_rows(state["conv"], proj,
                         jnp.clip(true_lens - start, 0, t))
    return _kda_out(cfg, p, x, o[:, :t]), {"s": s, "conv": conv}


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _qkv(cfg: SolarConfig, p, x):
    """x [B, T, D] (normed) -> (q [B, T, Hq, hd], k, v [B, T, Hkv, hd]):
    one product; no norm, no rotation."""
    b, t, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = x @ p["w_qkv"]
    return (qkv[..., :hq * hd].reshape(b, t, hq, hd),
            qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd),
            qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd))


@jax.named_scope("attn_out")
def _gqa_out(cfg: SolarConfig, p, x, o):
    """o [B, T, Hq, hd] -> [B, T, D]: the elementwise sigmoid gate of
    the layer's input x, then ``W_o``."""
    b, t = o.shape[:2]
    gate = jax.nn.sigmoid(jnp.dot(x, p["w_gate"],
                                  preferred_element_type=jnp.float32))
    o = (o.reshape(b, t, -1).astype(jnp.float32) * gate)
    return o.astype(cfg.compute_dtype) @ p["wo"]


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def prefill(params, tokens, true_lens, cfg: SolarConfig,
            loads: bool = False, live=None, differentiable: bool = False):
    """tokens [B, T] (right-padded, ``true_lens`` [B] real) from empty
    state, the tokenwise parts in segments of ``moe.segment_rows`` rows
    (module docstring) -> (h [B, T, D] before the final norm, the
    streams' state {"kda": a list of {"s", "conv"} a KDA layer,
    "k_full", "v_full" [L_full, B, T, Hkv * hd]: the GQA layers' rows,
    padding's among them}, and with ``loads`` (the held experts'
    assignments from the real positions [L, count] int32, the expert
    layer's calls and those that took its compact branch [2]:
    ``moe.compact_calls``), else None).

    ``live`` (``jnp.max(true_lens)``, traced: the serving call's) leaves
    the DEAD segments out of every layer's scans (``moe.in_segments``:
    those that begin past the longest prompt's last real row): a KDA
    layer's state after the last live segment is the state after the
    last real token, and a dead segment's rows of h, k and v are zeros,
    which nothing reads (the GQA layer's one flash call over the bucket
    takes them as it took the padding's: causal, behind every real
    row). ``None`` runs every segment: the whole sequences of
    ``forward``.

    ``differentiable`` (``forward`` alone sets it: what ``loss_fn``
    differentiates) keeps the GQA layer on ``ops.attention.attention``
    and its lse; a serving prefill takes the forward-only call
    (``attend_bucket``; that docstring says which is whose)."""
    b, t = tokens.shape
    seg = moe.segment_rows(t, cfg.kda_chunk)
    attend = attention if differentiable else attend_bucket  # (causal)
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    kda, k_rows, v_rows, counts = [], [], [], []

    def experts(p, h_seg, start):
        aux = {} if loads else None
        h_seg = moe.mlp_layer(cfg, True, p, h_seg, aux)
        return h_seg, ((moe.prefill_loads(cfg, aux["expert_ids"][None],
                                          true_lens - start)[0],
                        moe.compact_calls([aux])) if loads else ())

    for i, p in enumerate(params["layers"]):
        def norm(h_seg, p=p):
            with jax.named_scope("qkv"):
                return rms_norm(h_seg, p["attn_norm"], cfg.rms_eps)

        if cfg.full(i):
            def project(_, xs, p=p):
                return (), _qkv(cfg, p["attn"], norm(xs[1]))

            _, (q, k, v) = moe.in_segments(project, (), h, seg, live)
            with jax.named_scope("attn/attn_full"):
                o = attend(q, k, v, use_flash=cfg.use_flash)

            def rest(count, xs, p=p):
                start, (h_seg, o_seg) = xs
                with jax.named_scope("attn_out"):
                    h_seg = h_seg + _gqa_out(cfg, p["attn"], norm(h_seg),
                                             o_seg)
                h_seg, n = experts(p, h_seg, start)
                return jax.tree_util.tree_map(jnp.add, count, n), h_seg

            with jax.named_scope("cache"):
                k_rows.append(k.reshape(b, t, -1))
                v_rows.append(v.reshape(b, t, -1))
            count, h = moe.in_segments(rest, _zero_loads(cfg, loads), (h, o),
                                       seg, live)
        else:
            def layer(carry, xs, p=p):
                state, count = carry
                start, h_seg = xs
                y, state = kda_segment(cfg, p["attn"], norm(h_seg), state,
                                       start, true_lens)
                with jax.named_scope("attn_out"):
                    h_seg = h_seg + y
                h_seg, n = experts(p, h_seg, start)
                return (state, jax.tree_util.tree_map(jnp.add, count, n)), \
                    h_seg

            (state, count), h = moe.in_segments(
                layer, (kda_empty(cfg, b), _zero_loads(cfg, loads)), h, seg,
                live)
            kda.append(state)
        counts.append(count)

    def stack(parts):  # (no GQA layer: no rows)
        return jnp.stack(parts) if parts else jnp.zeros(
            (0, b, t, cfg.kv_width), cfg.compute_dtype)

    with jax.named_scope("cache"):
        state = {"kda": kda, "k_full": stack(k_rows),
                 "v_full": stack(v_rows)}
    return h, state, moe.prefill_counts(counts) if loads else None


def _zero_loads(cfg: SolarConfig, loads: bool):
    return (jnp.zeros((cfg.held[1],), jnp.int32),
            jnp.zeros((2,), jnp.int32)) if loads else ()


def forward(params, tokens, cfg: SolarConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    chunkwise KDA and the prompt's attention."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg,
                      differentiable=True)
    return moe.logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: SolarConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` as :meth:`_Slots.init_state` makes it, without ``pos``. A
    KDA layer updates its ``S`` and convolution rows (``ops.kda_step``);
    a GQA layer writes its B new rows at ``[layer, slot, pos]`` and
    attends over the slot's ``pos + 1`` rows (``ops.decode_attention``
    on the stack in place, the kernel's visits made here once, before
    the layers); an inactive slot keeps its state and attends over
    nothing. -> (float32 logits [B, V], the state updated, three [L]
    int32 counters of the ACTIVE slots' routing: distinct held experts
    touched, assignments, assignments to held experts)."""
    b = tok.shape[0]
    slots = jnp.arange(b)
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        plan = _da.visits(lengths, state["k_full"].shape[2])
    kf, vf, kda = state["k_full"], state["v_full"], list(state["kda"])
    counts = []
    for i, p in enumerate(params["layers"]):
        layer = cfg.stack_index(i)
        with jax.named_scope("qkv"):
            x = rms_norm(h, p["attn_norm"], cfg.rms_eps)
        if cfg.full(i):
            q, k, v = _qkv(cfg, p["attn"], x)
            with jax.named_scope("cache"):
                kf = kf.at[layer, slots, pos].set(k.reshape(b, -1))
                vf = vf.at[layer, slots, pos].set(v.reshape(b, -1))
            with jax.named_scope("attn/attn_full"):
                o = _da.decode_attention(q, kf, vf, layer, lengths,
                                         plan=plan)
            y = _gqa_out(cfg, p["attn"], x, o)
        else:
            y, kda[layer] = kda_step(cfg, p["attn"], x, kda[layer], active)
        with jax.named_scope("attn_out"):
            h = h + y
        aux = {}
        h = moe.mlp_layer(cfg, True, p, h, aux)
        counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    state = {"kda": kda, "k_full": kf, "v_full": vf}
    return moe.logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """A recurrent state a KDA layer, which cannot be cut or rewound at
    a position, beside the GQA layers' stacks of rows."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "o_norm", "a_log",
                  "dt_bias", "router_bias")

    @staticmethod
    def row_kinds(cfg: SolarConfig) -> dict:
        # (a recurrent layer keeps no rows: 0 of a slot's are live)
        return {"recurrent": (cfg.kda_layers, 0),
                "full": (cfg.full_layers, None)}

    @staticmethod
    def prefill_segments(cfg: SolarConfig, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket, cfg.kda_chunk)

    @staticmethod
    def init_state(cfg: SolarConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        full = (cfg.full_layers, slots, max_len, cfg.kv_width)
        return {
            "kda": [kda_empty(cfg, slots) for _ in range(cfg.kda_layers)],
            "k_full": jnp.zeros(full, cdt), "v_full": jnp.zeros(full, cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k_full"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def size(a):  # (by shape: the state may be described only)
            return a.size * a.dtype.itemsize

        return {"recurrent": sum(size(a) for st in state["kda"]
                                 for a in st.values()),
                "full": size(state["k_full"]) + size(state["v_full"])}

    @staticmethod
    def step(cfg: SolarConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: SolarConfig, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state (a reused slot starts from a
        zero ``S`` and zero convolution rows). -> (the streams' state,
        [F] prompt lengths, [F] first tokens, [F] their logprobs, the
        held experts' assignments from the real positions [L, count],
        the expert layer's calls and compact calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, streams, loads = prefill(params, prompts, true_lens, cfg,
                                    loads=True, live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(moe.logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        return streams, true_lens, toks0, logp0, *loads

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' state into their slots: a KDA layer's
        ``S`` and convolution rows replaced whole, a GQA layer's P rows
        onto the first P rows of the slot. What the slot's last stream
        wrote behind them stays: no reader looks past a slot's own
        length (``_prefill_batch_into_slots``' docstring)."""
        def rows(all_, new):  # [L, slots, S, C] <- [L, F, P <= S, C]
            return all_.at[:, slots, :new.shape[2]].set(
                new.astype(all_.dtype))

        return {
            "kda": [{name: st[name].at[slots].set(
                        new[name].astype(st[name].dtype)) for name in st}
                    for st, new in zip(state["kda"], streams["kda"])],
            "k_full": rows(state["k_full"], streams["k_full"]),
            "v_full": rows(state["v_full"], streams["v_full"]),
            "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
