"""A hybrid decoder of gated short convolutions: a mixer that is one
depthwise causal convolution of three taps between two gates, three
layers in four, beside GQA layers of 64-wide heads with a norm a head on
q and k; two dense SwiGLU layers first, then a sigmoid top-k router over
experts of which this device holds a part, with no shared expert; a tied
head. The language model of LFM2-8B-A1B (``model_type`` ``lfm2_moe``) as
its ``config.json`` and transformers' ``Lfm2Moe*`` classes give it; the
eleventh block beside ``llama.py``, ``ling.py``, ``exaone.py``,
``instella.py``, ``solar.py``, ``mimo.py``, ``granite.py``, ``dots.py``,
``glm_dsa.py`` and ``glm_next.py``.

Layer ``i`` mixes by ``layer_types[i]``: ``"conv"`` or
``"full_attention"``. The published list is NOT periodic to its end
(``conv conv | full conv conv conv`` x 4 ``| full conv conv | full conv
conv``): the block reads the list and derives no period. Pre-norm, ``h
<- h + Mix(RMSNorm(h))``, ``h <- h + FF(RMSNorm(h))``. The layers are
NOT a stack scanned by one loop: each is its own dict of leaves and the
programs unroll them.

- **Conv** (``Lfm2ShortConv``): ``[b | c | x] = n W_in`` (three thirds
  of ``d_model``, no bias); ``u = b * x``; ``v_t = sum_i w_i u_{t - (K
  - 1) + i}`` a channel (depthwise, causal, zeros before a stream's
  first row, ``conv_kernel`` K = 3 taps, no bias, no activation); ``y =
  c * v``; ``y W_out``. A stream's whole state is the last K - 1 rows of
  ``u`` (the published cache keeps K columns and reads K - 1 of them).
- **Full**: q of ``n_heads`` x ``head_dim``, k and v of ``n_kv_heads``
  x ``head_dim``, no bias; an RMS norm over each head's numbers on q and
  on k (``q_norm``, ``k_norm``) BEFORE the rotation; rotary over the
  whole head (rotate-half, ``rope_theta``); query head h = kv * group +
  r on kv head ``kv``; causal softmax over ``q k^T / sqrt(head_dim)``.
  A prompt's segment attends through ``ops.attention.attend_rows`` (the
  flash kernel at a traced offset on a TPU), a decode step through
  ``ops.decode_attention`` over the slot's rows: a 64-wide head is half
  a lane tile, two heads a tile (``decode_attention``'s docstring).
- **FF**: layers below ``n_dense_layers`` a dense SwiGLU of
  ``dense_d_ff``; the others ``models/moe.py``'s expert layer: sigmoid
  scores in float32, ``router_bias`` added for the selection only, the
  ``top_k`` best chosen, their unbiased scores divided by ``their sum +
  norm_topk_eps`` (the published ``1e-6``) and scaled. ``held_experts =
  (first, count)``: the part this device computes.

**Prefill runs in row segments**, every layer one ``lax.scan`` over
segments of at most ``moe.SEGMENT_ROWS`` rows (``moe.in_segments``) with
the layer's expert half inside it: a conv layer carries its K - 1 rows
of ``u`` from segment to segment and keeps the last K - 1 REAL ones (a
padding row reaches neither the state nor a real row's output: the
convolution is causal), a full layer carries its k and v rows so far in
the flash kernel's layout and a segment's queries see them at a traced
offset. A serving call's scans skip the dead segments behind its longest
prompt.

A slot's state in the serving engine is this model's own
(:data:`SLOTS`, found through ``Lfm2Config.slot_model``), of two kinds
side by side: ``conv [L_conv, slots, K - 1, d_model]`` in the compute
dtype and the full layers' k and v stacks ``[L_full, slots, max_len, Hkv
* hd]`` in the Llama block's layout. Convolution rows can be kept only
where they were saved, so the prefix cache, speculative decoding and the
prefill workers refuse this model by name (``rows_state``).

Types: matrices and the taps in ``dtype`` (bf16), products accumulated
in float32; ``u`` rounded to the compute dtype (what the slot keeps),
the taps' sum and the gate ``c *`` in float32; norm vectors, the head
norms and ``router_bias`` float32; router scores and softmax statistics
float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_rows
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rotary, rotary_embedding

KINDS = ("conv", "full_attention")
# The seeded weights' (init_params says what for): what the matrices that
# write into the stream are scaled by besides, the mixers' and the
# feed-forwards', as multiples of the embedding's scale sqrt(8 / d_model)
MIXER_TO_START, FF_TO_START = 3.2, 0.8


@dataclasses.dataclass(frozen=True)
class Lfm2Config(moe.HeldExperts):
    vocab_size: int = 65536
    d_model: int = 2048
    n_layers: int = 24
    # "conv" | "full_attention" a layer, all of them: no period is derived
    layer_types: tuple = ()
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3  # taps; a stream keeps conv_kernel - 1 rows of u
    # the first n_dense_layers layers' SwiGLU; d_ff is ONE expert's width
    n_dense_layers: int = 2
    dense_d_ff: int = 7168
    d_ff: int = 1792
    shared_d_ff: int = 0
    n_experts: int = 32
    top_k: int = 4
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # what the chosen scores' sum gains before it divides (moe.route)
    norm_topk_eps: float = 1e-6
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    max_seq_len: int = 8720
    dtype: str = "bfloat16"
    # None: ``ops.attention``'s own choice (flash on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        kinds = tuple(self.layer_types)
        if len(kinds) != self.n_layers or set(kinds) - set(KINDS):
            raise ValueError(
                f"layer_types {kinds} do not name one of {KINDS} for each "
                f"of {self.n_layers} layers")
        object.__setattr__(self, "layer_types", kinds)

    @property
    def kv_width(self) -> int:
        """What a cache row holds: the position's kv heads end to end."""
        return self.n_kv_heads * self.head_dim

    def full(self, i: int) -> bool:
        return self.layer_types[i] == "full_attention"

    def sparse(self, i: int) -> bool:
        return i >= self.n_dense_layers

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind."""
        return sum(self.full(j) == self.full(i) for j in range(i))

    @property
    def full_layers(self) -> int:
        return sum(k == "full_attention" for k in self.layer_types)

    @property
    def conv_layers(self) -> int:
        return self.n_layers - self.full_layers

    @property
    def moe_layers(self) -> int:
        return max(0, self.n_layers - self.n_dense_layers)

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "Lfm2Config":
        """Test-size config: the published list's head (two conv layers,
        a full one, a conv layer) and an uneven tail, one dense layer,
        heads x head_dim unequal to the hidden size; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=6,
            layer_types=("conv", "conv", "full_attention", "conv",
                         "full_attention", "conv"),
            n_heads=4, n_kv_heads=2, head_dim=16, n_dense_layers=1,
            dense_d_ff=96, d_ff=32, n_experts=8, top_k=2, max_seq_len=128,
            dtype="float32")
        base.update(kw)
        return Lfm2Config(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: Lfm2Config, key):
    """The tree in the SERVING types (module docstring), leaf by leaf in
    blocks (``moe.draw``). Matrices are normal / sqrt(fan_in), and those
    that write into the residual stream (``w_out``, ``wo``, every
    ``w_down``) are scaled by (2 x depth)^-1/2 besides (``moe.makers``);
    the taps normal / sqrt(K); norm scales, the head norms among them,
    around 1; the router's bias away from 0 (``moe.init_experts``). The
    embedding is the head too (tied): one array, no ``lm_head``.

    Granite's two choices for a seeded model with a TIED head
    (``granite.init_params`` has the readings) and for its reasons: the
    stream starts as the token's own row of the matrix the head
    multiplies by, so (1) the final norm's scale is drawn around 1 with
    a random SIGN a channel (with a positive scale every position's
    largest logit is its own input token's) and (2) the embedding is
    drawn normal x sqrt(8 / d_model), 1 / 16 at the published width, so
    that what the layers add outweighs the start and the next token is
    no symmetric form of the last one, which greedy decoding climbs
    into a cycle of two. The logits then spread by sqrt(8) = 2.8 at
    every width: a tolerance in logits read at one width means the same
    at another.

    **What the layers add, against what the stream starts with**
    (:data:`MIXER_TO_START`, :data:`FF_TO_START`: a rule of this
    initialisation, no field of the configuration). With ``makers``'
    scales alone the 48 sublayers add 0.70 to a start of 0.0625 at the
    published width, the FIRST layer alone three times the start, and
    the bf16 program's stream stands 19% off the float32 one's behind
    layer 24 (0.6% behind layer 1, 9% behind layer 12): a stream
    rewritten eleven times over by sublayers that are cubic in their
    input (``c * conv(b * x)``) multiplies what bf16 rounds off, and an
    expert layer that holds HALF of a top-4 of 32 with no shared expert
    turns a score's last bit into a whole expert's output (4% of the
    held assignments moved): its logits stood 0.29-0.33 off in the
    median against a top-two gap of 0.42 and a comparison of served
    tokens compared rounding. So the matrices that write into the
    stream are scaled once more, by a multiple of the embedding's own
    scale (never above 1: a test's width keeps ``makers``' scales for
    its mixers): the mixers' (``w_out``, ``wo``) by 3.2 x sqrt(8 /
    d_model), 0.2 at the published width, the feed-forwards' (every
    ``w_down``) by 0.8 x sqrt(8 / d_model), 0.05. Read on the chip at
    the harness's own probe (127 tokens in, 24 out, 32 prompts a seed;
    ``PERF.md`` section 6, PR 68) against every write at 0.1: over 160
    probes the served token parts from the reference's up to a gap of
    0.40 (0.86 over 32) and gives up 0.036 a token at most, while the
    same program with its matrices in 3 mantissa bits gives up 0.30 in
    the median and 0.17 at nine probes in ten (at 0.1 it parted from a
    gap of 0.07 up and no limit stood between the two): the
    feed-forwards' share is what a moved assignment costs, the mixers'
    what a rounded weight shows. Greedy streams emit 62-64 distinct
    tokens in their last 64 (both at 0.05 they fall into cycles)."""
    cdt = cfg.compute_dtype
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 16 * (cfg.n_layers + 1)))
    drawn, around_one = moe.makers(cfg, keys)

    def writing(to_start):
        """``drawn``, with what writes into the stream scaled besides."""
        by = min(1.0, to_start * (8.0 / d) ** 0.5)

        def mat(*shape, out=False):
            w = drawn(*shape, out=out)
            return (w * by).astype(w.dtype) if out and by != 1.0 else w

        return mat

    mix, ff = writing(MIXER_TO_START), writing(FF_TO_START)

    def conv():
        return {"w_in": mix(d, 3 * d),
                "conv": moe.draw(next(keys), (cfg.conv_kernel, d),
                                 cfg.conv_kernel ** -0.5, cdt),
                "w_out": mix(d, d, out=True)}

    def gqa():
        return {"w_qkv": mix(d, (hq + 2 * hkv) * hd),
                "q_norm": around_one(hd), "k_norm": around_one(hd),
                "wo": mix(hq * hd, d, out=True)}

    layers = [{
        "attn_norm": around_one(d),
        "attn": gqa() if cfg.full(i) else conv(),
        "mlp_norm": around_one(d),
        "mlp": moe.init_experts(cfg, ff, keys) if cfg.sparse(i)
        else moe.init_dense(cfg, ff),
    } for i in range(cfg.n_layers)]
    sign = jnp.where(jax.random.bernoulli(next(keys), 0.5, (d,)), 1.0, -1.0)
    return {"embed": moe.draw(next(keys), (cfg.vocab_size, d),
                              (8.0 / d) ** 0.5, cdt),
            "layers": layers, "final_norm": around_one(d) * sign}


# --------------------------------------------------------------------------
# The gated short convolution
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _conv_inputs(cfg: Lfm2Config, p, h):
    """h [B, T, D] -> the input product's thirds (b, c, x), each [B, T,
    D] in the compute dtype, in the order the family's code chunks them."""
    d = cfg.d_model
    proj = rms_norm(h, p["attn_norm"], cfg.rms_eps) @ p["attn"]["w_in"]
    return proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]


@jax.named_scope("attn/attn_conv")
def _short_conv(p, b, c, x, rows):
    """``c * conv_K(b * x)`` from the ``rows`` [B, K-1, D] of ``u = b *
    x`` before the first of these T -> (y [B, T, D] in the compute
    dtype, u [B, K-1+T, D]: ``rows`` and these rows' own, whose tail is
    the next ``rows``). The taps' sum and the gate in float32."""
    t = b.shape[1]
    f32 = jnp.float32
    u = jnp.concatenate([rows, b * x], axis=1)
    w = p["conv"].astype(f32)
    v = sum(w[i] * u[:, i:i + t].astype(f32) for i in range(w.shape[0]))
    return (c.astype(f32) * v).astype(b.dtype), u


def conv_step(cfg: Lfm2Config, p, h, rows, active):
    """A decode step of a conv layer's mixer. h [B, 1, D]; ``rows`` [B,
    K-1, D]; a slot that is not ``active`` keeps its rows. -> (the
    mixer's output [B, 1, D], rows)."""
    y, u = _short_conv(p["attn"], *_conv_inputs(cfg, p, h), rows)
    with jax.named_scope("cache"):
        rows = jnp.where(active[:, None, None], u[:, 1:], rows)
    with jax.named_scope("attn_out"):
        return y @ p["attn"]["w_out"], rows


def conv_segment(cfg: Lfm2Config, p, h, rows, start, true_lens):
    """A conv layer's mixer over one segment of whole prompts: rows
    ``start`` .. ``start + T - 1`` of h [B, T, D] (right-padded:
    ``true_lens`` [B] rows of each prompt are real), from the ``rows``
    [B, K-1, D] of ``u`` the rows before them left (zeros at a prompt's
    start). The rows kept are the last K - 1 REAL ones, so the state
    after a prompt's last segment is the state after its last real
    token (``granite.ssm_segment``'s rule). -> ([B, T, D], rows)."""
    t = h.shape[1]
    y, u = _short_conv(p["attn"], *_conv_inputs(cfg, p, h), rows)
    with jax.named_scope("cache"):
        # u's row j is position start - (K-1) + j: the last K-1 real
        # rows are j = n .. n + K-2 for n = the real rows in or before
        # this segment; a prompt that ended earlier keeps what it had
        n = jnp.clip(true_lens - start, 0, t)
        at = n[:, None] + jnp.arange(rows.shape[1])[None, :]
        rows = jnp.take_along_axis(u, at[..., None], axis=1)
    with jax.named_scope("attn_out"):
        return y @ p["attn"]["w_out"], rows


# --------------------------------------------------------------------------
# GQA with a norm a head
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _qkv(cfg: Lfm2Config, p, h, positions):
    """h [B, T, D] at ``positions`` [B, T] -> (q [B, T, Hq, hd], k, v
    [B, T, Hkv, hd]): one product, each head of q and of k normed over
    its own numbers, then rotated."""
    b, t, _ = h.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = p["attn"]
    qkv = rms_norm(h, p["attn_norm"], cfg.rms_eps) @ a["w_qkv"]
    q = qkv[..., :hq * hd].reshape(b, t, hq, hd)
    k = qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd)
    v = qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd)
    rotation = rotary_embedding(positions, hd, cfg.rope_theta)
    return (apply_rotary(rms_norm(q, a["q_norm"], cfg.rms_eps), *rotation),
            apply_rotary(rms_norm(k, a["k_norm"], cfg.rms_eps), *rotation),
            v)


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

@jax.named_scope("lm_head")
def logits(cfg: Lfm2Config, params, h):
    """h [..., D] before the final norm -> float32 logits [..., V]: the
    tied head."""
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return jax.lax.dot_general(
        h, params["embed"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def prefill(params, tokens, true_lens, cfg: Lfm2Config,
            loads: bool = False, live=None):
    """tokens [B, T] (right-padded, ``true_lens`` [B] real) from empty
    state, every layer in segments of ``moe.segment_rows`` rows (module
    docstring) -> (h [B, T, D] before the final norm, the streams' state
    {"conv" [L_conv, B, K-1, D]: each conv layer's last real rows of
    ``u``, "k_full", "v_full": a list of [B, T, Hkv * hd] a full layer,
    padding's rows among them}, and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count] int32, the expert
    layer's calls that had and that took its compact branch [2]: none
    here, half of the experts is held), else None). ``live`` as
    ``mimo.prefill`` takes it: the serving call's ``max(true_lens)``
    leaves the dead segments out; ``None`` runs every segment."""
    b, t = tokens.shape
    seg = moe.segment_rows(t)
    cdt = cfg.compute_dtype
    with jax.named_scope("embed"):
        h = params["embed"][tokens]
    conv, k_rows, v_rows, counts = [], [], [], []
    for i, p in enumerate(params["layers"]):
        full, sparse = cfg.full(i), cfg.sparse(i)
        count_loads = loads and sparse

        def layer(carry, xs, p=p, full=full, sparse=sparse,
                  count_loads=count_loads):
            mixer, count = carry
            start, h_seg = xs
            if full:
                k_all, v_all = mixer
                at = start + jnp.arange(h_seg.shape[1], dtype=jnp.int32)
                q, k, v = _qkv(cfg, p, h_seg,
                               jnp.broadcast_to(at, h_seg.shape[:2]))
                with jax.named_scope("cache"):
                    # the layer's rows so far, heads outermost: the
                    # flash kernel's layout
                    k_all = jax.lax.dynamic_update_slice(
                        k_all, k.transpose(0, 2, 1, 3), (0, 0, start, 0))
                    v_all = jax.lax.dynamic_update_slice(
                        v_all, v.transpose(0, 2, 1, 3), (0, 0, start, 0))
                with jax.named_scope("attn/attn_full"):
                    o = attend_rows(q.transpose(0, 2, 1, 3), k_all, v_all,
                                    offset=start, use_flash=cfg.use_flash)
                with jax.named_scope("attn_out"):
                    o = o.transpose(0, 2, 1, 3).reshape(*h_seg.shape[:2], -1)
                    y = o @ p["attn"]["wo"]
                mixer = (k_all, v_all)
            else:
                y, mixer = conv_segment(cfg, p, h_seg, mixer, start,
                                        true_lens)
            with jax.named_scope("attn_out"):
                h_seg = h_seg + y
            aux = {} if count_loads else None
            h_seg = moe.mlp_layer(cfg, sparse, p, h_seg, aux)
            if count_loads:
                count = jax.tree_util.tree_map(jnp.add, count, (
                    moe.prefill_loads(cfg, aux["expert_ids"][None],
                                      true_lens - start)[0],
                    moe.compact_calls([aux])))
            return (mixer, count), h_seg

        rows = (b, cfg.n_kv_heads, t, cfg.head_dim)
        empty = ((jnp.zeros(rows, cdt), jnp.zeros(rows, cdt)) if full
                 else jnp.zeros((b, cfg.conv_kernel - 1, cfg.d_model), cdt),
                 (jnp.zeros((cfg.held[1],), jnp.int32),
                  jnp.zeros((2,), jnp.int32)) if count_loads else ())
        (mixer, count), h = moe.in_segments(layer, empty, h, seg, live)
        with jax.named_scope("cache"):
            if full:
                k_rows.append(mixer[0].transpose(0, 2, 1, 3).reshape(b, t, -1))
                v_rows.append(mixer[1].transpose(0, 2, 1, 3).reshape(b, t, -1))
            else:
                conv.append(mixer)
        if count_loads:
            counts.append(count)
    with jax.named_scope("cache"):
        state = {"conv": jnp.stack(conv) if conv else jnp.zeros(
                     (0, b, cfg.conv_kernel - 1, cfg.d_model), cdt),
                 "k_full": k_rows, "v_full": v_rows}
    return h, state, moe.prefill_counts(counts) if counts else None


def forward(params, tokens, cfg: Lfm2Config):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg)
    return logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: Lfm2Config, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` as :meth:`_Slots.init_state` makes it, without ``pos``. A
    conv layer reads its slot's K - 1 rows of ``u`` and writes the newer
    K - 1; a full layer writes its B new rows at ``[layer, slot, pos]``
    and attends over the slot's ``pos + 1`` rows
    (``ops.decode_attention`` on the stack in place, the kernel's visits
    made here once, before the layers); an inactive slot keeps its state
    and attends over nothing. -> (float32 logits [B, V], the state
    updated, three [L_moe] int32 counters of the ACTIVE slots' routing:
    distinct held experts touched, assignments, assignments to held
    experts)."""
    b = tok.shape[0]
    slots = jnp.arange(b)
    with jax.named_scope("embed"):
        h = params["embed"][tok][:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        plan = _da.visits(lengths, state["k_full"].shape[2])
    kf, vf, conv = state["k_full"], state["v_full"], state["conv"]
    counts = []
    for i, p in enumerate(params["layers"]):
        layer = cfg.stack_index(i)
        if cfg.full(i):
            q, k, v = _qkv(cfg, p, h, pos[:, None])
            with jax.named_scope("cache"):
                kf = kf.at[layer, slots, pos].set(k.reshape(b, -1))
                vf = vf.at[layer, slots, pos].set(v.reshape(b, -1))
            with jax.named_scope("attn/attn_full"):
                o = _da.decode_attention(q, kf, vf, layer, lengths,
                                         plan=plan)
            with jax.named_scope("attn_out"):
                y = o.reshape(b, 1, -1) @ p["attn"]["wo"]
        else:
            y, rows = conv_step(cfg, p, h, conv[layer], active)
            with jax.named_scope("cache"):
                conv = conv.at[layer].set(rows)
        with jax.named_scope("attn_out"):
            h = h + y
        aux = {} if cfg.sparse(i) else None
        h = moe.mlp_layer(cfg, cfg.sparse(i), p, h, aux)
        if aux:
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    state = {"conv": conv, "k_full": kf, "v_full": vf}
    return logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """Convolution rows a conv layer, which can be kept only where they
    were saved, beside the full layers' stacks of rows."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "q_norm", "k_norm",
                  "router_bias")

    @staticmethod
    def row_kinds(cfg: Lfm2Config) -> dict:
        # (a recurrent layer keeps no rows: 0 of a slot's are live)
        return {"recurrent": (cfg.conv_layers, 0),
                "full": (cfg.full_layers, None)}

    @staticmethod
    def prefill_segments(cfg: Lfm2Config, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket)

    @staticmethod
    def init_state(cfg: Lfm2Config, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        full = (cfg.full_layers, slots, max_len, cfg.kv_width)
        return {
            "conv": jnp.zeros((cfg.conv_layers, slots, cfg.conv_kernel - 1,
                               cfg.d_model), cdt),
            "k_full": jnp.zeros(full, cdt), "v_full": jnp.zeros(full, cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k_full"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def size(a):  # (by shape: the state may be described only)
            return a.size * a.dtype.itemsize

        return {"recurrent": size(state["conv"]),
                "full": size(state["k_full"]) + size(state["v_full"])}

    @staticmethod
    def step(cfg: Lfm2Config, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: Lfm2Config, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state (a reused slot starts from
        zero convolution rows). -> (the streams' state, [F] prompt
        lengths, [F] first tokens, [F] their logprobs, the held experts'
        assignments from the real positions [L_moe, count], the expert
        layer's calls and compact calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, streams, loads = prefill(params, prompts, true_lens, cfg,
                                    loads=cfg.moe_layers > 0,
                                    live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        return streams, true_lens, toks0, logp0, *(loads or ())

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' state into their slots: a conv layer's
        rows replaced whole, a full layer's P rows onto the first P rows
        of the slot. What the slot's last stream wrote behind them
        stays: no reader looks past a slot's own length
        (``_prefill_batch_into_slots``' docstring)."""
        return {
            "conv": state["conv"].at[:, slots].set(
                streams["conv"].astype(state["conv"].dtype)),
            "k_full": Slots.put_rows(state["k_full"], slots,
                                     streams["k_full"]),
            "v_full": Slots.put_rows(state["v_full"], slots,
                                     streams["v_full"]),
            "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
