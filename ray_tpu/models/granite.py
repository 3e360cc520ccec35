"""A hybrid decoder of state-space layers: Mamba-2 mixers nine layers in
ten beside softmax GQA layers without positions, every layer a mixture
of experts with a shared expert, of which this device holds a part. The
language model of Granite-4.0-H-Small (``model_type``
``granitemoehybrid``) as its ``config.json`` gives it; the seventh block
beside ``llama.py``, ``ling.py``, ``exaone.py``, ``instella.py``,
``solar.py`` and ``mimo.py``.

Layer ``i`` mixes by ``layer_types[i]``: ``"mamba"`` or ``"attention"``
(published: an attention layer at 5, 15, 25, 35). Pre-norm; both halves
are added to the stream times ``residual_multiplier``. The layers are
NOT a stack scanned by one loop: each is its own dict of leaves and the
programs unroll them.

- **Mamba-2** (Dao & Gu, arXiv:2405.21060; transformers'
  ``GraniteMoeHybridMambaLayer``, Bamba's): ``[z | xBC | dt] = u W_in``;
  ``xBC <- silu(conv_K(xBC) + bias)``, depthwise and causal; ``[x | B |
  C]``, x as ``ssm_heads`` heads of ``ssm_head_dim``, B and C ONE row of
  ``ssm_state`` for all heads (``mamba_n_groups`` 1; the mixer's
  functions and the ops take ``ssm_groups`` groups of heads, each with
  a B, a C and a gated norm of its own: ``models/nemotron.py`` calls
  them with eight); ``dt = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)`` a head; a head's state ``H [P, N]``:
  ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D
  x_t``; ``y <- RMSNorm(y * silu(z)) * w`` over all of the inner width
  (the gate BEFORE the norm); ``W_out``. A decode step is
  ``ops/ssd_step.py`` on every slot's state in place, a prefill
  ``ops/ssd_chunk.py``. State: float32 ``H [heads, P, N]`` a stream and
  the last ``conv_kernel - 1`` rows of ``xBC``.
- **GQA**: q of ``n_heads`` x ``head_dim``, k and v of ``n_kv_heads`` x
  ``head_dim``, no rotary (``position_embedding_type`` ``nope``), causal
  softmax over ``q k^T * attention_multiplier`` (the multiplier is
  folded into q, which every kernel then scales by ``head_dim^-1/2`` as
  it does for the other blocks). Prefill attends through
  ``ops.attention`` (the flash kernel on a TPU), a decode step through
  ``ops.decode_attention`` over the slot's rows.
- **MoE**, every layer (``models/moe.py``): the router scores by SOFTMAX
  over all ``n_experts`` in float32 (``router_softmax``), no bias, one
  group, ``top_k`` chosen and renormalised, which is the published
  top-k of the logits and a softmax over the chosen; one shared expert
  of its own width. ``held_experts = (first, count)``: the part this
  device computes.
- The model's four scalars stand in this file's lines alone:
  ``embedding_multiplier`` on the embedding's rows,
  ``residual_multiplier`` on both sublayers' outputs,
  ``attention_multiplier`` as the softmax's scale, ``logits_scaling``
  dividing the logits of the tied head.

**Prefill runs in row segments**, as ``models/solar.py``'s does and for
its reasons: what is a function of a row and a carried state runs over
segments of at most ``moe.SEGMENT_ROWS`` rows under one ``lax.scan`` a
layer (``moe.in_segments``), a Mamba layer's ``H`` and last three
``xBC`` rows carried from segment to segment; only the attention
layer's q, k and v (bf16) and one flash call over them are whole. A
serving call's scans skip the dead segments behind its longest prompt.

A slot's state in the serving engine is this model's own
(:data:`SLOTS`, found through ``GraniteConfig.slot_model``), of two
kinds side by side: for each Mamba layer ``h [slots, heads / g, N, g
P]`` float32 (the step kernel's layout, ``ops.ssd_step.pack``: N down
the sublanes, ``g`` = 2 heads' P side by side in the lanes) and ``conv
[slots, K-1, inner + 2 N]`` (each layer its own array: the step kernel
writes ``h`` into the buffer it came from), for
the attention layers k and v stacks ``[L_full, slots, max_len, Hkv *
hd]`` in the Llama block's layout. A recurrent state cannot be cut at a
position, so the prefix cache, speculative decoding and the prefill
workers refuse this model by name (``rows_state``).

Types: matrices in ``dtype`` (bf16), products accumulated in float32;
norm vectors, ``a_log``, ``dt_bias``, ``d_skip`` and the convolution's
bias float32; ``dt``, the decay, router scores, softmax statistics and
``H`` float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_bucket, attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ssd_chunk import ssd_chunked as _ssd_chunk
from ray_tpu.ops import ssd_step as _ss
from ray_tpu.ops.ssd_step import ssd_step as _ssd_step


@dataclasses.dataclass(frozen=True)
class GraniteConfig(moe.HeldExperts):
    vocab_size: int = 100352
    d_model: int = 4096
    n_layers: int = 40
    # "mamba" | "attention" a layer; () = an attention layer after every
    # five Mamba layers of ten (the published period)
    layer_types: tuple = ()
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    # Mamba-2: the inner width is ssm_heads x ssm_head_dim
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    # groups of heads that share a B and a C row (``mamba_n_groups``;
    # the mixer's functions here are ``models/nemotron.py``'s too, whose
    # configuration has eight)
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk: int = 256  # rows of a chunk of the prefill's scan
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 768
    shared_d_ff: int = 1536
    n_experts: int = 72
    top_k: int = 10
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: str = "bfloat16"
    # None: ``ops.attention``'s own choice (flash on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    # (no field: what ``moe.moe`` scores its router's logits by)
    router_softmax = True

    def __post_init__(self):
        kinds = tuple(self.layer_types) or tuple(
            "attention" if i % 10 == 5 else "mamba"
            for i in range(self.n_layers))
        if len(kinds) != self.n_layers or set(kinds) - {"mamba", "attention"}:
            raise ValueError(
                f"layer_types {kinds} do not name 'mamba' or 'attention' "
                f"for each of {self.n_layers} layers")
        object.__setattr__(self, "layer_types", kinds)

    @property
    def kv_width(self) -> int:
        """What a cache row holds: the position's kv heads end to end."""
        return self.n_kv_heads * self.head_dim

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """What the convolution runs over: x, every group's B and every
        group's C end to end."""
        return self.inner + 2 * self.ssm_groups * self.ssm_state

    def full(self, i: int) -> bool:
        return self.layer_types[i] == "attention"

    def stack_index(self, i: int) -> int:
        """Layer ``i``'s place among the layers of its kind."""
        return sum(self.full(j) == self.full(i) for j in range(i))

    @property
    def full_layers(self) -> int:
        return sum(k == "attention" for k in self.layer_types)

    @property
    def ssm_layers(self) -> int:
        return self.n_layers - self.full_layers

    @property
    def moe_layers(self) -> int:
        return self.n_layers

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "GraniteConfig":
        """Test-size config: two Mamba layers, an attention layer and a
        Mamba layer, heads x head_dim unequal to the hidden size; runs
        on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, n_layers=4,
            layer_types=("mamba", "mamba", "attention", "mamba"),
            n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=8,
            ssm_head_dim=8, ssm_state=16, ssm_chunk=8, d_ff=32,
            shared_d_ff=48, n_experts=16, top_k=4, max_seq_len=128,
            dtype="float32")
        base.update(kw)
        return GraniteConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: GraniteConfig, key):
    """The tree in the SERVING types (module docstring), leaf by leaf in
    blocks (``moe.draw``). Matrices are normal / sqrt(fan_in), and those
    that write into the residual stream (``w_out``, ``wo``, every
    ``w_down``) are scaled by (2 x depth)^-1/2 besides (``moe.makers``).
    The mixer's own leaves are Mamba-2's: ``a_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of ``exp(U(log 1e-3, log 1e-1))``,
    ``d_skip`` 1. The embedding is the head too (tied): one array, no
    ``lm_head``; the router has no bias.

    Two choices keep a seeded model with a TIED head from answering with
    its own input, so that a comparison of served tokens compares
    something (both read on the chip, PR 54; neither costs the program
    anything). The stream starts as 12 x the token's own row of the
    matrix the head multiplies by, and ten layers of seeded weights
    scaled for a depth of 40 add about 0.11 to it. (1) The final norm's
    scale is drawn around 1 with a random SIGN a channel: with a
    positive scale every position's largest logit is its own input
    token's (the row's squares all add: 21 against a spread of 0.33 with
    a unit stream) and a served stream repeats its first token. (2) The
    embedding is drawn normal / (16 x ``embedding_multiplier``), so that
    the stream starts at 1 / 16 and what the layers add outweighs it:
    at a unit start the next token is a SYMMETRIC form of the last one
    (``E[x] D E[v]``), greedy decoding climbs it into a cycle of two
    tokens within five steps (2 distinct tokens in a stream's last 32,
    four streams of four), and at a quarter still; at a sixteenth 94-96
    of a stream's 96 tokens are distinct."""
    cdt = cfg.compute_dtype
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32 * (cfg.n_layers + 1)))
    mat, around_one = moe.makers(cfg, keys)
    f32 = jnp.float32

    def mamba():
        dt = jnp.exp(jax.random.uniform(
            next(keys), (cfg.ssm_heads,), f32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "w_in": mat(d, cfg.inner + cfg.conv_width + cfg.ssm_heads),
            "conv": moe.draw(next(keys), (cfg.conv_kernel, cfg.conv_width),
                             cfg.conv_kernel ** -0.5, cdt),
            "conv_bias": 0.1 * jax.random.normal(
                next(keys), (cfg.conv_width,), f32),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (cfg.ssm_heads,), f32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "d_skip": jnp.ones((cfg.ssm_heads,), f32),
            "y_norm": around_one(cfg.inner),
            "w_out": mat(cfg.inner, d, out=True),
        }

    def gqa():
        return {"w_qkv": mat(d, (h + 2 * cfg.n_kv_heads) * hd),
                "wo": mat(h * hd, d, out=True)}

    layers = [{
        "attn_norm": around_one(d),
        "attn": gqa() if cfg.full(i) else mamba(),
        "mlp_norm": around_one(d), "mlp": moe.init_experts(cfg, mat, keys),
    } for i in range(cfg.n_layers)]
    sign = jnp.where(jax.random.bernoulli(next(keys), 0.5, (d,)), 1.0, -1.0)
    return {"embed": moe.draw(next(keys), (cfg.vocab_size, d),
                              1.0 / (16 * cfg.embedding_multiplier), cdt),
            "layers": layers, "final_norm": around_one(d) * sign}


# --------------------------------------------------------------------------
# Mamba-2
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _ssm_inputs(cfg, p, x, conv_rows):
    """What both forms of the mixer start from (``cfg``: this block's
    configuration or ``models/nemotron.py``'s, which has the fields
    read here). x [B, T, D] (normed); ``conv_rows`` [B, K-1, inner + 2 G
    N]: the ``xBC`` rows before x's first. -> (z [B, T, inner]: the
    gate's input, xs [B, T, H, P], dt [B, T, H] after its softplus, b, c
    [B, T, G, N]: each group's rows (this block's one group under the
    group axis that ``ops/ssd_step.py`` and ``ops/ssd_chunk.py`` take),
    all float32, the ``xBC`` rows [B, K-1+T, inner + 2 G N] whose tail
    is the next ``conv_rows``)."""
    b, t, _ = x.shape
    f32 = jnp.float32
    inner, n, g = cfg.inner, cfg.ssm_state, cfg.ssm_groups
    proj = x @ p["w_in"]
    z = proj[..., :inner].astype(f32)
    u = jnp.concatenate(
        [conv_rows, proj[..., inner:inner + cfg.conv_width]], axis=1)
    w = p["conv"].astype(f32)
    xbc = jax.nn.silu(sum(w[i] * u[:, i:i + t].astype(f32)
                          for i in range(cfg.conv_kernel)) + p["conv_bias"])
    dt = jax.nn.softplus(
        proj[..., inner + cfg.conv_width:].astype(f32) + p["dt_bias"])
    xs = xbc[..., :inner].reshape(b, t, cfg.ssm_heads, cfg.ssm_head_dim)
    return (z, xs, dt, xbc[..., inner:inner + g * n].reshape(b, t, g, n),
            xbc[..., inner + g * n:].reshape(b, t, g, n), u)


@jax.named_scope("attn_out")
def _ssm_out(cfg, p, y, xs, z):
    """y, xs [B, T, H, P] float32, z [B, T, inner] -> [B, T, D]: the
    skip ``D x``, the gate, the norm over each GROUP's channels (the
    gate before it; this block's one group: over the whole inner width)
    and ``W_out``."""
    b, t = y.shape[:2]
    g = cfg.ssm_groups
    y = (y + p["d_skip"][:, None] * xs).reshape(b, t, -1) * jax.nn.silu(z)
    y = rms_norm(y.reshape(b, t, g, -1), p["y_norm"].reshape(g, -1),
                 cfg.rms_eps).reshape(b, t, -1)
    return y.astype(cfg.compute_dtype) @ p["w_out"]


def ssm_empty(cfg, b: int) -> dict:
    """The state of ``b`` streams before their first token: ``h`` in the
    step kernel's layout (``ops.ssd_step.pack``: [B, H / g, N, g * P],
    ``g`` heads side by side in a row of lanes)."""
    g = _ss.lane_heads(cfg.ssm_heads, cfg.ssm_head_dim)
    return {"h": jnp.zeros((b, cfg.ssm_heads // g, cfg.ssm_state,
                            g * cfg.ssm_head_dim), jnp.float32),
            "conv": jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_width),
                              cfg.compute_dtype)}


def ssm_step(cfg, p, x, state, active):
    """A decode step of a Mamba layer. x [B, 1, D] (normed); ``state``
    {"h" [B, H / g, N, g P] float32, "conv" [B, K-1, inner + 2 G N]}. A slot
    that is not ``active`` keeps its state. -> ([B, 1, D], state)."""
    z, xs, dt, b, c, u = _ssm_inputs(cfg, p, x, state["conv"])
    with jax.named_scope("attn/attn_ssm"):
        dt0 = dt[:, 0]
        h, y = _ssd_step(state["h"], xs[:, 0] * dt0[..., None],
                         jnp.exp(-jnp.exp(p["a_log"]) * dt0), b[:, 0],
                         c[:, 0], active)
    with jax.named_scope("cache"):
        new = {"h": h, "conv": jnp.where(active[:, None, None], u[:, 1:],
                                         state["conv"])}
    return _ssm_out(cfg, p, y[:, None], xs, z), new


def ssm_segment(cfg, p, x, state, start, true_lens):
    """A Mamba layer over one segment of whole prompts: rows ``start``
    .. ``start + T - 1`` of x [B, T, D] (normed, right-padded:
    ``true_lens`` [B] rows of each prompt are real), from the ``state``
    the rows before them left ({"h", "conv"}: zeros at a prompt's
    start). A padding row has ``dt`` 0: it decays nothing and adds
    nothing, and the convolution rows kept are the last K-1 REAL ones,
    so the state after a prompt's last segment is the state after its
    last real token. -> ([B, T, D], state)."""
    t = x.shape[1]
    kw = cfg.conv_kernel - 1
    z, xs, dt, b, c, u = _ssm_inputs(cfg, p, x, state["conv"])
    with jax.named_scope("attn/attn_ssm"):
        real = start + jnp.arange(t)[None, :] < true_lens[:, None]  # [B, T]
        dt = jnp.where(real[..., None], dt, 0.0)
        pad = -t % cfg.ssm_chunk  # (a bucket narrower than a chunk: the
        # CPU rehearsal's)
        x_, dt, b, c = (jnp.pad(a, ((0, 0), (0, pad))
                                + ((0, 0),) * (a.ndim - 2)) if pad else a
                        for a in (xs, dt, b, c))
        # (the carried state lies in the step kernel's layout: turned
        # once a segment, 4 MB a prompt and layer)
        y, h = _ssd_chunk(x_, dt, -jnp.exp(p["a_log"]), b, c,
                          _ss.unpack(state["h"], cfg.ssm_head_dim),
                          chunk=cfg.ssm_chunk)
        h = _ss.pack(h)
    with jax.named_scope("cache"):
        # u's row j is position start - (K-1) + j: the last K-1 real
        # rows are j = n .. n + K-2 for n = the real rows in or before
        # this segment; a prompt that ended earlier keeps what it had
        n = jnp.clip(true_lens - start, 0, t)
        rows = n[:, None] + jnp.arange(kw)[None, :]
        conv = jnp.take_along_axis(u, rows[..., None], axis=1)
    return _ssm_out(cfg, p, y[:, :t], xs, z), {"h": h, "conv": conv}


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _qkv(cfg: GraniteConfig, p, x):
    """x [B, T, D] (normed) -> (q [B, T, Hq, hd], k, v [B, T, Hkv, hd]):
    one product; no norm, no rotation. q carries ``attention_multiplier
    * sqrt(head_dim)``, so that the kernels' ``head_dim^-1/2`` leaves
    the published scale; it is rounded to the compute type once, after
    the factor."""
    b, t, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    qkv = jnp.dot(x, p["w_qkv"], preferred_element_type=jnp.float32)
    q = qkv[..., :hq * hd] * (cfg.attention_multiplier * hd ** 0.5)
    return (q.astype(cdt).reshape(b, t, hq, hd),
            qkv[..., hq * hd:(hq + hkv) * hd].astype(cdt).reshape(
                b, t, hkv, hd),
            qkv[..., (hq + hkv) * hd:].astype(cdt).reshape(b, t, hkv, hd))


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def _embed(cfg: GraniteConfig, params, tokens):
    with jax.named_scope("embed"):
        return (params["embed"][tokens].astype(jnp.float32)
                * cfg.embedding_multiplier).astype(cfg.compute_dtype)


def _add(cfg: GraniteConfig, h, y):
    """A sublayer's output onto the stream, times the model's residual
    multiplier."""
    return h + (y.astype(jnp.float32) * cfg.residual_multiplier).astype(
        h.dtype)


def _experts(cfg: GraniteConfig, p, h, aux: dict | None = None):
    """A layer's expert half with its norm, added to ``h`` [B, T, D]
    (``moe.mlp_layer`` with the residual multiplier)."""
    with jax.named_scope("moe_router"):
        x = rms_norm(h, p["mlp_norm"], cfg.rms_eps)
    y = moe.moe(cfg, p["mlp"], x, aux)
    with jax.named_scope("moe_shared"):
        return _add(cfg, h, y)


@jax.named_scope("lm_head")
def logits(cfg: GraniteConfig, params, h):
    """h [..., D] before the final norm -> float32 logits [..., V]: the
    tied head, divided by ``logits_scaling``."""
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return jax.lax.dot_general(
        h, params["embed"], (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / cfg.logits_scaling


def prefill(params, tokens, true_lens, cfg: GraniteConfig,
            loads: bool = False, live=None, differentiable: bool = False):
    """tokens [B, T] (right-padded, ``true_lens`` [B] real) from empty
    state, the tokenwise parts in segments of ``moe.segment_rows`` rows
    (module docstring) -> (h [B, T, D] before the final norm, the
    streams' state {"ssm": a list of {"h", "conv"} a Mamba layer,
    "k_full", "v_full" [L_full, B, T, Hkv * hd]: the attention layers'
    rows, padding's among them}, and with ``loads`` (the held experts'
    assignments from the real positions [L, count] int32, the expert
    layer's calls that had and that took its compact branch [2]: none
    here, a quarter of the experts is held), else None). ``live`` as
    ``solar.prefill`` takes it: the serving call's ``max(true_lens)``
    leaves the dead segments out; ``None`` runs every segment.
    ``differentiable`` as there too: ``forward`` alone sets it and keeps
    ``ops.attention.attention``; a serving prefill's attention layer
    takes the forward-only ``attend_bucket``."""
    b, t = tokens.shape
    seg = moe.segment_rows(t, cfg.ssm_chunk)
    attend = attention if differentiable else attend_bucket  # (causal)
    h = _embed(cfg, params, tokens)
    ssm, k_rows, v_rows, counts = [], [], [], []

    def experts(p, h_seg, start):
        aux = {} if loads else None
        h_seg = _experts(cfg, p, h_seg, aux)
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
                    h_seg = _add(cfg, h_seg, o_seg.reshape(
                        *o_seg.shape[:2], -1) @ p["attn"]["wo"])
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
                y, state = ssm_segment(cfg, p["attn"], norm(h_seg), state,
                                       start, true_lens)
                with jax.named_scope("attn_out"):
                    h_seg = _add(cfg, h_seg, y)
                h_seg, n = experts(p, h_seg, start)
                return (state, jax.tree_util.tree_map(jnp.add, count, n)), \
                    h_seg

            (state, count), h = moe.in_segments(
                layer, (ssm_empty(cfg, b), _zero_loads(cfg, loads)), h, seg,
                live)
            ssm.append(state)
        counts.append(count)

    def stack(parts):  # (no attention layer: no rows)
        return jnp.stack(parts) if parts else jnp.zeros(
            (0, b, t, cfg.kv_width), cfg.compute_dtype)

    with jax.named_scope("cache"):
        state = {"ssm": ssm, "k_full": stack(k_rows),
                 "v_full": stack(v_rows)}
    return h, state, moe.prefill_counts(counts) if loads else None


def _zero_loads(cfg: GraniteConfig, loads: bool):
    return (jnp.zeros((cfg.held[1],), jnp.int32),
            jnp.zeros((2,), jnp.int32)) if loads else ()


def forward(params, tokens, cfg: GraniteConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    chunked scan and the prompt's attention."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg,
                      differentiable=True)
    return logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: GraniteConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` as :meth:`_Slots.init_state` makes it, without ``pos``. A
    Mamba layer updates its ``h`` and convolution rows
    (``ops.ssd_step``); an attention layer writes its B new rows at
    ``[layer, slot, pos]`` and attends over the slot's ``pos + 1`` rows
    (``ops.decode_attention`` on the stack in place, the kernel's visits
    made here once, before the layers); an inactive slot keeps its state
    and attends over nothing. -> (float32 logits [B, V], the state
    updated, three [L] int32 counters of the ACTIVE slots' routing:
    distinct held experts touched, assignments, assignments to held
    experts)."""
    b = tok.shape[0]
    slots = jnp.arange(b)
    h = _embed(cfg, params, tok)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        plan = _da.visits(lengths, state["k_full"].shape[2])
    kf, vf, ssm = state["k_full"], state["v_full"], list(state["ssm"])
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
            with jax.named_scope("attn_out"):
                y = o.reshape(b, 1, -1) @ p["attn"]["wo"]
        else:
            y, ssm[layer] = ssm_step(cfg, p["attn"], x, ssm[layer], active)
        with jax.named_scope("attn_out"):
            h = _add(cfg, h, y)
        aux = {}
        h = _experts(cfg, p, h, aux)
        counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    state = {"ssm": ssm, "k_full": kf, "v_full": vf}
    return logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(Slots):
    """A recurrent state a Mamba layer, which cannot be cut or rewound
    at a position, beside the attention layers' stacks of rows."""

    F32_LEAVES = ("attn_norm", "mlp_norm", "final_norm", "y_norm", "a_log",
                  "dt_bias", "d_skip", "conv_bias")

    @staticmethod
    def row_kinds(cfg: GraniteConfig) -> dict:
        # (a recurrent layer keeps no rows: 0 of a slot's are live)
        return {"recurrent": (cfg.ssm_layers, 0),
                "full": (cfg.full_layers, None)}

    @staticmethod
    def prefill_segments(cfg: GraniteConfig, bucket: int) -> int:
        return bucket // moe.segment_rows(bucket, cfg.ssm_chunk)

    @staticmethod
    def init_state(cfg: GraniteConfig, slots: int, max_len: int) -> dict:
        cdt = cfg.compute_dtype
        full = (cfg.full_layers, slots, max_len, cfg.kv_width)
        return {
            "ssm": [ssm_empty(cfg, slots) for _ in range(cfg.ssm_layers)],
            "k_full": jnp.zeros(full, cdt), "v_full": jnp.zeros(full, cdt),
            "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k_full"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        def size(a):  # (by shape: the state may be described only)
            return a.size * a.dtype.itemsize

        return {"recurrent": sum(size(a) for st in state["ssm"]
                                 for a in st.values()),
                "full": size(state["k_full"]) + size(state["v_full"])}

    @staticmethod
    def step(cfg: GraniteConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: GraniteConfig, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state (a reused slot starts from a
        zero ``h`` and zero convolution rows). -> (the streams' state,
        [F] prompt lengths, [F] first tokens, [F] their logprobs, the
        held experts' assignments from the real positions [L, count],
        the expert layer's calls and compact calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, streams, loads = prefill(params, prompts, true_lens, cfg,
                                    loads=True, live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        return streams, true_lens, toks0, logp0, *loads

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        """The prefilled streams' state into their slots: a Mamba
        layer's ``h`` and convolution rows replaced whole, an attention
        layer's P rows onto the first P rows of the slot. What the
        slot's last stream wrote behind them stays: no reader looks past
        a slot's own length (``_prefill_batch_into_slots``' docstring)."""
        def rows(all_, new):  # [L, slots, S, C] <- [L, F, P <= S, C]
            return all_.at[:, slots, :new.shape[2]].set(
                new.astype(all_.dtype))

        return {
            "ssm": [{name: st[name].at[slots].set(
                        new[name].astype(st[name].dtype)) for name in st}
                    for st, new in zip(state["ssm"], streams["ssm"])],
            "k_full": rows(state["k_full"], streams["k_full"]),
            "v_full": rows(state["v_full"], streams["v_full"]),
            "pos": state["pos"].at[slots].set(full_lens)}


SLOTS = _Slots
