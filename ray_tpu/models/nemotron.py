"""A hybrid decoder whose layer is ONE sublayer: a Mamba-2 mixer whose B
and C come in groups of heads, an expert layer of two-matrix relu^2
experts beside a shared one, or a GQA attention of two kv heads without
positions, as a pattern string names them. The language model of
NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``) as its
``config.json`` and transformers' ``NemotronH*`` classes give it; the
twelfth block beside ``llama.py``, ``ling.py``, ``exaone.py``,
``instella.py``, ``solar.py``, ``mimo.py``, ``granite.py``, ``dots.py``,
``glm_dsa.py``, ``glm_next.py`` and ``lfm2.py``.

Block ``i`` is ``h <- h + Mix_i(RMSNorm_i(h))`` with ``Mix_i`` by
``pattern[i]`` (``hybrid_override_pattern``): ``M`` a Mamba-2 mixer,
``E`` an expert layer, ``*`` attention. The published string
(``MEMEM*EMEMEM*...EMEMEMEME``: 23 M, 23 E, 6 ``*`` at 5, 12, 19, 26,
33, 42) has NO period: the block reads the string and derives none. No
block pairs a mixer with a feed-forward: what the other blocks count a
layer (``moe_experts_touched``, the expert layer's calls, the states'
list) is counted here by KIND. Each block is its own dict of leaves
(``norm`` and ``mix``) and the programs unroll them. No multiplier
anywhere, an untied head.

- **M** (``NemotronHMamba2Mixer``): ``models/granite.py``'s mixer
  functions with ``ssm_groups`` 8: ``[z | xBC | dt] = n W_in`` (inner
  width = ``ssm_heads`` x ``ssm_head_dim``), ``xBC <- silu(conv_K(xBC)
  + bias)``, ``[x | B | C]`` with B and C ``ssm_groups`` rows of
  ``ssm_state`` each, head j reading group ``j // (heads / groups)``;
  ``dt = softplus(dt + dt_bias)`` unclamped, ``A = -exp(A_log)``; ``H_t
  = exp(dt_t A) H_{t-1} + dt_t x_t B_{g,t}^T``, ``y_t = H_t C_{g,t} + D
  x_t``; ``y <- RMSNorm_group(y * silu(z)) * w``, the norm over each
  group's channels, the gate before it; ``W_out``. A decode step is
  ``ops/ssd_step.py`` on every slot's state in place, a prefill
  ``ops/ssd_chunk.py``.
- **``*``** (``NemotronHAttention``): q of ``n_heads`` x ``head_dim``
  (inner 4,096, not the hidden size), k and v of ``n_kv_heads`` x
  ``head_dim``, no bias, NO rotary and no other position encoding (the
  Mamba layers carry the order), causal softmax over ``q k^T /
  sqrt(head_dim)`` in float32, ``W_o``. A prompt attends through
  ``ops.attention`` (the forward-only flash call on a TPU), a decode
  step through ``ops.decode_attention`` over the slot's rows.
- **E** (``NemotronHMOE``; ``models/moe.py`` on leaves without a
  ``w_gate``, ``moe.init_ungated_experts``): sigmoid scores in
  float32, ``router_bias`` added for the selection only, one group,
  the ``top_k`` best chosen, their unbiased scores renormalised and
  scaled by ``routed_scaling_factor`` (2.5);
  expert e ``W_down,e relu(W_up,e n)^2``, no gate; a shared expert of
  the same form, unweighted. ``held_experts = (first, count)``: the part
  this device computes.

**Prefill runs in row segments** as ``models/granite.py``'s does: a
block's tokenwise work under one ``moe.in_segments`` scan (one plain
call up to ``moe.SEGMENT_ROWS`` rows), a Mamba block's ``H`` and last
three ``xBC`` rows carried; an attention block's q, k and v (bf16) and
one flash call over them are whole. A serving call's scans skip the dead
segments behind its longest prompt.

A slot's state is Granite's two kinds (:data:`SLOTS` subclasses
``granite._Slots``: ``scatter``, ``state_bytes`` and ``max_len`` are
its): for each M block ``h`` float32 in the step kernel's layout and
``conv [slots, K-1, inner + 2 G N]``, for the attention blocks k and v
stacks ``[L_full, slots, max_len, Hkv * hd]``. The prefix cache,
speculative decoding and the prefill workers refuse this model by name
(``rows_state``).

Types as Granite's: matrices in ``dtype`` (bf16), products accumulated
in float32; norm vectors, ``a_log``, ``dt_bias``, ``d_skip``, the
convolution's bias and the router's bias float32; ``dt``, the decay,
router scores, softmax statistics and ``H`` float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ray_tpu.models import granite, moe
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.attention import attend_bucket, attention
from ray_tpu.ops.norms import rms_norm

KINDS = "ME*"
# The seeded weights' (init_params says what for): what the matrices that
# write into the stream are scaled by besides, the mixers' (``w_out``,
# ``wo``) and the expert blocks' (``w_down``, ``shared_down``), as
# multiples of the (2 x depth)^-1/2 of ``moe.makers``
MIXER_WRITES, EXPERT_WRITES = 5.0, 0.25


@dataclasses.dataclass(frozen=True)
class NemotronConfig(moe.HeldExperts):
    vocab_size: int = 131072
    d_model: int = 2688
    # a block a character: "M" Mamba-2, "E" experts, "*" attention; all
    # of them: no period is derived
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2: the inner width is ssm_heads x ssm_head_dim
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    # groups of heads that share a B and a C row and a gated norm (the
    # MIXER's ``n_groups``; the router's groups are ``n_group`` below)
    ssm_groups: int = 8
    conv_kernel: int = 4
    ssm_chunk: int = 256  # rows of a chunk of the prefill's scan
    # mixture of experts: d_ff is ONE expert's width
    d_ff: int = 1856
    shared_d_ff: int = 3712
    n_experts: int = 128
    top_k: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count): the experts this device holds; None = all of them
    held_experts: tuple | None = None
    rms_eps: float = 1e-5
    max_seq_len: int = 3088
    dtype: str = "bfloat16"
    # None: ``ops.attention``'s own choice (flash on a TPU)
    use_flash: bool | None = None
    # the depth the weights are initialised for (init_params); 0 =
    # n_layers. A configuration cut in depth names its model's own.
    published_layers: int = 0

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r} must name one of {KINDS!r} for "
                "each block")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"{self.ssm_groups} groups do not divide {self.ssm_heads} "
                "Mamba heads")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

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

    def stack_index(self, i: int) -> int:
        """Block ``i``'s place among the blocks of its kind."""
        return self.pattern[:i].count(self.pattern[i])

    @property
    def full_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def ssm_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def moe_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def slot_model(self):
        return SLOTS

    @staticmethod
    def tiny(**kw) -> "NemotronConfig":
        """Test-size config: the published string's head and an uneven
        tail, two groups of two heads, a quarter of the experts held,
        heads x head_dim unequal to the hidden size; runs on the CPU."""
        base = dict(
            vocab_size=256, d_model=48, pattern="MEM*EMEME", n_heads=4,
            n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8,
            ssm_state=16, ssm_groups=2, ssm_chunk=8, d_ff=24,
            shared_d_ff=40, n_experts=16, top_k=4, max_seq_len=128,
            dtype="float32")
        base.update(kw)
        return NemotronConfig(**base)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def init_params(cfg: NemotronConfig, key):
    """The tree in the SERVING types (module docstring), leaf by leaf in
    blocks (``moe.draw``). Matrices are normal / sqrt(fan_in), and those
    that write into the residual stream (``w_out``, ``wo``, ``w_down``,
    ``shared_down``) are scaled by (2 x depth)^-1/2 besides
    (``moe.makers``: the published ``rescale_prenorm_residual``). The
    mixer's own leaves are Mamba-2's, as ``granite.init_params`` draws
    them: ``a_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))`` floored at 1e-4 (the published
    ``time_step_min`` / ``_max`` / ``_floor``), ``d_skip`` 1. The
    embedding and the head are two matrices (untied); an expert has no
    ``w_gate`` and its ``w_up`` lies ``[count, F, D]``
    (``moe.init_ungated_experts``).

    **What the mixers write, against what the expert blocks write**
    (:data:`MIXER_WRITES`, :data:`EXPERT_WRITES`: a rule of this
    initialisation, as ``lfm2.init_params`` has one, no field of the
    configuration). With ``makers``' scale alone, (2 x 52)^-1/2 = 0.098
    on every writer, the served-token check compared rounding with
    rounding: at the harness's own probe (127 tokens in, 24 served, 32
    prompts a seed; my chip runs, PR 70, ``PERF.md`` section 6) the
    bf16 program gave up 0.0008 a token in the median and 0.0149 at
    most where the same program with matrices of 3 mantissa bits gave
    up 0.0145 in the median: no limit stands between the two. What
    bf16 rounds off the 52-block STREAM is the same whatever the
    blocks write, and a router's near-tie that flips one of the six
    experts moves a logit by a whole expert's output x 2.5 / 6; what a
    rounded WEIGHT shows grows with what its block writes. So the
    mixers' writers (``w_out``, ``wo``) are scaled by 5 besides (0.49
    at the published depth) and the expert blocks' (``w_down``,
    ``shared_down``) by 0.25 (0.0245), never above the matrix's own
    normal / sqrt(fan_in) (a test's depth of 1 keeps its mixers at 1):
    over 64 probes the program then gives up 0.0052 a token at most and
    parts up to a gap of 0.074, the 3-bit control 0.016 at least (0.048
    in the median) and parts up to 0.26 in the median. Greedy streams
    emit 29-32 distinct tokens in their last 32 at either rule."""
    cdt = cfg.compute_dtype
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 16 * (cfg.n_layers + 1)))
    drawn, around_one = moe.makers(cfg, keys)
    f32 = jnp.float32

    def writing(times):
        """``drawn``, with what writes into the stream scaled besides
        (never above the matrix's own normal / sqrt(fan_in))."""
        by = min(times, (2.0 * (cfg.published_layers or cfg.n_layers))
                 ** 0.5)

        def mat(*shape, out=False):
            w = drawn(*shape, out=out)
            return (w * by).astype(w.dtype) if out and by != 1.0 else w

        return mat

    mat, ff = writing(MIXER_WRITES), writing(EXPERT_WRITES)

    def mamba():
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(keys), (cfg.ssm_heads,), f32, jnp.log(1e-3),
            jnp.log(1e-1))), 1e-4)
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

    make = {"M": mamba, "*": gqa,
            "E": lambda: moe.init_ungated_experts(cfg, ff, keys)}
    layers = [{"norm": around_one(d), "mix": make[kind]()}
              for kind in cfg.pattern]
    return moe.init_model(cfg, drawn, around_one, keys, layers)


# --------------------------------------------------------------------------
# The three sublayers
# --------------------------------------------------------------------------

@jax.named_scope("qkv")
def _norm(cfg: NemotronConfig, p, h):
    return rms_norm(h, p["norm"], cfg.rms_eps)


@jax.named_scope("qkv")
def _qkv(cfg: NemotronConfig, p, x):
    """x [B, T, D] (normed) -> (q [B, T, Hq, hd], k, v [B, T, Hkv, hd]):
    one product; no norm, no rotation, no bias."""
    b, t, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = x @ p["w_qkv"]
    return (qkv[..., :hq * hd].reshape(b, t, hq, hd),
            qkv[..., hq * hd:(hq + hkv) * hd].reshape(b, t, hkv, hd),
            qkv[..., (hq + hkv) * hd:].reshape(b, t, hkv, hd))


def _experts(cfg: NemotronConfig, p, h, aux: dict | None = None):
    """An expert block with its norm, added to ``h`` [B, T, D]."""
    with jax.named_scope("moe_router"):
        x = rms_norm(h, p["norm"], cfg.rms_eps)
    y = moe.moe(cfg, p["mix"], x, aux)
    with jax.named_scope("moe_shared"):
        return h + y


def _embed(params, tokens):
    with jax.named_scope("embed"):
        return params["embed"][tokens]


logits = moe.logits


# --------------------------------------------------------------------------
# The model: whole sequences, prefill into a slot's state, a ragged step
# --------------------------------------------------------------------------

def prefill(params, tokens, true_lens, cfg: NemotronConfig,
            loads: bool = False, live=None, differentiable: bool = False):
    """tokens [B, T] (right-padded, ``true_lens`` [B] real) from empty
    state, the tokenwise parts in segments of ``moe.segment_rows`` rows
    (module docstring) -> (h [B, T, D] before the final norm, the
    streams' state {"ssm": a list of {"h", "conv"} an M block, "k_full",
    "v_full" [L_full, B, T, Hkv * hd]: the attention blocks' rows,
    padding's among them}, and with ``loads`` (the held experts'
    assignments from the real positions [L_moe, count] int32, the expert
    layer's calls that had and that took its compact branch [2]), else
    None). ``live`` and ``differentiable`` as ``granite.prefill`` takes
    them."""
    b, t = tokens.shape
    seg = moe.segment_rows(t, cfg.ssm_chunk)
    attend = attention if differentiable else attend_bucket  # (causal)
    h = _embed(params, tokens)
    ssm, k_rows, v_rows, counts = [], [], [], []
    for kind, p in zip(cfg.pattern, params["layers"]):
        if kind == "M":
            def mixer(state, xs, p=p):
                start, h_seg = xs
                y, state = granite.ssm_segment(
                    cfg, p["mix"], _norm(cfg, p, h_seg), state, start,
                    true_lens)
                with jax.named_scope("attn_out"):
                    return state, h_seg + y

            state, h = moe.in_segments(mixer, granite.ssm_empty(cfg, b), h,
                                       seg, live)
            ssm.append(state)
        elif kind == "*":
            def project(_, xs, p=p):
                return (), _qkv(cfg, p["mix"], _norm(cfg, p, xs[1]))

            _, (q, k, v) = moe.in_segments(project, (), h, seg, live)
            with jax.named_scope("attn/attn_full"):
                o = attend(q, k, v, use_flash=cfg.use_flash)
            with jax.named_scope("cache"):
                k_rows.append(k.reshape(b, t, -1))
                v_rows.append(v.reshape(b, t, -1))

            def rest(_, xs, p=p):
                h_seg, o_seg = xs[1]
                with jax.named_scope("attn_out"):
                    return (), h_seg + o_seg.reshape(
                        *o_seg.shape[:2], -1) @ p["mix"]["wo"]

            _, h = moe.in_segments(rest, (), (h, o), seg, live)
        else:
            def experts(count, xs, p=p):
                start, h_seg = xs
                aux = {} if loads else None
                h_seg = _experts(cfg, p, h_seg, aux)
                if loads:
                    count = jax.tree_util.tree_map(jnp.add, count, (
                        moe.prefill_loads(cfg, aux["expert_ids"][None],
                                          true_lens - start)[0],
                        moe.compact_calls([aux])))
                return count, h_seg

            zero = (jnp.zeros((cfg.held[1],), jnp.int32),
                    jnp.zeros((2,), jnp.int32)) if loads else ()
            count, h = moe.in_segments(experts, zero, h, seg, live)
            counts.append(count)

    def stack(parts):  # (no attention block: no rows)
        return jnp.stack(parts) if parts else jnp.zeros(
            (0, b, t, cfg.kv_width), cfg.compute_dtype)

    with jax.named_scope("cache"):
        state = {"ssm": ssm, "k_full": stack(k_rows),
                 "v_full": stack(v_rows)}
    return h, state, moe.prefill_counts(counts) if loads and counts else None


def forward(params, tokens, cfg: NemotronConfig):
    """tokens [B, T] -> float32 logits [B, T, V]: whole sequences, the
    chunked scan and the prompt's attention."""
    b, t = tokens.shape
    h, _, _ = prefill(params, tokens, jnp.full((b,), t, jnp.int32), cfg,
                      differentiable=True)
    return logits(cfg, params, h)


loss_fn = moe.loss_fn(forward)


def step(cfg: NemotronConfig, params, tok, state, pos, active):
    """One token a slot at PER-SLOT positions. tok, pos, active [B];
    ``state`` as :meth:`_Slots.init_state` makes it, without ``pos``. An
    M block updates its ``h`` and convolution rows (``ops.ssd_step``);
    an attention block writes its B new rows at ``[its place, slot,
    pos]`` and attends over the slot's ``pos + 1`` rows
    (``ops.decode_attention`` on the stack in place, the kernel's visits
    made here once, before the blocks); an inactive slot keeps its state
    and attends over nothing. -> (float32 logits [B, V], the state
    updated, three [L_moe] int32 counters of the ACTIVE slots' routing:
    distinct held experts touched, assignments, assignments to held
    experts)."""
    b = tok.shape[0]
    slots = jnp.arange(b)
    h = _embed(params, tok)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        lengths = jnp.where(active, pos + 1, 0).astype(jnp.int32)
        plan = _da.visits(lengths, state["k_full"].shape[2])
    kf, vf, ssm = state["k_full"], state["v_full"], list(state["ssm"])
    counts = []
    for i, (kind, p) in enumerate(zip(cfg.pattern, params["layers"])):
        place = cfg.stack_index(i)
        if kind == "E":
            aux = {}
            h = _experts(cfg, p, h, aux)
            counts.append(moe.routing_counts(cfg, aux["expert_ids"], active))
            continue
        x = _norm(cfg, p, h)
        if kind == "M":
            y, ssm[place] = granite.ssm_step(cfg, p["mix"], x, ssm[place],
                                             active)
        else:
            q, k, v = _qkv(cfg, p["mix"], x)
            with jax.named_scope("cache"):
                kf = kf.at[place, slots, pos].set(k.reshape(b, -1))
                vf = vf.at[place, slots, pos].set(v.reshape(b, -1))
            with jax.named_scope("attn/attn_full"):
                o = _da.decode_attention(q, kf, vf, place, lengths,
                                         plan=plan)
            with jax.named_scope("attn_out"):
                y = o.reshape(b, 1, -1) @ p["mix"]["wo"]
        with jax.named_scope("attn_out"):
            h = h + y
    counters = tuple(jnp.stack(c) for c in zip(*counts))
    state = {"ssm": ssm, "k_full": kf, "v_full": vf}
    return logits(cfg, params, h)[:, 0], state, *counters


# --------------------------------------------------------------------------
# The serving engine's half (the protocol: models/slots.py)
# --------------------------------------------------------------------------

class _Slots(granite._Slots):
    """Granite's two kinds of state (``init_state``, ``scatter``,
    ``state_bytes``, ``max_len``, ``row_kinds`` and ``prefill_segments``
    are its: they read the fields both configurations have), with this
    block's own leaves, step and prefill."""

    F32_LEAVES = ("norm", "final_norm", "y_norm", "a_log", "dt_bias",
                  "d_skip", "conv_bias", "router_bias")

    @staticmethod
    def step(cfg: NemotronConfig, params, prepared, tok, state, pos, active):
        return step(cfg, params, tok, state, pos, active)

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps,
                cfg: NemotronConfig, slot_len: int, prefix=None):
        """Whole prompts from EMPTY state (a reused slot starts from a
        zero ``h`` and zero convolution rows). -> (the streams' state,
        [F] prompt lengths, [F] first tokens, [F] their logprobs, the
        held experts' assignments from the real positions [L_moe,
        count], the expert layer's calls and compact calls [2])."""
        Slots.refuse_prefix(cfg, prefix)
        h, streams, loads = prefill(params, prompts, true_lens, cfg,
                                    loads=cfg.moe_layers > 0,
                                    live=jnp.max(true_lens))
        toks0, logp0 = Slots.first_token(
            functools.partial(logits, cfg), params, h, true_lens,
            seeds, temps, top_ps)
        return streams, true_lens, toks0, logp0, *(loads or ())


SLOTS = _Slots
