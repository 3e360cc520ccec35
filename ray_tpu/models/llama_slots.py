"""The Llama block's half of the serving engine (``models/slots.py`` is
the protocol): a slot's state is rows of k and v, the stacked ragged
cache, and this is its format, its one model step and its one prefill.

The state is rows of positions that can be cut, copied and rewound at
any position, so this block alone is served by the three mechanisms that
need that (``decode_engine.decode_chunk_spec``, ``prefill_kv``,
``_adopt_kv_into_slot`` and the prefix cache), which import what they
use from here. Found from the configuration
(``LlamaConfig.slot_model``), as every block's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.slots import Slots
from ray_tpu.ops import decode_attention as _da
from ray_tpu.ops.sampling import sample_from_logits


def init_ragged_cache(cfg: LlamaConfig, slots: int, max_len: int) -> dict:
    """The Llama block's slot state: k and v stacks [L, slots, max_len,
    Hkv * hd] in the compute dtype, a row the position's kv heads laid
    end to end (the layout ``ops/decode_attention.py`` reads in place:
    a head is whole lanes of a block of rows), and each slot's filled
    length."""
    shape = (cfg.n_layers, slots, max_len, cfg.n_kv_heads * cfg.head_dim)
    cdt = cfg.compute_dtype
    return {
        "k": jnp.zeros(shape, cdt),
        "v": jnp.zeros(shape, cdt),
        "pos": jnp.zeros((slots,), jnp.int32),  # per-slot filled length
    }


def _kv_rows(rows):
    """k or v rows [..., Hkv, hd] as the stack holds them:
    [..., Hkv * hd]."""
    return rows.reshape(*rows.shape[:-2], -1)


def _layer_ragged(cfg: LlamaConfig, h, p, sin, cos, k, v, layer, pos,
                  lengths, plan, aux: dict | None = None):
    """One layer over T rows a slot at PER-SLOT positions, on the STACKED
    cache. h: [B, T, D] (T == 1: a decode step; T == K+1: the
    speculative verify, the current token plus the K drafted ones);
    k/v: [L, B, S, Hkv * hd], the whole cache; pos: [B], each slot's
    base position; lengths: [B], the rows of a slot that hold something
    once this layer's are written (pos + T; 0: the slot is inactive),
    and ``plan`` the kernel's visits for them (made once a step).
    The layer writes its B x T new rows at [layer, slot, pos..pos+T-1]
    into the stack it was given (a scatter of rows: nothing else of the
    cache moves) and attends over the stack's ``layer`` in place, each
    slot up to its own length with a per-query causal mask
    (``ops.decode_attention``: on a TPU the ``decode_attn`` kernel,
    which reads only blocks that hold a row; elsewhere the XLA body
    over ``stack[layer]``), so a T-wide pass computes exactly T
    sequential one-row steps in one layer sweep. Returns (h, k, v), the
    stacks updated."""
    b, t, _ = h.shape
    q, k_new, v_new = llama._qkv(cfg, p, h, sin, cos)  # [B, T, H*, hd]
    with jax.named_scope("cache"):
        rows = jnp.arange(b)[:, None]
        cols = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        k = k.at[layer, rows, cols].set(_kv_rows(k_new))
        v = v.at[layer, rows, cols].set(_kv_rows(v_new))
    with jax.named_scope("attn"):
        o = _da.decode_attention(q, k, v, layer, lengths, plan=plan)
    return llama._attn_out_and_mlp(cfg, p, h, o, aux), k, v


def _layers_ragged(cfg: LlamaConfig, layers, attach, h, sin, cos, k, v,
                   pos, active=None):
    """The one layer loop of the chunk programs, and how the cache
    travels through it: the stacked k and v are loop STATE beside h and
    the layer's index, and only the layer parameters (``layers``, as
    ``llama.split_layers`` gives them with ``attach``) are scanned. As a
    scan's xs and ys each layer's [B, S, Hkv, hd] would be sliced out of
    the stack and written back whole around B new rows (two copies a
    layer and step); as state the stack stays where it lies
    (:func:`_layer_ragged`). The loop runs as many layers as ``layers``
    holds (the draft's: the first few). With ``active`` [B], an inactive
    slot's rows are not attended over (its length is 0, its attention
    output zeros), and a model that reports its routing also returns
    ``experts_touched`` [L] (see ``_experts_touched``). Returns
    (h, k, v, *touched)."""
    routed = active is not None and llama.reports_routing(cfg)
    # the same for every layer of the step: made here, not in the body
    with jax.named_scope("attn"):
        lengths = pos + h.shape[1]
        if active is not None:
            lengths = jnp.where(active, lengths, 0)
        plan = _da.visits(lengths, k.shape[2])

    def body(carry, p_):
        h_, k_, v_, layer = carry
        aux = {} if routed else None
        h_, k_, v_ = _layer_ragged(
            cfg, h_, attach(p_), sin, cos, k_, v_, layer, pos, lengths,
            plan, aux)
        return (h_, k_, v_, layer + 1), _experts_touched(cfg, aux, active)

    (h, k, v, _), touched = jax.lax.scan(
        body, (h, k, v, jnp.int32(0)), layers)
    return h, k, v, *touched


@jax.named_scope("moe_router")
def _experts_touched(cfg: LlamaConfig, aux: dict | None, active) -> tuple:
    """What a layer adds to its scan's outputs for the routing counters:
    ``()`` for a model that reports no routing (its program is the one
    it was), else the number of distinct experts that got a row from an
    ACTIVE slot in this layer (an int32 scalar)."""
    if aux is None:
        return ()
    hit = jax.nn.one_hot(aux["expert_ids"], cfg.n_experts, dtype=jnp.bool_)
    hit = hit & active[:, None, None, None]  # ids are [B, T, top_k]
    return (jnp.sum(jnp.any(hit, axis=(0, 1, 2)), dtype=jnp.int32),)


def _split_model(cfg: LlamaConfig, params):
    """What a chunk program prepares once: the layers as the layer loop
    scans them (``llama.split_layers``: (layers, attach)) and the
    unembedding in the compute dtype (as it lies in the engine's serving
    tree; a program handed f32 masters casts it here)."""
    w_out = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cfg.compute_dtype)
    return *llama.split_layers(cfg, params["layers"]), w_out


def _step_logits(cfg: LlamaConfig, params, layers, attach, w_out, toks, k,
                 v, pos, qpos, active=None, before_norm=None):
    """The one model step of the chunk programs: T tokens a slot through
    ``layers`` (all of them, or the draft's first few) on the stacked
    cache. toks: [B, T] at positions qpos [B, T], where
    qpos[:, 0] == pos [B], the slots' base positions; ``before_norm``
    is applied between the layers and the final norm (the draft's
    adapter head). Returns (float32 logits [B, T, V], k, v, *touched):
    see :func:`_layers_ragged` for the stacks and ``active``."""
    with jax.named_scope("qkv"):
        sin, cos = llama.rotary_embedding(qpos, cfg.head_dim,
                                          cfg.rope_theta)
    with jax.named_scope("embed"):
        h = params["embed"].astype(cfg.compute_dtype)[toks]  # [B, T, D]
    h, k, v, *touched = _layers_ragged(
        cfg, layers, attach, h, sin, cos, k, v, pos, active)
    with jax.named_scope("lm_head"):
        if before_norm is not None:
            h = before_norm(h)
        h = llama.rms_norm(h, params["final_norm"], cfg.rms_eps)
        return (h @ w_out).astype(jnp.float32), k, v, *touched


def _prefill_core(params, prompts, true_lens, seeds, temps, top_ps,
                  cfg: LlamaConfig, prefix=None):
    """The one prefill: [F, P] RIGHT-padded tokens (one shared bucket P,
    ``true_lens`` [F] of them real). Its keys are the rows the call was
    given plus the rows it makes (``llama.prefill``). ``prefix`` is
    ``None``: the tokens are whole prompts, and the work is the
    bucket's, P rows a layer whatever the slot's length (attention over
    the prompt's own rows, the flash kernel on a TPU: one block up to
    its 1,024 rows, a wider bucket whole blocks, or ``attention`` raises
    when the program is traced); or ``(k, v, n_prefix)``: rows
    [L, F, S, Hkv, D] filled up to the scalar ``n_prefix`` (the prefix
    cache's), behind which the tokens (the prompts' suffixes) are
    written. The final norm and the head see the TRUE last prompt
    position alone, and the first token comes from it on the (seed,
    position) lane of the chunk programs (seeds/temps/top_ps [F];
    temperature 0 = greedy), so a failover replay reproduces it
    whichever prefill path (inline, suffix, disaggregated) the
    replacement replica takes. Returns (k, v [L, F, P or S, Hkv * D]:
    rows as the stack holds them, [F] whole prompt lengths, [F] first
    tokens, [F] their logprobs) and, for a model that reports its
    routing, ``expert_tokens`` [L, E].

    Right-padding is safe without a pad mask: causal attention means
    real tokens (a prefix) never see the pad garbage, and each later
    decode step overwrites a pad cache row at its position before the
    growing per-slot mask can expose it."""
    if prefix is None:
        given, full_lens = None, true_lens
    else:
        k, v, n_prefix = prefix
        given = (_kv_rows(k), _kv_rows(v), n_prefix)
        full_lens = n_prefix + true_lens
    aux = {}
    last_logits, k, v = llama.prefill(
        params, prompts, true_lens - 1, cfg, given, aux)
    toks0, logp0 = sample_from_logits(
        last_logits, seeds, full_lens - 1, temps, top_ps)
    return (k, v, full_lens, toks0, logp0,
            *_expert_tokens(cfg, aux, true_lens))


@jax.named_scope("moe_router")
def _expert_tokens(cfg: LlamaConfig, aux: dict, true_lens) -> tuple:
    """``()`` for a model that reports no routing, else ([L, E] int32,):
    the assignments each expert got in each layer from the REAL
    positions of the prompts (``true_lens`` masks the bucket's padding)."""
    if "expert_ids" not in aux:
        return ()
    ids = aux["expert_ids"]  # [L, F, P, top_k]
    real = jnp.arange(ids.shape[2])[None, :] < true_lens[:, None]  # [F, P]
    hit = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.int32)
    return (jnp.sum(hit * real[None, :, :, None, None], axis=(1, 2, 3)),)


class _LlamaSlots(Slots):
    """Rows of k and v (:func:`init_ragged_cache`); the step counts the
    distinct experts touched (``_experts_touched``) and the prefill each
    expert's assignments (``_expert_tokens``), over all experts: this
    block holds every one."""

    rows_state = True
    step_counters = ("experts_touched",)
    F32_LEAVES = llama._F32_LEAVES
    reports_routing = staticmethod(llama.reports_routing)
    init_state = staticmethod(init_ragged_cache)
    split = staticmethod(_split_model)

    @staticmethod
    def max_len(state: dict) -> int:
        return state["k"].shape[2]

    @staticmethod
    def state_bytes(state: dict) -> dict:
        return {"kv": state["k"].nbytes + state["v"].nbytes}

    @staticmethod
    def step(cfg, params, prepared, tok, state, pos, active):
        logits, k, v, *touched = _step_logits(
            cfg, params, *prepared, tok[:, None], state["k"], state["v"],
            pos, pos[:, None], active)
        return logits[:, 0], {"k": k, "v": v}, *touched

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps, cfg,
                slot_len, prefix=None):
        k, v, *rest = _prefill_core(
            params, prompts, true_lens, seeds, temps, top_ps, cfg, prefix)
        return {"k": k, "v": v}, *rest

    @staticmethod
    def scatter(state: dict, slots, streams: dict, full_lens) -> dict:
        # k/v: [L, F, R, Hkv * D], the R rows the prefill made, onto the
        # first R rows of their slots
        rows = streams["k"].shape[2]
        return {
            "k": state["k"].at[:, slots, :rows].set(streams["k"]),
            "v": state["v"].at[:, slots, :rows].set(streams["v"]),
            "pos": state["pos"].at[slots].set(full_lens),
        }


SLOTS = _LlamaSlots
