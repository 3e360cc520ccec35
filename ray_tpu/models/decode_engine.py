"""Chunked continuous-batching decode engine (ragged KV cache).

The reference serves LLMs through vLLM-style external engines (its Serve
LLM examples, release_tests.yaml OPT-30B inference); this is the
framework-native TPU equivalent: a fixed SLOT batch over a static-shape
ragged cache — per-slot positions ([B] int32, unlike llama.py's
scalar-pos cache, so every slot decodes at its own offset — new streams
admit into free slots the moment one finishes, instead of waiting for
the whole batch (static batching's tail waste).

TPU-shaped: decoding advances in CHUNKS of `chunk_tokens` steps inside
one jit (lax.scan), so the host's dispatch and the device-to-host sync
are paid once per chunk, not per token. Admission happens at chunk
boundaries — continuous batching at chunk granularity.
Prefill runs per stream at a bucketed prompt length (one compile per
bucket) into a temp slot-1 cache, then scatters into the slot's rows.

What a slot HOLDS is the model's (:func:`slot_model`,
``models/slots.py``): for the Llama block rows of k and v
(``models/llama_slots.py``), for a model with recurrent or latent
layers whatever its own module says. The engine carries that state,
donates it to its two programs and reads ``state["pos"]``; it looks at
nothing else, but in the three mechanisms that need a state of rows
(:func:`require_rows`: :func:`decode_chunk_spec`, :func:`prefill_kv`,
:func:`_adopt_kv_into_slot`), which are the Llama block's alone.
"""

from __future__ import annotations

import collections
import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import accelerator as _acc
from ray_tpu._private import flight_recorder as _fr
from ray_tpu._private import trace as _trace
# (the Llama block's half, for the three mechanisms that need a state of
# rows: decode_chunk_spec, prefill_kv, _adopt_kv_into_slot; nothing else
# here knows a block)
from ray_tpu.models import llama_slots, mlp
from ray_tpu.ops.sampling import sample_from_logits


_metrics = None


def _get_metrics():
    """Lazy Prometheus-style metrics (collective/ring.py idiom): one
    family per engine signal, tagged by engine name."""
    global _metrics
    if _metrics is None:
        from ray_tpu.util import metrics as M

        _metrics = {
            "tbt": M.Histogram(
                "serve_tbt_seconds",
                "per-token time-between-tokens (chunk gap / chunk "
                "tokens, per active stream)",
                boundaries=(0.001, 0.005, 0.02, 0.05, 0.1, 0.25,
                            0.5, 1.0),
                tag_keys=("engine", "tenant")),
            "spec_proposed": M.Counter(
                "decode_engine_spec_proposed_total",
                "draft tokens proposed to the speculative verify step",
                tag_keys=("engine",)),
            "spec_accepted": M.Counter(
                "decode_engine_spec_accepted_total",
                "draft tokens accepted by the speculative verify step",
                tag_keys=("engine",)),
        }
    return _metrics


def slot_model(cfg):
    """The model's half of the engine (``models/slots.py`` is the
    protocol), which every block's configuration carries."""
    return cfg.slot_model


def require_rows(cfg, mechanism: str) -> None:
    """Raise for a model whose slot state is not rows of positions:
    ``mechanism`` (the prefix cache, speculative decoding, a prefill
    worker) cuts, copies or rewinds a state at a position, which a
    recurrent state does not have."""
    if not slot_model(cfg).rows_state:
        raise ValueError(
            f"{mechanism} needs a slot state that is rows of positions; "
            f"{type(cfg).__name__}'s is its own (recurrent or latent "
            "layers, or a ring of rows) and cannot be cut at a position")


@functools.partial(jax.jit, static_argnames=("cfg", "chunk"),
                   donate_argnames=("cache", "tok"))
def decode_chunk(params, cache, tok, active, lanes, cfg, chunk: int):
    """Advance every ACTIVE slot `chunk` tokens inside one jit.

    tok: [B] current token per slot; active: [B] bool. ``lanes`` is
    ``None``, the greedy program (argmax; no per-token sort or
    log-softmax, no logprob output), or the per-slot sampling lanes
    ``(seeds [B] uint32, temps [B], top_ps [B])``: a slot with
    temperature 0 then decodes greedily too, with bit-identical tokens,
    and every token carries its logprob. Inactive slots re-write
    garbage at their frozen pos (invisible: their mask never advances;
    a later prefill overwrites). The donated cache (the model's slot
    state, :func:`slot_model`) is loop state of the step loop and, for
    the Llama block, inside it of the layer loop
    (``llama_slots._layers_ragged``): a step writes B rows a layer into the
    stack and reads, of that layer, each active slot's rows up to its
    own length (``ops.decode_attention``); no layer's cache is sliced
    out or written back, and none is repeated for its query group.
    Returns
    ([B, chunk] tokens, [B, chunk] f32 logprobs or without lanes
    ``None``, new cache, [B] last token) and, for a model that reports
    its routing, its step counters, [chunk, L] each (the Llama block:
    ``experts_touched``, see ``llama_slots._experts_touched``)."""
    model = slot_model(cfg)
    max_len = model.max_len(cache)
    prepared = model.split(cfg, params)

    def one_step(carry, _):
        t, state, pos = carry
        logits, state, *touched = model.step(
            cfg, params, prepared, t, state, pos, active)
        with jax.named_scope("sample"):
            if lanes is None:
                nxt, lp = jnp.argmax(logits, axis=-1).astype(t.dtype), None
            else:
                seeds, temps, top_ps = lanes
                nxt, lp = sample_from_logits(logits, seeds, pos, temps,
                                             top_ps)
            nxt = jnp.where(active, nxt, t)  # frozen slots hold their token
        # clamp: a slot that exhausts its cache rows mid-chunk (pump()
        # only frees slots at chunk boundaries) must keep scattering
        # in-range — unclamped, jit's clamping scatter would write row
        # max_len-1 anyway, but the mask (k_pos <= pos) would open past
        # the cache and pump()'s pos >= max_len-1 finish check stays
        # exact instead of relying on overflow
        pos = jnp.minimum(pos + active.astype(pos.dtype), max_len - 1)
        return (nxt, state, pos), (nxt, lp, touched)

    state = {k: v for k, v in cache.items() if k != "pos"}
    (last, state, pos), (toks, lps, touched) = jax.lax.scan(
        one_step, (tok, state, cache["pos"]), None, length=chunk)
    return (jnp.moveaxis(toks, 0, 1),
            None if lanes is None else jnp.moveaxis(lps, 0, 1),
            {**state, "pos": pos}, last, *touched)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "rounds", "depth",
                                    "draft_layers"),
                   donate_argnames=("cache", "tok"))
def decode_chunk_spec(params, draft_head, cache, tok, active, seeds,
                      temps, top_ps, cfg, rounds: int,
                      depth: int, draft_layers: int):
    """Speculative chunk: `rounds` rounds of (K sequential DRAFT steps +
    ONE K+1-wide VERIFY forward), all inside one jit — one dispatch per
    pump, like `decode_chunk`, but each round can emit up to K+1 tokens
    per slot.

    The draft is the target's own first `draft_layers` layers under the
    target's embedding, final norm and head (a view of the same
    weights) plus an optional residual adapter head,
    mlp.apply_draft_head. Because the trunk layers ARE the target's,
    the draft reads the target's ragged cache rows directly; the k/v
    rows it writes for drafted positions are kept in a private carry
    and DISCARDED — the verify re-writes every layer's rows at
    pos..pos+K itself before attending, so draft state never leaks into
    the persistent cache.

    The verify computes the target's OWN token y_j at every position
    via the same (seed, position) RNG lanes as the non-speculative
    kernels (temperature 0 rows reduce to argmax), accepts draft tokens
    up to the first mismatch with y, and emits the target token at the
    mismatch — so the emitted sequence equals non-speculative decode
    token for token, greedy or sampled, and failover seed-replay is
    exact regardless of which draft lengths were accepted before a
    kill. ROLLBACK is free: each slot's pos advances by its accepted
    count only; rejected rows sit beyond the mask (invisible, like
    inactive-slot garbage) and are overwritten by the next round's
    writes before the mask can reach them.

    Returns (toks [B, rounds, K+1], lps [B, rounds, K+1],
    counts [B, rounds] — tokens emitted per round (0 for inactive
    slots), new cache, [B] last token) and, for a model that reports
    its routing, the verify passes' ``experts_touched`` [rounds, L]."""
    require_rows(cfg, "speculative decoding (decode_chunk_spec)")
    max_len = cache["k"].shape[2]
    b = tok.shape[0]
    t_wide = depth + 1
    rows = jnp.arange(b)
    layers, attach, w_out = llama_slots._split_model(cfg, params)
    # the draft scans the first layers of the same stack
    dlayers = jax.tree_util.tree_map(lambda a: a[:draft_layers], layers)

    def one_round(carry, _):
        t, k, v, pos = carry

        # -- draft: K sequential 1-wide steps over the trunk layers --
        def draft_step(dc, _):
            dt, kd, vd, dpos = dc
            logits, kd, vd = llama_slots._step_logits(
                cfg, params, dlayers, attach, w_out, dt[:, None], kd, vd,
                dpos, dpos[:, None],
                before_norm=lambda h: mlp.apply_draft_head(draft_head, h))
            # the proposal for position dpos+1 rides lane dpos — the
            # SAME lane the verify uses for its token at dpos+1's
            # predecessor, so under sampling the draft and target draw
            # with shared Gumbel noise (agreement is higher than the
            # argmax overlap of their distributions)
            d, _ = sample_from_logits(logits[:, 0], seeds, dpos, temps,
                                      top_ps)
            dpos = jnp.minimum(dpos + 1, max_len - 1)
            return (d, kd, vd, dpos), d

        (_, _, _, _), drafts = jax.lax.scan(
            draft_step,
            (t, k[:draft_layers], v[:draft_layers], pos),
            None, length=depth)
        drafts = jnp.moveaxis(drafts, 0, 1)  # [B, K]

        # -- verify: ONE wide forward over the K+1 positions --
        xs = jnp.concatenate([t[:, None], drafts], axis=1)  # [B, T]
        qpos = pos[:, None] + jnp.arange(t_wide, dtype=jnp.int32)
        logits, k, v, *touched = llama_slots._step_logits(
            cfg, params, layers, attach, w_out, xs, k, v, pos, qpos,
            active)
        y, lp = sample_from_logits(
            logits.reshape(b * t_wide, -1),
            jnp.repeat(seeds, t_wide), qpos.reshape(-1),
            jnp.repeat(temps, t_wide), jnp.repeat(top_ps, t_wide))
        y = y.reshape(b, t_wide)
        lp = lp.reshape(b, t_wide)

        # -- accept until first mismatch; rollback = pos truncation --
        with jax.named_scope("sample"):
            match = (drafts == y[:, :depth]).astype(jnp.int32)
            m = jnp.cumprod(match, axis=1).sum(axis=1) + 1  # [B], 1..K+1
            m = jnp.where(active, m, 0)
            t = jnp.where(active, y[rows, jnp.maximum(m - 1, 0)], t)
        pos = jnp.minimum(pos + m, max_len - 1)
        return (t, k, v, pos), (y, lp, m, *touched)

    (last, k, v, pos), (toks, lps, counts, *touched) = jax.lax.scan(
        one_round, (tok, cache["k"], cache["v"], cache["pos"]),
        None, length=rounds)
    return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(lps, 0, 1),
            jnp.moveaxis(counts, 0, 1), {"k": k, "v": v, "pos": pos},
            last, *touched)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache", "cur_tok"))
def _prefill_batch_into_slots(params, prompts, true_lens, slots,
                              seeds, temps, top_ps,
                              cache, cur_tok, cfg, prefix=None):
    """Prefill streams (the model's ``prefill``; the Llama block's is
    ``llama_slots._prefill_core``) into their slots of the
    shared ragged cache: prefill, k/v scatters, pos and first-token
    updates in ONE dispatch. The engine calls it with F = 1, one prompt
    a call, so one program per bucket: at F = `slots` the padding rows
    were a third to a half of the device's time (PERF.md, PR 25).
    `slots` [F] are in-range slot indices. With ``prefix`` (the
    prefix-cache warm path) ``prompts`` hold the suffixes behind the
    cached rows: row independence + exact softmax masking make the
    result identical to a cold prefill of the whole prompt
    (kv_prefix_cache.py docstring). Returns (new cache, new cur_tok,
    [F] first tokens, [F] first-token logprobs, *expert_tokens).

    NO READER LOOKS PAST A SLOT'S OWN LENGTH: the scatter writes the
    rows the prefill made (a cold call: its bucket's P) and the slot's
    ``pos``, and leaves what the slot's last stream wrote behind them.
    Slot reuse is correct because nothing reads those rows before the
    new stream has written them: a decode step (and a speculative
    verify) writes its rows at ``pos`` before it attends, the attention
    reads a slot up to ``lengths`` = the rows written (``decode_attn``
    takes no block past them and masks inside the last; the XLA body
    ``attend_ragged`` masks by them), an inactive slot's length is 0,
    and a stream ends at ``pos == max_len - 1``, where the clamped write
    lands on the row the mask has just reached
    (``tests/test_serve_llm.py``: a reused slot)."""
    model = slot_model(cfg)
    streams, full_lens, toks0, logp0, *expert_tokens = model.prefill(
        params, prompts, true_lens, seeds, temps, top_ps, cfg,
        model.max_len(cache), prefix)
    with jax.named_scope("cache"):
        cache = model.scatter(cache, slots, streams, full_lens)
        return (cache, cur_tok.at[slots].set(toks0), toks0, logp0,
                *expert_tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "slot_len"))
def prefill_kv(params, prompts, true_lens, seeds, temps, top_ps,
               cfg, slot_len: int):
    """Prefill WITHOUT a slot (a cold ``llama_slots._prefill_core``): the raw
    KV rows, the first tokens and their behavior logprobs
    ((k, v) [L, F, S, Hkv, D], the prompt's rows with zeros behind them
    up to ``slot_len``, the payload ``submit_prefilled`` checks; toks0
    [F], logp0 [F]). This is the
    dedicated prefill worker's op (serve/llm_pool.py): the rows travel
    through the object store and a decode replica adopts them into a
    slot with `RaggedDecoder.submit_prefilled` — the same prefill and
    lane as an inline one, so the adopted stream is bit-identical to an
    inline-prefilled one, greedy or sampled."""
    require_rows(cfg, "disaggregated prefill (prefill_kv)")
    k, v, _, toks0, logp0, *_ = llama_slots._prefill_core(
        params, prompts, true_lens, seeds, temps, top_ps, cfg)

    def payload(rows):  # [L, F, P, Hkv * D] -> [L, F, S, Hkv, D]
        rows = jnp.pad(rows, ((0, 0), (0, 0),
                              (0, slot_len - rows.shape[2]), (0, 0)))
        return rows.reshape(*rows.shape[:3], cfg.n_kv_heads, cfg.head_dim)

    with jax.named_scope("cache"):
        return payload(k), payload(v), toks0, logp0


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache", "cur_tok"))
def _adopt_kv_into_slot(k_rows, v_rows, true_len, tok0, slot, cache,
                        cur_tok, cfg):
    """Scatter externally-prefilled KV rows ([L, S, Hkv, D] as
    ``prefill_kv`` hands them out, S == the slot cache length) into
    `slot` and seed its current token."""
    with jax.named_scope("cache"):
        cache = {
            "k": cache["k"].at[:, slot].set(llama_slots._kv_rows(k_rows)),
            "v": cache["v"].at[:, slot].set(llama_slots._kv_rows(v_rows)),
            "pos": cache["pos"].at[slot].set(true_len),
        }
        return cache, cur_tok.at[slot].set(tok0)


# the engine's programs as a trace's ``XLA Modules`` line names them
_CHUNK = f"jit_{decode_chunk.__name__}"
_CHUNK_SPEC = f"jit_{decode_chunk_spec.__name__}"
_PREFILL = f"jit_{_prefill_batch_into_slots.__name__}"
PREFILL_KV = f"jit_{prefill_kv.__name__}"


def _nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def adopt_weights(cfg, params, version: int):
    """The one way a serving process takes weights in: -> the serving
    tree of ``params`` (the model's ``serving_params``). The cast runs here,
    once an adoption (a replica's start, a weight publish), and is
    waited for, so the ``serve.weights_cast`` span (ring-only) is what
    the adoption cost; the caller keeps the serving tree alone and lets
    go of ``params``."""
    with _fr.span("serve", "serve.weights_cast", flush=False, attrs={
            "version": int(version), "bytes_in": _nbytes(params)}) as sp:
        serving = jax.block_until_ready(
            slot_model(cfg).serving_params(cfg, params))
        sp["bytes_out"] = _nbytes(serving)
    return serving


# What ``engine.compiled`` marks add up to, an engine (``compiled`` in
# :class:`RaggedDecoder`; the replica's ``stats()["setup"]`` carries it)
_COMPILED_SUMS = ("trace_ms", "lower_ms", "compile_ms", "cache_read_ms")
# the calling thread's compile tally: ``_TALLY.n`` read before a jitted
# call and compared after it is all a call that compiled nothing pays
_TALLY = _acc.tally
_CAST = "jit__cast_leaves"  # the slot protocol's ``serving_params``' program


def note_compiled(program: str, bucket: int, mark: int, *, name: str,
                  ready: float | None = None,
                  totals: dict | None = None) -> None:
    """``engine.compiled`` (a flushed mark, so also an instant event on
    the profiler's host line and a row of ``ray_tpu.timeline()``): the
    call of ``program`` that has just returned traced, lowered or
    compiled on this thread (the caller took ``mark`` =
    ``accelerator.compile_mark()`` before it and found it moved; a call
    that compiled nothing never comes here). Attrs: ``program`` as a
    trace's ``XLA Modules`` line names it, ``bucket`` (a prefill's; 0 for
    a chunk), the thread tally's six numbers
    (``accelerator.compile_since``), ``call_ms`` from the call's first
    stage (jax's own stamp) to now, the call being asynchronous, and
    ``since_ready_ms`` (0 before the engine was). ``totals``, where
    given, takes the sums."""
    now, found = time.monotonic(), _acc.compile_since(mark)
    if found is None:
        return
    began = _acc.compile_began(mark)
    call_ms = round(1e3 * (time.time() - began), 3) if began else 0.0
    _fr.mark("serve", "engine.compiled", attrs={
        "engine": name, "program": program, "bucket": int(bucket),
        "call_ms": call_ms, **found,
        "since_ready_ms": round(1e3 * (now - ready), 1) if ready else 0.0})
    if totals is not None:
        totals["first_calls"] += 1
        totals["first_call_ms"] = round(totals["first_call_ms"] + call_ms, 3)
        totals["compile_requests"] += found["requests"]
        totals["cache_hits"] += found["hits"]
        for k in _COMPILED_SUMS:
            totals[k] = round(totals[k] + found[k], 3)
        totals["last_compile_mono_ns"] = int(now * 1e9)


# Birth stamps a request may carry, in the order they are taken, and the
# name of the part between each and the next (the last: engine.submit).
_STAMPS = ("proxy_recv", "pool_enqueue", "pool_admitted")
_STAMP_PARTS = ("proxy_to_pool_ms", "admission_wait_ms",
                "pool_to_replica_ms")


def _upstream_ms(stamps, submitted_wall: float) -> dict:
    """A request's way to ``engine.submit`` split at its birth stamps
    (epoch seconds): each part whose two ends are there, and
    ``upstream_ms`` from the earliest stamp to the submit. ``stamps``
    came with the request: anything that is no number is passed over."""
    if not isinstance(stamps, dict):
        return {}
    pts = [stamps.get(k) for k in _STAMPS] + [submitted_wall]
    pts = [t if isinstance(t, (int, float)) else None for t in pts]
    out = {name: round(1e3 * (b - a), 3)
           for name, a, b in zip(_STAMP_PARTS, pts, pts[1:])
           if a is not None and b is not None}
    first = next((t for t in pts[:-1] if t is not None), None)
    if first is not None:
        out["upstream_ms"] = round(1e3 * (submitted_wall - first), 3)
    return out


def _caller_trace() -> dict | None:
    cur = _trace.current()
    return None if cur is None else {"trace_id": cur[0], "parent": cur[1]}


@dataclass
class _Stream:
    sid: int
    prompt: np.ndarray
    max_new: int
    tokens: list = field(default_factory=list)
    token_times: list = field(default_factory=list)  # monotonic stamps
    submitted: float = 0.0
    admitted_at: float = 0.0  # slot granted, prefill dispatched
    bucket: int = 0  # padded prompt width it was prefilled at (0: none)
    done: bool = False
    taken: int = 0  # tokens already handed out via take_tokens()
    prefilled: dict | None = None  # external KV payload (k/v/first_token)
    # sampling lane (temperature 0 = greedy, the default serving mode)
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    logprobs: list = field(default_factory=list)  # parallel to tokens
    # weight version the stream decodes under — None until admission
    # stamps it (the ENGINE's version, which may lag a pool publish by
    # the staleness window; the pool's splice guard needs the version
    # the tokens were actually generated under, not the publish stamp)
    version: int | None = None
    # tenant for per-tenant SLO attribution (TBT histograms)
    tenant: str = "-"
    # the submitter's trace context ({"trace_id", "parent"}): the pump
    # thread records this stream's spans under it
    trace: dict | None = None
    # birth stamps the request carried (epoch seconds on the recorder's
    # clock: proxy_recv, pool_enqueue, pool_admitted), or None
    stamps: dict | None = None


class RaggedDecoder:
    """The engine: fixed slot batch + chunked continuous batching.

    submit() enqueues; pump() admits queued streams into free slots
    (prefill) and advances one chunk; finished streams free their slots
    immediately — the next queued stream rides the same chunk cadence.
    Thread-unsafe by design: ONE pump owner (the serve replica's loop
    thread) drives it; submit/result queues are the boundary."""

    def __init__(self, params, cfg, *, slots: int = 8,
                 max_len: int = 512, chunk_tokens: int = 32,
                 prompt_buckets: tuple = (32, 64, 128, 256),
                 prefix_cache=None, name: str = "default",
                 chunk_delay_s: float = 0.0, weights_version: int = 0,
                 spec_depth: int = 0, spec_draft_layers: int = 0,
                 spec_draft_head=None):
        # ``cfg`` is the model's own configuration; the model's half of
        # the engine is found from it
        self.model = slot_model(cfg)
        if prefix_cache is not None:
            require_rows(cfg, "the prefix cache (kv_prefix_cache)")
        if int(spec_depth) > 0:
            require_rows(cfg, "speculative decoding (spec_depth > 0)")
        self.name = name
        # sums over this engine's ``engine.compiled`` marks, and when it
        # stood ready (``time.monotonic``; None while it is built)
        self.compiled = {
            "first_calls": 0, **dict.fromkeys(_COMPILED_SUMS, 0.0),
            "first_call_ms": 0.0, "compile_requests": 0, "cache_hits": 0,
            "last_compile_mono_ns": 0}
        self.ready_mono: float | None = None
        # the serving tree (the model's serving_params), the only weights
        # the engine holds: the caller's f32 masters are not kept
        self.params = self._adopt(cfg, params, weights_version)
        # Emulated per-chunk device time for exercising the SERVING
        # tier on hosts without an accelerator: on a TPU each chunk
        # waits on the device, time that overlaps across replicas — a
        # sleep is the CPU stand-in for it, same idiom as the injected
        # per-chunk latency in the pipelined-pull floor test. Numbers
        # taken with it are host-path counts, never device speed; 0 on
        # the chip (ROADMAP Design 3 removes it).
        self.chunk_delay_s = chunk_delay_s
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.chunk = chunk_tokens
        self.buckets = tuple(sorted(prompt_buckets))
        # every slot's state, the model's: carried, donated to the two
        # programs, never looked into (``pos`` apart)
        self.cache = self.model.init_state(cfg, slots, max_len)
        self.state_bytes = self.model.state_bytes(self.cache)
        self.row_kinds = self.model.row_kinds(cfg)
        self.cur_tok = jnp.zeros((slots,), jnp.int32)
        # per-slot sampling lanes, rewritten at admission; frozen slots'
        # values are dead (their sampled token is overwritten anyway)
        self._slot_seed = np.zeros((slots,), np.uint32)
        self._slot_temp = np.zeros((slots,), np.float32)
        self._slot_topp = np.ones((slots,), np.float32)
        # sticky: flips at the first sampled submit and stays — a
        # greedy-only engine (the serving default) hands decode_chunk
        # no lanes (no per-token argsort/log_softmax cost, token
        # logprobs reported as 0.0); after any sampled request the
        # engine pays for exact logprobs on every stream
        self._sampling_seen = False
        # weight-version bookkeeping: bumped by set_params(); streams
        # stamp the version live at their admission
        self.weights_version = int(weights_version)
        self.pumps = 0  # engine steps — staleness windows count these
        # calls of the cold prefill program, one prompt each (a
        # monotonic total)
        self.prefill_calls = 0
        # routing counters of a mixture-of-experts model (monotonic
        # totals; both stay 0 for a model that reports no routing):
        # (token, expert) assignments of the real positions the prefill
        # program ran (whole prompts, and a warm admission's suffix),
        # and experts touched summed over decode steps and layers
        self.moe_assignments = 0
        self.moe_touched_expert_steps = 0
        # rows the chunks' attention had to read against rows the slots
        # hold (monotonic totals, one addition a read-back: _count_rows)
        self.attn_live_rows = 0
        self.attn_cache_rows = 0
        # and, for a model with rows of several kinds, the live rows of
        # one layer of each kind
        self.attn_live_rows_by_kind = dict.fromkeys(self.row_kinds, 0)
        # the device counts of the prefill calls since the last
        # read-back, fetched with it: one tuple a call, ([L, E] loads
        # and, from a block whose expert layer has a compact branch,
        # [2] calls)
        self._pending_expert_tokens: list = []
        self.slot_stream: list[_Stream | None] = [None] * slots
        self.queue: collections.deque[_Stream] = collections.deque()
        self._next_sid = 0
        self.finished: dict[int, _Stream] = {}
        # (stream, device tok0) fetched with the next chunk's device_get
        self._pending_first: list = []
        # sid -> stream for every not-yet-purged stream (streaming reads)
        self._by_sid: dict[int, _Stream] = {}
        self.prefix_cache = prefix_cache  # models.kv_prefix_cache or None
        # speculative decoding (decode_chunk_spec): depth K drafts per
        # verify round; 0 = off. The live config knobs
        # serve_spec_enabled / serve_spec_depth are consulted at every
        # pump (_spec_depth_now) so speculation can be flipped or
        # re-depthed on a running engine — emitted tokens are identical
        # either way, only the pump's token yield changes.
        self.spec_depth = max(0, int(spec_depth))
        ld = int(spec_draft_layers) or max(1, cfg.n_layers // 2)
        self.spec_draft_layers = min(max(ld, 1), cfg.n_layers)
        self.spec_draft_head = spec_draft_head
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_pumps = 0
        # accepted-length histogram: accept_hist[m] = verify rounds (of
        # active slots) that accepted exactly m draft tokens, 0..depth
        self._spec_hist: collections.Counter = collections.Counter()
        self._total_tokens = 0
        # (stamp, n_tokens) per pump for the tokens/s scaling signal
        self._rate_window: collections.deque = collections.deque()
        self.mark_state()
        self.ready_mono = time.monotonic()

    def _adopt(self, cfg, params, version: int):
        """:func:`adopt_weights` for this engine: what the cast took
        (``weights_cast_ms``, the newest adoption's) and, where its
        program was new to the process, an ``engine.compiled``."""
        mark, t0 = _TALLY.n, time.monotonic()
        serving = adopt_weights(cfg, params, version)
        self.weights_cast_ms = round(1e3 * (time.monotonic() - t0), 3)
        if _TALLY.n != mark:
            self._note_compiled(_CAST, 0, mark)
        return serving

    def _note_compiled(self, program: str, bucket: int, mark: int) -> None:
        note_compiled(program, bucket, mark, name=self.name,
                      ready=self.ready_mono, totals=self.compiled)

    def mark_state(self) -> None:
        """``engine.state_init`` (ring-only, and an instant event on the
        profiler's host line): what the slots hold, ``<kind>_bytes`` for
        each kind of state the model keeps and, where its layers keep
        rows of several kinds, ``<kind>_layers`` and ``<kind>_row_bytes``
        (one position's bytes in one layer of a kind that keeps rows:
        the kind's bytes over its layers, slots and rows a slot). Once
        an engine, and again where a trace starts
        (``LLMServer.start_trace``), so that a trace says what engine it
        is of."""
        kinds = {kind: (n, self.max_len if most is None else most)
                 for kind, (n, most) in self.row_kinds.items()}
        _fr.mark("serve", "engine.state_init", flush=False, attrs={
            "engine": self.name, "slots": self.slots,
            "max_len": self.max_len,
            **{f"{kind}_bytes": n for kind, n in self.state_bytes.items()},
            **{f"{kind}_layers": n for kind, (n, _) in kinds.items()},
            **{f"{kind}_row_bytes":
               self.state_bytes[kind] // (n * self.slots * rows)
               for kind, (n, rows) in kinds.items() if n * rows}})

    def program_parts(self) -> dict:
        """{program, as a trace's ``XLA Modules`` line names it: [{"what":
        the call's shapes in words, "parts": {instruction: part}}, one
        entry a signature]}: ``program_parts.parts_of`` the compiled
        text of every program THIS engine has run, at the shapes it ran
        them: the chunk (greedy, and with lanes once a sampled request
        came; the speculative one where speculation is on), the prefill
        call at each of its buckets (cold, and behind a cached prefix
        where there is a prefix cache). A signature that has not run is
        left out, never compiled (``program_parts.compiled_text``). For
        a capture (``LLMServer.stop_trace``); any thread may call it:
        nothing of the engine is written, and of its arrays only shapes
        are read."""
        from ray_tpu.models import program_parts as _pp

        params, cache, tok = self.params, self.cache, self.cur_tok
        if params is None:  # (between the two halves of a weight publish)
            return {}
        mask = np.zeros((self.slots,), bool)
        lanes = tuple(jax.ShapeDtypeStruct((self.slots,), dt)
                      for dt in (jnp.uint32, jnp.float32, jnp.float32))
        calls = [("greedy", decode_chunk, (
            params, cache, tok, mask, None, self.cfg, self.chunk))]
        if self._sampling_seen:
            calls.append(("sampled", decode_chunk, (
                params, cache, tok, mask, lanes, self.cfg, self.chunk)))
        if self.spec_depth:
            calls.append((f"depth {self.spec_depth}", decode_chunk_spec, (
                params, self.spec_draft_head, cache, tok, mask, *lanes,
                self.cfg, self.chunk, self.spec_depth,
                self.spec_draft_layers)))
        one = lambda dt: np.zeros((1,), dt)  # noqa: E731
        prefixes = [("cold", None)]
        if self.prefix_cache is not None:
            rows = jax.ShapeDtypeStruct(
                (self.cfg.n_layers, 1, self.max_len, self.cfg.n_kv_heads,
                 self.cfg.head_dim), self.cfg.compute_dtype)
            prefixes.append(("warm", (rows, rows, np.int32(0))))
        for width in self.buckets:
            for kind, prefix in prefixes:
                calls.append((
                    f"{kind}, bucket {width}", _prefill_batch_into_slots,
                    (params, np.zeros((1, width), np.int32), one(np.int32),
                     one(np.int32), one(np.uint32), one(np.float32),
                     one(np.float32), cache, tok, self.cfg, prefix)))
        out: dict = {}
        for what, jitted, args in calls:
            text = _pp.compiled_text(jitted, *args)
            if text is not None:
                out.setdefault(_pp.program_name(text), []).append(
                    {"what": what, "parts": _pp.parts_of(text)})
        return out

    # -- submission boundary --

    def submit(self, prompt_tokens, max_new: int, *,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, tenant: str = "-",
               stamps: dict | None = None) -> int:
        """Validates HERE (caller's thread) so a bad request raises at
        the submitter, never inside the pump loop. ``temperature`` 0 is
        greedy decode; > 0 samples on the stream's (seed, position)
        RNG lane with nucleus (top-p) filtering. ``stamps``: the
        request's birth stamps, for the first-token span's split."""
        return self._enqueue(prompt_tokens, max_new, None, temperature,
                             top_p, seed, tenant, stamps)

    def submit_prefilled(self, prompt_tokens, max_new: int,
                         kv: dict, *, temperature: float = 0.0,
                         top_p: float = 1.0, seed: int = 0,
                         tenant: str = "-",
                         stamps: dict | None = None) -> int:
        """Enqueue a stream whose prefill already happened elsewhere
        (a dedicated prefill worker, serve/llm_pool.py). `kv`:
        {"k"/"v": [n_layers, S, n_kv_heads, head_dim] with S == this
        engine's max_len, "first_token": int, "true_len": int}.
        Admission is a pure slot scatter — no prefill dispatch."""
        require_rows(self.cfg, "disaggregated prefill (submit_prefilled)")
        k = np.asarray(kv["k"])
        if k.shape[1] != self.max_len:
            raise ValueError(
                f"prefilled KV has {k.shape[1]} rows; this engine's "
                f"slots hold {self.max_len} (prefill and decode pools "
                f"must agree on max_len)")
        if int(kv["true_len"]) != len(prompt_tokens):
            raise ValueError("prefilled true_len != prompt length")
        prefilled = {"k": k, "v": np.asarray(kv["v"]),
                     "first_token": int(kv["first_token"]),
                     "first_logprob": float(kv.get("first_logprob", 0.0))}
        return self._enqueue(prompt_tokens, max_new, prefilled,
                             temperature, top_p, seed, tenant, stamps)

    def _enqueue(self, prompt_tokens, max_new: int, prefilled, temperature,
                 top_p, seed, tenant, stamps) -> int:
        """The one way in: validate, build the stream, queue it."""
        prompt = np.asarray(prompt_tokens, np.int32)
        if prefilled is None:
            self._bucket(len(prompt))  # raises if no bucket fits
        # clamp generation to the slot's cache capacity: past max_len
        # the k/v scatters drop and tokens would come from a silently
        # truncated attention window
        room = self.max_len - len(prompt) - 1
        if room < 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens leaves no decode room "
                f"in a max_len={self.max_len} cache")
        if not 0.0 < float(top_p) <= 1.0:
            # an out-of-range top_p reaching the kernel filters EVERY
            # logit to -inf (NaN logprobs, arbitrary tokens) instead of
            # failing loudly
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if float(temperature) > 0.0:
            self._sampling_seen = True
        s = _Stream(self._next_sid, prompt, min(max_new, room),
                    submitted=time.monotonic(),
                    temperature=float(temperature), top_p=float(top_p),
                    seed=int(seed) & 0xFFFFFFFF, tenant=str(tenant),
                    trace=_caller_trace(), stamps=stamps,
                    prefilled=prefilled)
        self._next_sid += 1
        self.queue.append(s)
        self._by_sid[s.sid] = s
        return s.sid

    def pop_finished(self, sid: int) -> _Stream | None:
        self._by_sid.pop(sid, None)
        return self.finished.pop(sid, None)

    def stream_version(self, sid: int) -> int | None:
        """The weight version `sid`'s tokens are generated under (None
        until admission) — what the serving layer reports so failover
        decisions compare GENERATING versions, not publish stamps."""
        s = self._by_sid.get(sid)
        return None if s is None else s.version

    def purge(self, sid: int) -> None:
        """Drop a finished/abandoned stream's bookkeeping."""
        self._by_sid.pop(sid, None)
        self.finished.pop(sid, None)

    def take_tokens(self, sid: int, *, with_logprobs: bool = False):
        """Streaming read: tokens appended since the last take, plus a
        done flag — ``with_logprobs=True`` adds the parallel per-token
        behavior logprobs ((tokens, logprobs, done) instead of
        (tokens, done)), the RL experience surface. Safe to call from a
        handler thread while the pump appends (list append/slice are
        atomic under the GIL; the pump only ever appends; logprobs are
        appended BEFORE tokens so the parallel slice below never runs
        ahead of them). A fully-drained finished stream is purged on
        the way out."""
        s = self._by_sid.get(sid)
        if s is None:
            return ([], [], True) if with_logprobs else ([], True)
        n = len(s.tokens)
        new = s.tokens[s.taken:n]
        lps = s.logprobs[s.taken:n]
        s.taken = n
        done = s.done and s.sid in self.finished
        if done and s.taken >= len(s.tokens):
            self.purge(sid)
            return (new, lps, True) if with_logprobs else (new, True)
        return (new, lps, False) if with_logprobs else (new, False)

    # -- engine internals --

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds the largest "
                         f"bucket {self.buckets[-1]}")

    def _admit(self):
        free = [i for i, s in enumerate(self.slot_stream) if s is None]
        grabbed: list[tuple[int, _Stream]] = []
        while free and self.queue:
            grabbed.append((free.pop(), self.queue.popleft()))
        if not grabbed:
            return
        n_prefilled = sum(s.prefilled is not None for _, s in grabbed)
        with _fr.span("serve", "engine.admit", flush=False, attrs={
                "admitted": len(grabbed),
                "prefilled": n_prefilled}) as sp:
            n_cold = self._admit_grabbed(grabbed)
            sp["cold"] = n_cold
            sp["warm"] = len(grabbed) - n_prefilled - n_cold

    def _admit_grabbed(self, grabbed) -> int:
        """Put each (slot, stream) on the device by the path it takes:
        adopted KV, prefix-cache warm, or a cold prefill of its own, in
        queue order. Returns how many went cold."""
        cold: list[tuple[int, _Stream]] = []
        t_now = time.monotonic()
        for slot, s in grabbed:
            s.version = self.weights_version
            s.admitted_at = t_now
            self._set_lane(slot, s)
            if s.prefilled is not None:
                # disaggregated path: the KV rows were computed by a
                # prefill worker; admission is one scatter dispatch and
                # the first token is already known host-side
                p = s.prefilled
                self.cache, self.cur_tok = _adopt_kv_into_slot(
                    jnp.asarray(p["k"], self.cfg.compute_dtype),
                    jnp.asarray(p["v"], self.cfg.compute_dtype),
                    np.int32(len(s.prompt)),
                    np.int32(p["first_token"]), np.int32(slot),
                    self.cache, self.cur_tok, self.cfg)
                s.logprobs.append(p.get("first_logprob", 0.0))
                s.tokens.append(p["first_token"])
                s.token_times.append(t_now)
                self._record_first_token(s, t_now)
                s.prefilled = None  # free the host slab
                self.slot_stream[slot] = s
            elif self.prefix_cache is not None and self._admit_warm(
                    slot, s):
                pass  # adopted a cached prefix + suffix prefill
            else:
                cold.append((slot, s))
        for slot, s in cold:
            self._prefill_cold(slot, s)
        if self.prefix_cache is not None:
            self._insert_prefixes(cold)
        return len(cold)

    def _prefill_cold(self, slot: int, s: _Stream) -> None:
        """One call of the prefill program for one prompt, at one row of
        its bucket's width (``prefill_calls`` in stats(), one
        ``engine.prefill`` span a call: ``segments`` is the BUCKET's,
        ``live_segments`` those of them that hold a row of the prompt,
        which are the ones a segmented block runs)."""
        n = len(s.prompt)
        s.bucket = pb = self._bucket(n)
        self.prefill_calls += 1
        segments = self.model.prefill_segments(self.cfg, pb)
        with _fr.span("serve", "engine.prefill", flush=False, attrs={
                "bucket": pb, "prompts": 1, "rows": 1, "tokens": n,
                "segments": segments,
                "live_segments": -(-n // (pb // segments))}):
            self._prefill_into_slot(slot, s, s.prompt, pb)

    def _prefill_into_slot(self, slot: int, s: _Stream, tokens, width: int,
                           prefix=None) -> None:
        """``_prefill_batch_into_slots`` for one stream: ``tokens`` (its
        prompt, or with ``prefix`` the suffix after the cached rows)
        right-padded to one row of ``width``."""
        n = len(tokens)
        row = np.zeros((1, width), np.int32)
        row[0, :n] = tokens
        mark = _TALLY.n  # (a thread-local's attribute: no call)
        (self.cache, self.cur_tok, tok0, logp0,
         *expert_tokens) = _prefill_batch_into_slots(
            self.params, row, np.array([n], np.int32),
            np.array([slot], np.int32),
            np.array([s.seed], np.uint32),
            np.array([s.temperature], np.float32),
            np.array([s.top_p], np.float32),
            self.cache, self.cur_tok, self.cfg, prefix)
        if _TALLY.n != mark:  # the bucket's first call
            self._note_compiled(_PREFILL, width, mark)
        # NO host sync here: first tokens ride the next chunk's
        # single device_get (a per-admission sync would stall the
        # host until the prefill finished)
        self._pending_first.append((s, tok0[0], logp0[0]))
        if expert_tokens:
            self._pending_expert_tokens.append(expert_tokens)
        self.slot_stream[slot] = s

    def _set_lane(self, slot: int, s: _Stream) -> None:
        self._slot_seed[slot] = s.seed
        self._slot_temp[slot] = s.temperature
        self._slot_topp[slot] = s.top_p

    def _admit_warm(self, slot: int, s: _Stream) -> bool:
        """Try the prefix-cache warm path for one stream: adopt the
        longest cached block-aligned prefix and prefill only the
        suffix. Returns False (cold path) on a miss, a sub-block hit,
        or when no suffix bucket fits the remaining cache rows. The
        miss depth is remembered on the stream so the post-prefill
        insert fetches only rows the cache lacks."""
        pc = self.prefix_cache
        n_pref, entry = pc.match(s.prompt)
        s.__dict__["_pc_have"] = n_pref
        if entry is None:
            pc.record_outcome(False)
            return False
        suffix = s.prompt[n_pref:]
        try:
            sb = self._bucket(len(suffix))
        except ValueError:
            pc.record_outcome(False)  # matched but unusable: cold path
            return False
        if n_pref + sb > self.max_len:
            # the static suffix write window would clamp into the prefix
            pc.record_outcome(False)
            return False
        # the cached rows, zero-padded to one stream's full slot
        pad_k = np.zeros(
            (self.cfg.n_layers, 1, self.max_len, self.cfg.n_kv_heads,
             self.cfg.head_dim), dtype=entry["k"].dtype)
        pad_v = np.zeros_like(pad_k)
        pad_k[:, 0, :n_pref] = entry["k"][:, :n_pref]
        pad_v[:, 0, :n_pref] = entry["v"][:, :n_pref]
        self._prefill_into_slot(slot, s, suffix, sb, prefix=(
            jnp.asarray(pad_k, self.cfg.compute_dtype),
            jnp.asarray(pad_v, self.cfg.compute_dtype), np.int32(n_pref)))
        s.bucket = sb
        pc.record_outcome(True)  # cached rows actually served
        return True

    def _insert_prefixes(self, entries) -> None:
        """After the cold prefills, capture each stream's
        block-aligned prefix rows into the prefix cache. Costs one
        device_get per stream that actually has uncached blocks — the
        amortized price of never prefilling that prefix again."""
        pc = self.prefix_cache
        for slot, s in entries:
            n_ins = ((len(s.prompt) - 1) // pc.block) * pc.block
            if n_ins < pc.block or s.__dict__.get("_pc_have", 0) >= n_ins:
                continue
            k, v = jax.device_get((self.cache["k"][:, slot, :n_ins],
                                   self.cache["v"][:, slot, :n_ins]))
            # the prefix cache keeps rows as the prefill makes them,
            # [L, n, Hkv, hd]
            heads = (*k.shape[:2], self.cfg.n_kv_heads, self.cfg.head_dim)
            pc.insert(s.prompt[:n_ins], k.reshape(heads), v.reshape(heads))

    def pump(self) -> int:
        """Admit + advance one chunk; returns number of active slots.

        Exactly ONE device→host sync per chunk: tokens and per-slot pos
        fetch together. Every further per-slot scalar read here would
        be another blocking round-trip to the device."""
        self._admit()
        self.pumps += 1
        active_mask = np.array(
            [st is not None for st in self.slot_stream])
        if not active_mask.any():
            return 0
        depth = self._spec_depth_now()
        if depth > 0:
            from ray_tpu._private import fault_injection as _fi
            # chaos site: "drop" falls back to the plain kernel for
            # this pump — RETRYABLE by construction, the plain path
            # emits the exact same tokens (just fewer per pump);
            # "stall"/"delay" sleep inside fire() (bounded)
            if _fi.fire("serve.spec_verify", engine=self.name) == "drop":
                depth = 0
        if depth > 0:
            return self._pump_spec(active_mask, depth)
        n_active = int(active_mask.sum())
        with _fr.span("serve", "engine.decode_dispatch", flush=False,
                      attrs={"active": n_active, "chunk": self.chunk,
                             "depth": 0}):
            lanes = self._lanes() if self._sampling_seen else None
            mark = _TALLY.n
            toks, lps, self.cache, self.cur_tok, *touched = decode_chunk(
                self.params, self.cache, self.cur_tok, active_mask, lanes,
                self.cfg, self.chunk)
            if _TALLY.n != mark:
                self._note_compiled(_CHUNK, 0, mark)
        toks, lps, pos_np, firsts = self._readback((toks, lps), touched)
        self._account(*self._deliver_chunk(firsts, pos_np, {
            slot: (toks[slot].tolist(),
                   None if lps is None else lps[slot].tolist())
            for slot, s in enumerate(self.slot_stream) if s is not None}))
        return n_active

    # -- one chunk's host side, shared by the plain and the speculative
    # pump --

    def _lanes(self) -> tuple:
        return (jnp.asarray(self._slot_seed), jnp.asarray(self._slot_temp),
                jnp.asarray(self._slot_topp))

    def _readback(self, chunk_out: tuple, touched: list) -> tuple:
        """The chunk's ONE device→host sync: ``chunk_out`` and each
        slot's pos, plus the first tokens and logprobs of the streams
        prefilled before it and, for a model that reports its routing,
        the chunk's ``experts_touched`` and the prefills'
        ``expert_tokens``. Returns (*chunk_out, pos [B], firsts) on the
        host, firsts as [(stream, token, logprob)]."""
        firsts, self._pending_first = self._pending_first, []
        loads, self._pending_expert_tokens = self._pending_expert_tokens, []
        with _fr.span("serve", "engine.readback", flush=False) as sp:
            if self.chunk_delay_s:
                time.sleep(self.chunk_delay_s)  # see __init__: emulated
                # device time (GIL released; replicas overlap)
            *out, first_toks, first_lps, touched, loads = jax.device_get(
                (*chunk_out, self.cache["pos"], [t for _, t, _ in firsts],
                 [lp for _, _, lp in firsts], touched, loads))
            self._count_rows(sp, out[-1])
            self._count_routing(sp, touched, loads)
        return (*out, [(s, int(t0), float(lp0)) for (s, _, _), t0, lp0
                       in zip(firsts, first_toks, first_lps)])

    def _deliver_chunk(self, firsts: list, pos_np, emitted: dict) -> tuple:
        """A fetched chunk to its streams: the first tokens of the
        streams prefilled before it, then to every occupied slot the
        tokens the chunk emitted for it, ``emitted[slot]`` = (tokens,
        logprobs) as lists, cut here to what the stream still wants.
        Logprobs ``None``: a greedy-only engine's, reported as the
        placeholder 0.0. Returns (the tokens' host stamp, how many were
        delivered) for :meth:`_account`."""
        t_now = time.monotonic()
        with _fr.span("serve", "engine.deliver", flush=False) as sp:
            for s, t0, lp0 in firsts:
                # logprob and stamp first, token last: take_tokens slices
                # by len(tokens), so the parallel lists must never lag it
                s.logprobs.append(lp0)
                s.token_times.append(t_now)
                s.tokens.append(t0)
                self._record_first_token(s, t_now)
            delivered, finished = len(firsts), 0
            for slot, (toks, lps) in emitted.items():
                s = self.slot_stream[slot]
                if lps is None:
                    lps = [0.0] * len(toks)
                take = min(len(toks), s.max_new - len(s.tokens))
                finished += self._deliver(
                    slot, s, toks[:take], lps[:take], t_now,
                    int(pos_np[slot]))
                delivered += take
            sp.update(delivered=delivered, firsts=len(firsts),
                      finished=finished)
        return t_now, delivered

    def _count_rows(self, sp: dict, pos_np) -> None:
        """How much of the slots' rows the chunk's attention had to
        read, from the positions the read-back fetched anyway:
        ``live_rows``, the sum over the occupied slots of their position
        at the chunk's end, and ``cache_rows``, slots x max_len (span
        attrs; ``attn_live_rows`` / ``attn_cache_rows`` in stats() are
        their monotonic totals). Their ratio is the share of the cache
        that held a row. Where the model's layers keep rows of several
        kinds (``row_kinds``), also ``live_rows_<kind>``: the same sum
        with each slot's position cut to the most rows a layer of that
        kind keeps (``attn_live_rows_by_kind`` in stats())."""
        held = pos_np[[st is not None for st in self.slot_stream]]
        sp["live_rows"] = live = int(held.sum())
        sp["cache_rows"] = self.slots * self.max_len
        self.attn_live_rows += live
        self.attn_cache_rows += self.slots * self.max_len
        for kind, (_, most) in self.row_kinds.items():
            sp[f"live_rows_{kind}"] = rows = int(
                np.minimum(held, self.max_len if most is None
                           else most).sum())
            self.attn_live_rows_by_kind[kind] += rows

    def _count_routing(self, sp: dict, touched: list, loads: list) -> None:
        """The routing counters a read-back brought: ``touched`` holds
        the chunk's [steps, L] step counters in the order of the model's
        ``step_counters`` (or nothing), ``loads`` one tuple per prefill
        call since the last read-back: an [L, E] array of assignments (a
        model that holds a part of its experts counts those it holds)
        and, from a block whose expert layer has the compact branch
        (``moe.moe``), a [2] array, its expert-layer calls that had the
        branch and those that took it. Span attrs: each step counter's mean over the chunk's steps and
        layers (``experts_touched``; with held experts ``assignments``
        and ``held_assignments`` too) and, with a prefill's counts,
        ``expert_load_max`` / ``expert_load_mean`` (assignments on the
        fullest expert and the mean over experts, of one call's layers;
        means over the calls where there were several) and, with the
        calls, their sums ``moe_expert_calls`` / ``moe_compact_calls``."""
        for name, t in zip(self.model.step_counters, touched):
            sp[name] = float(t.mean())
            if name == "experts_touched":
                self.moe_touched_expert_steps += int(t.sum())
        if loads:
            calls = [c[1] for c in loads if len(c) > 1]
            loads = [c[0] for c in loads]
            self.moe_assignments += int(sum(a.sum() for a in loads))
            sp["expert_load_max"] = float(
                np.mean([a.max() for a in loads]))
            sp["expert_load_mean"] = float(
                np.mean([a.mean() for a in loads]))
            if calls:
                sp["moe_expert_calls"], sp["moe_compact_calls"] = (
                    int(n) for n in np.sum(calls, axis=0))

    def _deliver(self, slot: int, s: _Stream, toks: list, lps: list,
                 t_now: float, pos: int) -> int:
        """Hand one slot's tokens of this chunk to its stream; frees the
        slot when the stream is done. Returns 1 if it finished."""
        take = len(toks)
        s.logprobs.extend(lps)
        s.token_times.extend([t_now] * take)
        s.tokens.extend(toks)
        # per-token TBT: this stream's inter-chunk gap amortized
        # over the chunk's tokens (tokens inside one chunk land
        # together — the gap IS the per-token pacing a client sees)
        if take > 0 and len(s.token_times) > take:
            prev = s.token_times[-take - 1]
            if t_now > prev:
                self._tbt_obs((t_now - prev) / take, s.tenant)
        if len(s.tokens) < s.max_new and pos < self.max_len - 1:
            return 0
        s.done = True
        self.finished[s.sid] = s
        self.slot_stream[slot] = None  # slot freed THIS chunk
        if len(s.token_times) > 1:
            _fr.record("serve", "serve.decode", s.token_times[0],
                       s.token_times[-1], trace=s.trace,
                       attrs={"sid": s.sid, "tokens": len(s.tokens)})
        return 1

    def _record_first_token(self, s: _Stream, t_now: float) -> None:
        """``serve.first_token``, once per stream, at the moment its
        first token is stamped on the host: submit -> first token under
        the submitter's trace, split in the attrs into engine queue wait
        and prefill-to-token, with the way to ``submit`` from the
        request's birth stamps where it carried any. Also an instant
        event on the profiler's host line."""
        attrs = {
            "sid": s.sid, "engine": self.name,
            "queue_wait_ms": round(
                1e3 * (s.admitted_at - s.submitted), 3),
            "prefill_to_token_ms": round(1e3 * (t_now - s.admitted_at), 3),
            "bucket": s.bucket, "prompt_len": len(s.prompt),
            **_upstream_ms(s.stamps, _fr.wall(s.submitted)),
        }
        _fr.record("serve", "serve.first_token", s.submitted, t_now,
                   attrs=attrs, trace=s.trace, annotate=True)

    MAX_SPEC_DEPTH = 8  # each distinct depth compiles its own kernel

    def _spec_depth_now(self) -> int:
        """Effective draft depth for THIS pump. Read from live config
        every pump (the transfer_scatter_read idiom): serve_spec_enabled
        gates speculation, serve_spec_depth > 0 overrides the engine's
        constructor depth. Returns 0 when speculation is off."""
        from ray_tpu._private import config as _cfg
        try:
            if not _cfg.get("serve_spec_enabled"):
                return 0
            override = int(_cfg.get("serve_spec_depth"))
        except Exception:  # noqa: BLE001 — config never breaks decode
            return self.spec_depth
        depth = override if override > 0 else self.spec_depth
        return max(0, min(depth, self.MAX_SPEC_DEPTH))

    def _pump_spec(self, active_mask, depth: int) -> int:
        """Speculative pump: `chunk` draft/verify rounds in one
        dispatch, emitting 1..depth+1 tokens per slot per round. Same
        single device→host sync as the plain pump; per-slot sequences
        are assembled host-side from the per-round accept counts."""
        n_active = int(active_mask.sum())
        with _fr.span("serve", "serve.spec_verify", flush=False, attrs={
                "engine": self.name, "depth": depth,
                "rounds": self.chunk}) as sv:
            with _fr.span("serve", "engine.decode_dispatch", flush=False,
                          attrs={"active": n_active, "chunk": self.chunk,
                                 "depth": depth}):
                lanes = self._lanes()
                mark = _TALLY.n
                toks, lps, counts, self.cache, self.cur_tok, *touched = \
                    decode_chunk_spec(
                        self.params, self.spec_draft_head, self.cache,
                        self.cur_tok, active_mask, *lanes,
                        self.cfg, self.chunk, depth,
                        self.spec_draft_layers)
                if _TALLY.n != mark:
                    self._note_compiled(_CHUNK_SPEC, 0, mark)
            toks, lps, counts, pos_np, firsts = self._readback(
                (toks, lps, counts), touched)
            emitted = {}
            for slot, s in enumerate(self.slot_stream):
                if s is None:
                    continue
                seq_t: list = []
                seq_lp: list = []
                for r, m in enumerate(counts[slot].tolist()):
                    if m > 0:
                        seq_t.extend(toks[slot, r, :m].tolist())
                        seq_lp.extend(lps[slot, r, :m].tolist())
                # greedy-only engine: match the plain program's logprob
                # surface (the placeholder) so spec on/off is
                # indistinguishable to consumers
                emitted[slot] = (
                    seq_t, seq_lp if self._sampling_seen else None)
            # a round that emitted m tokens accepted m - 1 of its
            # `depth` proposals
            rounds = counts[sorted(emitted)]
            rounds = rounds[rounds > 0]
            proposed, accepted = depth * rounds.size, int((rounds - 1).sum())
            self._spec_hist.update((rounds - 1).tolist())
            sv.update(proposed=proposed, accepted=accepted)
            t_now, delivered = self._deliver_chunk(firsts, pos_np, emitted)
        self._account(t_now, delivered)
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._spec_pumps += 1
        if proposed:
            try:
                m = _get_metrics()
                tags = {"engine": self.name}
                m["spec_proposed"].inc(proposed, tags)
                m["spec_accepted"].inc(accepted, tags)
            except Exception:  # noqa: BLE001 — telemetry never breaks
                pass
        return n_active

    def set_params(self, params, version: int) -> None:
        """Adopt published weights at a chunk boundary (call ONLY from
        the pump owner's thread, between pump()s). The prefix cache is
        dropped wholesale: its KV rows were computed under the old
        weights and would poison warm admissions. In-flight streams
        keep their already-computed KV (their continuation mixes
        versions inside the bounded staleness window — their recorded
        per-token logprobs stay exact regardless, which is what the RL
        importance correction consumes). ``params`` is the published
        tree, f32 masters as a rule: the engine keeps its serving cast
        (``adopt_weights``), made here, once a publish."""
        # the old tree goes first: beside it, the incoming masters and
        # their cast do not fit a chip that holds a 1.9 B model
        self.params = None
        self.params = self._adopt(self.cfg, params, version)
        self.weights_version = int(version)
        if self.prefix_cache is not None:
            self.prefix_cache.clear()

    RATE_WINDOW_S = 5.0

    def _tbt_obs(self, v: float, tenant: str = "-") -> None:
        try:
            _get_metrics()["tbt"].observe(
                v, {"engine": self.name, "tenant": tenant})
        except Exception:  # noqa: BLE001 — telemetry never breaks decode
            pass

    def _account(self, t_now: float, delivered: int) -> None:
        self._total_tokens += delivered
        w = self._rate_window
        w.append((t_now, delivered))
        while w and t_now - w[0][0] > self.RATE_WINDOW_S:
            w.popleft()

    def tokens_per_sec(self) -> float:
        w = self._rate_window
        if len(w) < 2:
            return 0.0
        span = w[-1][0] - w[0][0]
        return sum(n for _, n in w) / span if span > 0 else 0.0

    def stats(self) -> dict:
        """Scaling signals for the serving pool (serve/llm_pool.py):
        occupied slots, queue depth, and recent tokens/s, and monotonic
        totals an outside reader takes deltas of (``total_tokens``, ``pumps``,
        ``prefill_calls``: cold prefills, one prompt each;
        ``weights_bytes``: what the serving tree holds on the device,
        ``state_bytes``: what the slots' state holds there, by kind;
        ``attn_live_rows`` / ``attn_cache_rows`` and, for a model with
        rows of several kinds, ``attn_live_rows_by_kind``: see
        ``_count_rows``;
        for a mixture-of-experts model ``moe_assignments`` and
        ``moe_touched_expert_steps``, see ``__init__``)."""
        active = sum(1 for st in self.slot_stream if st is not None)
        out = {
            "slots": self.slots,
            "active": active,
            "queued": len(self.queue),
            "tokens_per_sec": round(self.tokens_per_sec(), 1),
            "total_tokens": self._total_tokens,
            "weights_version": self.weights_version,
            "weights_bytes": _nbytes(self.params),
            "state_bytes": dict(self.state_bytes),
            "pumps": self.pumps,
            "prefill_calls": self.prefill_calls,
            "attn_live_rows": self.attn_live_rows,
            "attn_cache_rows": self.attn_cache_rows,
        }
        if self.row_kinds:
            out["attn_live_rows_by_kind"] = dict(self.attn_live_rows_by_kind)
        if self.model.reports_routing(self.cfg):
            out["moe_assignments"] = self.moe_assignments
            out["moe_touched_expert_steps"] = self.moe_touched_expert_steps
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self.spec_depth or self._spec_pumps:
            prop, acc = self._spec_proposed, self._spec_accepted
            out["spec"] = {
                "depth": self.spec_depth,
                "draft_layers": self.spec_draft_layers,
                "pumps": self._spec_pumps,
                "proposed": prop,
                "accepted": acc,
                "acceptance_rate":
                    round(acc / prop, 4) if prop else 0.0,
                # accepted-length histogram: length -> verify rounds
                "accept_hist": {
                    str(k): v
                    for k, v in sorted(self._spec_hist.items())},
            }
        return out

    def drain(self, deadline_s: float = 600.0) -> None:
        t0 = time.monotonic()
        while (self.queue or any(s is not None
                                 for s in self.slot_stream)):
            if time.monotonic() - t0 > deadline_s:
                raise TimeoutError("decode drain exceeded deadline")
            self.pump()
