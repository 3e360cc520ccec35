"""LLM serving deployment: continuous-batching decode behind Serve.

Reference anchor: the reference's LLM serving examples and its OPT-30B
inference release test (release_tests.yaml) run decode through Serve
replicas; this is the TPU-native equivalent — each replica owns a
RaggedDecoder (models/decode_engine.py: fixed slot batch, chunked
continuous batching over a ragged KV cache) and a pump thread. Handler
threads (the replica runs with actor max_concurrency) only enqueue and
wait; every device step happens on the ONE pump thread, so concurrent
HTTP requests ride the same slot batch — admission into free slots at
chunk boundaries, not a new batch per request.

Multi-replica serving (serve/llm_pool.py LLMPool) builds on the extras
here: `params_blob` lets every replica adopt ONE published weight blob
(a single object-store put, pulled via the pipelined multi-source
path) instead of re-serializing per replica; `adopt_prefilled` admits
KV computed by a dedicated prefill worker; `submit_stream`/
`poll_stream` expose token streaming; `shutdown()` is the
deterministic drain used on replica downscale.
"""

from __future__ import annotations

import contextlib
import threading
import time

from ray_tpu._private import flight_recorder as _fr


def build_model(model_size: str = "tiny", *, max_len: int = 512,
                vocab_size: int = 32128, seed: int = 0,
                params_blob=None):
    """(params, cfg) for a serving model — shared by decode replicas
    and prefill workers so both pools run the identical network. When
    `params_blob` (a host tree published through the object store) is
    given, weights are adopted instead of re-initialized: one shared
    put serves every replica via the multi-source pull path.

    The tree is the published one, f32 masters (``llama.init_params``).
    What a replica HOLDS is its serving cast: ``RaggedDecoder`` and
    ``PrefillWorker`` round the matrices to the compute dtype once, when
    they adopt the tree (``decode_engine.adopt_weights``), and let the
    masters go; a replica never trains."""
    import jax

    from ray_tpu.models import llama

    import ray_tpu

    if isinstance(params_blob, ray_tpu.ObjectRef):
        # actor CONSTRUCTOR args ship as an opaque payload (no dep
        # staging, unlike method calls) — resolve the published weight
        # ref here, via the pipelined multi-source pull, tagged as the
        # weights broadcast for pacing + byte attribution
        from ray_tpu._private.worker import fetch_context

        with fetch_context(qos="bulk", owner="weights"):
            params_blob = ray_tpu.get(params_blob, timeout=600)

    if model_size == "tiny":  # test-sized config
        cfg = llama.LlamaConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, max_seq_len=max_len,
            dtype="float32", remat=False)
    elif model_size == "tiny-wide":  # bench-sized: compute-bound on CPU
        cfg = llama.LlamaConfig(
            vocab_size=512, d_model=256, n_layers=4, n_heads=8,
            n_kv_heads=4, d_ff=512, max_seq_len=max_len,
            dtype="float32", remat=False)
    else:
        base = llama.llama2_size(model_size)
        cfg = llama.LlamaConfig(**{
            **base.__dict__, "vocab_size": vocab_size,
            "max_seq_len": max_len, "dtype": "bfloat16",
            "remat": False,
        })
    if params_blob is not None:
        params = jax.tree_util.tree_map(jax.numpy.asarray, params_blob)
    else:
        params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    return params, cfg


def build_spec_draft(cfg, *, draft_layers: int = 0,
                     draft_head: bool = False, seed: int = 0):
    """Draft-model assets for speculative decoding, built alongside the
    target (every replica derives the identical draft from the same cfg
    + seed, so failover replicas propose identically — irrelevant for
    correctness, it only keeps acceptance rates comparable). The draft
    is a WEIGHT VIEW: the target's first `draft_layers` layers (default
    half the stack) under the target's own final norm and head, plus an
    optional zero-init residual adapter head (mlp.init_draft_head —
    identity at init, a later distillation pass can train it). Returns
    (draft_layers, head_tree_or_None); the head is ENGINE-LOCAL state,
    never part of the published weight tree."""
    import jax

    from ray_tpu.models import mlp

    n = int(draft_layers) or max(1, cfg.n_layers // 2)
    n = min(max(n, 1), cfg.n_layers)
    head = None
    if draft_head:
        head = mlp.init_draft_head(
            cfg.d_model, jax.random.PRNGKey(int(seed) + 1))
    return n, head


def _pump_span(working: bool, name: str, **attrs):
    """A ring-only span of the pump thread, for a WORKING iteration
    only: an idle replica spins every 5 ms and would flush the ring."""
    if not working:
        return contextlib.nullcontext()
    return _fr.span("serve", name, attrs=attrs, flush=False)


class LLMServer:
    """Deployable class (wrap with @serve.deployment or Deployment(...)).

    init builds the model on THIS replica's device: the chip its node
    agent granted it (deploy with ``resources={"TPU": 1}``), else the
    CPU (_private/accelerator.py). generate() blocks its handler
    thread until the stream finishes and returns tokens + per-token
    latency stamps, so the caller can compute p50/p99."""

    STREAM_IDLE_PURGE_S = 120.0  # abandoned streaming sids

    def __init__(self, model_size: str = "tiny", *, slots: int = 8,
                 max_len: int = 512, chunk_tokens: int = 16,
                 vocab_size: int = 32128, seed: int = 0,
                 prompt_buckets: tuple = (32, 64, 128, 256),
                 params_blob=None, prefix_cache_block: int = 0,
                 prefix_cache_mb: int = 256, engine_name: str = "",
                 chunk_delay_s: float = 0.0, weights_version: int = 0,
                 spec_depth: int = 0, spec_draft_layers: int = 0,
                 spec_draft_head: bool = False):
        import os

        import jax

        from ray_tpu._private import accelerator
        from ray_tpu.models.decode_engine import RaggedDecoder, _nbytes

        # the bring-up in spans, flushed (once a replica's life):
        # ``serve.replica_start`` from here to the pump thread's start,
        # its ``process_age_ms`` everything before this line (spawn,
        # interpreter, imports, the actor's creation)
        t_init = time.monotonic()
        with _fr.span("serve", "serve.replica_start", attrs={
                "process_age_ms": round(1e3 * _fr.process_age_s(), 1)}) as rs:
            # before the first compile: a replica granted a chip must be on
            # it (raises otherwise), and the compile cache gets its place
            with _fr.span("serve", "serve.claim_device") as sp:
                claim = accelerator.claim_device()
                sp.update({k: claim[k]
                           for k in ("waited_ms", "platform", "count")})
            t_claimed, mark = time.monotonic(), accelerator.compile_mark()
            with _fr.span("serve", "serve.weights_build") as sp:
                # (waited for: a draw still running on the device would be
                # charged to the engine's cast, which waits next)
                params, cfg = build_model(
                    model_size, max_len=max_len, vocab_size=vocab_size,
                    seed=seed, params_blob=params_blob)
                sp["bytes"] = _nbytes(jax.block_until_ready(params))
                sp.update(accelerator.compile_since(mark) or {})
            t_built = time.monotonic()
            prefix_cache = None
            if prefix_cache_block > 0:
                from ray_tpu.models.kv_prefix_cache import PrefixCache

                prefix_cache = PrefixCache(
                    block=prefix_cache_block,
                    max_bytes=prefix_cache_mb * 2**20)
            draft_layers, draft_head = build_spec_draft(
                cfg, draft_layers=spec_draft_layers,
                draft_head=spec_draft_head, seed=seed)
            self.engine = RaggedDecoder(
                params, cfg, slots=slots, max_len=max_len,
                chunk_tokens=chunk_tokens, prompt_buckets=prompt_buckets,
                prefix_cache=prefix_cache, chunk_delay_s=chunk_delay_s,
                name=engine_name or f"llm-{os.getpid()}",
                weights_version=weights_version,
                spec_depth=spec_depth, spec_draft_layers=draft_layers,
                spec_draft_head=draft_head)
            del params  # the engine holds its serving cast, nobody the masters
            # (host params tree, version) staged by update_weights(); the
            # pump thread adopts it at the next chunk boundary — engine
            # params are touched only by the pump owner
            self._pending_weights: tuple | None = None
            self._trace_dir: str | None = None  # a capture's, while it runs
            self._lock = threading.Lock()
            self._done_events: dict[int, threading.Event] = {}
            # sids being consumed via poll_stream: the pump must NOT purge
            # their finished entries (no _done_events waiter is registered)
            self._stream_sids: dict[int, float] = {}  # sid -> last poll
            # poll RPCs served (single + batched): the batching test's
            # falsifiability counter — N streams should NOT mean N RPCs/tick
            self._poll_rpcs = 0
            self._stop = False
            self._draining = False
            self._pump_thread = threading.Thread(
                target=self._pump_loop, daemon=True,
                name="llm-decode-pump")
            self._pump_thread.start()
            rs["engine"] = self.engine.name
        # what the bring-up cost, kept for ``stats()["setup"]`` and the
        # ``serve.setup`` mark (:meth:`setup_record`); the stamps are
        # ``time.monotonic`` in ns, one clock for every process of a
        # Linux machine
        t_ready = time.monotonic()
        ms = lambda a, b: round(1e3 * (b - a), 3)  # noqa: E731
        self._setup = {
            "process_age_ms": rs["process_age_ms"],
            "claim_ms": ms(t_init, t_claimed),
            "chip_wait_ms": claim["waited_ms"],
            "weights_build_ms": ms(t_claimed, t_built),
            "weights_cast_ms": self.engine.weights_cast_ms,
            "replica_start_ms": ms(t_init, t_ready),
            "init_mono_ns": int(t_init * 1e9),
            "ready_mono_ns": int(t_ready * 1e9)}

    def setup_record(self) -> dict:
        """What this replica's bring-up cost and what its programs' first
        calls have cost so far, all plain numbers: the parts of
        ``serve.replica_start`` (``__init__``), the sums over the
        engine's ``engine.compiled`` marks (``first_calls``, ``trace_ms``,
        ``lower_ms``, ``compile_ms``, ``cache_read_ms``, ``first_call_ms``,
        ``compile_requests``, ``cache_hits``, ``last_compile_mono_ns``)
        and the process's own cache counters
        (``proc_compile_requests``, ``proc_cache_hits``): whether a cold
        replica's programs came from the persistent cache."""
        from ray_tpu._private import accelerator

        proc = accelerator.compile_report()
        return {**self._setup, **self.engine.compiled,
                "proc_compile_requests": proc["requests"],
                "proc_cache_hits": proc["hits"]}

    def _pump_loop(self):
        # engine state is touched ONLY by this thread; handlers interact
        # through submit (guarded by the small lock) and the finished
        # dict (written here BEFORE the event is set, read by the
        # handler only AFTER it) — the pump never holds a lock across
        # device work, so submissions land during the chunk wait
        eng = self.engine
        while not self._stop:
            active = sum(st is not None for st in eng.slot_stream)
            queued = len(eng.queue)
            working = bool(active or queued)
            # mono_ns: the one pair that maps the profiler's time to
            # time.monotonic, which the processes of a machine share
            with _pump_span(working, "serve.pump",
                            mono_ns=time.monotonic_ns(), active=active,
                            queued=queued):
                busy = self._pump_once(working)
            if not busy:
                time.sleep(0.005)  # idle: don't spin the device

    def _pump_once(self, working: bool) -> int:
        import logging

        from ray_tpu._private import fault_injection as _fi

        try:
            # chaos site: replica death / stall mid-decode (ctx
            # carries the engine name so a plan can pin ONE replica)
            _fi.fire("serve.replica_pump", engine=self.engine.name)
            pending = None
            with self._lock:
                pending, self._pending_weights = (
                    self._pending_weights, None)
            if pending is not None:
                import jax.numpy as jnp

                import jax as _jax

                tree, version = pending
                self.engine.set_params(
                    _jax.tree_util.tree_map(jnp.asarray, tree),
                    version)
            busy = self.engine.pump()
        except Exception:  # noqa: BLE001 — the pump must survive:
            # a dead pump thread bricks the replica for every
            # in-flight and future request (submit-time validation
            # rejects bad requests; this is the backstop)
            logging.getLogger(__name__).exception("decode pump error")
            busy = 0
        now = time.monotonic()
        with _pump_span(working, "serve.pump_bookkeeping",
                        waiters=len(self._done_events),
                        streams=len(self._stream_sids)), self._lock:
            for sid, ev in list(self._done_events.items()):
                if sid in self.engine.finished:
                    ev.set()
            for sid in list(self.engine.finished):
                if sid not in self._done_events \
                        and sid not in self._stream_sids:
                    # abandoned (handler timed out): don't pin the
                    # stream's tokens forever
                    self.engine.purge(sid)
            for sid, last in list(self._stream_sids.items()):
                if now - last > self.STREAM_IDLE_PURGE_S:
                    # streaming client went away mid-stream
                    self._stream_sids.pop(sid, None)
                    self.engine.purge(sid)
        return busy

    # -- blocking API --

    def _submit_locked(self, submit_fn):
        ev = threading.Event()
        with self._lock:
            if self._draining:
                raise RuntimeError("replica draining: not admitting")
            # submit() validates (prompt fits a bucket, room for at
            # least one token) and raises HERE, in the handler — the
            # proxy maps it to a per-request 500 instead of the pump
            # thread dying on it
            sid = submit_fn()
            self._done_events[sid] = ev
        return sid, ev

    def _wait_result(self, sid: int, ev: threading.Event,
                     max_tokens: int) -> dict:
        try:
            if not ev.wait(timeout=600):
                raise TimeoutError(
                    f"stream {sid} did not finish in 600s")
            s = self.engine.pop_finished(sid)
        finally:
            # timeout path too: a leaked event entry is rescanned every
            # pump tick; the pump purges finished streams with no
            # registered waiter (abandoned by a timed-out handler)
            with self._lock:
                self._done_events.pop(sid, None)
        return {
            "tokens": s.tokens[:max_tokens],
            "submitted_s": s.submitted,
            "token_times_s": s.token_times[:max_tokens],
            "logprobs": s.logprobs[:max_tokens],
            "weights_version": s.version,
        }

    def generate(self, prompt_ids: list, max_tokens: int = 64, *,
                 temperature: float = 0.0, top_p: float = 1.0,
                 seed: int = 0, tenant: str = "-",
                 stamps: dict | None = None) -> dict:
        """Blocking single-request API (one handler thread per call;
        all calls share the slot batch). ``stamps``: the request's birth
        stamps (proxy, pool), for the first-token span."""
        sid, ev = self._submit_locked(
            lambda: self.engine.submit(
                list(prompt_ids), int(max_tokens),
                temperature=temperature, top_p=top_p, seed=seed,
                tenant=tenant, stamps=stamps))
        return self._wait_result(sid, ev, int(max_tokens))

    def adopt_prefilled(self, kv: dict, prompt_ids: list,
                        max_tokens: int = 64, *,
                        temperature: float = 0.0, top_p: float = 1.0,
                        seed: int = 0, tenant: str = "-",
                        stamps: dict | None = None) -> dict:
        """Blocking generate for a stream prefilled ELSEWHERE: `kv` is
        the prefill worker's payload (decode_engine.prefill_kv rows +
        first token), typically passed as an ObjectRef so the KV rows
        ride the object store straight from the prefill worker's node
        to this replica (pipelined multi-source pull), never through
        the pool."""
        t0 = time.monotonic()
        sid, ev = self._submit_locked(
            lambda: self.engine.submit_prefilled(
                list(prompt_ids), int(max_tokens), kv,
                temperature=temperature, top_p=top_p, seed=seed,
                tenant=tenant, stamps=stamps))
        self._record_kv_handoff(kv, t0, tenant=tenant)
        return self._wait_result(sid, ev, int(max_tokens))

    def _record_kv_handoff(self, kv, t0: float, tenant: str = "-") -> None:
        """Span + kv-class rx attribution for an externally-prefilled
        payload adopted by this replica (the KV rows arrived via the
        object store during arg staging; this covers the replica-side
        handoff into the engine). The handoff claims a kv-class grant on
        the pacer first — under a finite rate, THIS is what preempts
        in-flight bulk chunks on the link (strict priority): the claim
        is latency-critical, so a refused window is logged as a park and
        the handoff proceeds (the bytes already arrived; the claim paces
        the link, it does not gate correctness)."""
        try:
            from ray_tpu._private import net_accounting as _net
            from ray_tpu._private import net_qos as _qos

            nb = int(getattr(kv.get("k"), "nbytes", 0)
                     + getattr(kv.get("v"), "nbytes", 0))
            try:
                _qos.acquire("prefill", "kv", nb,
                             owner=self.engine.name, timeout=5.0)
            except _qos.NetPaceError:
                pass  # typed park under injection/saturation: proceed
            _fr.record("serve", "serve.kv_handoff", t0, time.monotonic(),
                       attrs={"kv_bytes": nb, "tenant": tenant,
                              "engine": self.engine.name})
            _net.account_rx("prefill", "kv", self.engine.name, nb,
                            tenant=tenant)
        except Exception:  # noqa: BLE001 — observability best-effort
            pass

    # -- streaming API --

    @staticmethod
    def _sampling(req: dict) -> dict:
        return {"temperature": float(req.get("temperature", 0.0)),
                "top_p": float(req.get("top_p", 1.0)),
                "seed": int(req.get("seed", 0))}

    def submit_stream(self, req: dict) -> dict:
        """Start a stream; poll_stream drains it incrementally. `req`
        may carry a prefilled KV payload under "kv" and sampling knobs
        under "temperature"/"top_p"/"seed"."""
        prompt_ids = list(req["prompt_ids"])
        max_tokens = int(req.get("max_tokens", 64))
        sampling = self._sampling(req)
        tenant = str(req.get("tenant", "-"))
        stamps = req.get("stamps")
        t0 = time.monotonic()
        with self._lock:
            if self._draining:
                raise RuntimeError("replica draining: not admitting")
            if req.get("kv") is not None:
                sid = self.engine.submit_prefilled(
                    prompt_ids, max_tokens, req["kv"], tenant=tenant,
                    stamps=stamps, **sampling)
            else:
                sid = self.engine.submit(prompt_ids, max_tokens,
                                         tenant=tenant, stamps=stamps,
                                         **sampling)
            self._stream_sids[sid] = time.monotonic()
        if req.get("kv") is not None:
            self._record_kv_handoff(req["kv"], t0, tenant=tenant)
        return {"sid": sid}

    def submit_stream_prefilled(self, kv: dict, prompt_ids: list,
                                max_tokens: int = 64, *,
                                temperature: float = 0.0,
                                top_p: float = 1.0,
                                seed: int = 0,
                                tenant: str = "-",
                                stamps: dict | None = None) -> dict:
        """submit_stream for an externally-prefilled stream. `kv` is a
        dedicated TOP-LEVEL argument (not nested in a request dict) so
        an ObjectRef passed here is resolved by the executor's arg
        staging — the KV rows ride the object store from the prefill
        worker's node, never through the caller."""
        t0 = time.monotonic()
        with self._lock:
            if self._draining:
                raise RuntimeError("replica draining: not admitting")
            sid = self.engine.submit_prefilled(
                list(prompt_ids), int(max_tokens), kv,
                temperature=temperature, top_p=top_p, seed=seed,
                tenant=tenant, stamps=stamps)
            self._stream_sids[sid] = time.monotonic()
        self._record_kv_handoff(kv, t0, tenant=tenant)
        return {"sid": sid}

    def poll_stream(self, sid: int) -> dict:
        """New tokens (+ parallel behavior logprobs) since the last
        poll, plus a done flag. The final poll (done=True) releases the
        stream."""
        self._poll_rpcs += 1
        return self._poll_one(int(sid))

    def poll_streams(self, sids: list) -> dict:
        """Batched poll: ONE RPC drains every listed stream. The pool's
        fan-out consumers each poll per request, which caps aggregate
        streaming throughput at the RPC rate (~106 tok/s measured)
        rather than the engine's decode rate — the pool batches all
        sids co-located on this replica into one of these calls per
        tick. Returns {sid: poll result}."""
        self._poll_rpcs += 1
        return {int(sid): self._poll_one(int(sid)) for sid in sids}

    def _poll_one(self, sid: int) -> dict:
        with self._lock:
            if sid not in self._stream_sids:
                return {"tokens": [], "logprobs": [], "done": True,
                        "version": None}
            now = self._stream_sids[sid] = time.monotonic()
            # read BEFORE take_tokens: the final (fully-drained) take
            # purges the stream and with it the version record
            version = self.engine.stream_version(sid)
            s = self.engine._by_sid.get(sid)
            taken = s.taken if s is not None else 0
            new, lps, done = self.engine.take_tokens(
                sid, with_logprobs=True)
            if done:
                self._stream_sids.pop(sid, None)
        if new:
            # how long the oldest token taken lay in the replica before
            # this poll fetched it (ring-only: one per poll that found
            # tokens)
            _fr.mark("serve", "serve.poll_pickup", flush=False, attrs={
                "sid": sid, "tokens": len(new), "first": taken == 0,
                "pickup_ms": round(
                    1e3 * (now - s.token_times[taken]), 3)})
        return {"tokens": new, "logprobs": lps, "done": done,
                "version": version}

    # -- weight publishing (actor-learner loop) --

    def update_weights(self, params_blob, version: int) -> int:
        """Adopt a published weight tree. ``params_blob`` is normally an
        ObjectRef passed TOP-LEVEL by the pool, so the host tree arrives
        via the multi-source pipelined pull before this method runs. The
        swap itself happens on the pump thread at the next chunk
        boundary — the bounded staleness window is one engine chunk —
        so this returns as soon as the tree is staged."""
        import ray_tpu

        if isinstance(params_blob, ray_tpu.ObjectRef):
            from ray_tpu._private.worker import fetch_context

            with fetch_context(qos="bulk", owner="weights"):
                params_blob = ray_tpu.get(params_blob, timeout=600)
        with self._lock:
            self._pending_weights = (params_blob, int(version))
        return int(version)

    def weights_version(self) -> int:
        return self.engine.weights_version

    def apply_config(self, config: dict) -> dict:
        """Apply live config overrides in THIS replica's process — the
        pool-wide flip path for knobs the engine reads per pump
        (``serve_spec_enabled`` / ``serve_spec_depth`` /
        ``net_qos_bulk_share``). A driver-side ``set_system_config``
        only reaches processes spawned afterwards; the overload
        guardian broadcasts degradation flips here so a RUNNING pool
        sheds speculation within one chunk. Returns the applied dict."""
        from ray_tpu._private import config as _cfg

        _cfg.set_system_config(dict(config))
        return {k: _cfg.get(k) for k in config}

    def __call__(self, req: dict) -> dict:
        """HTTP entrypoint (serve http_proxy: POST body -> __call__):
        {"prompt_ids": [...], "max_tokens": N} -> generate()."""
        return self.generate(list(req["prompt_ids"]),
                             int(req.get("max_tokens", 64)))

    # -- profiler capture of THIS replica (only the process that holds
    # the chip can trace it) --

    def start_trace(self, log_dir: str) -> bool:
        """Start a ``jax.profiler`` capture into ``log_dir``: device
        operations and this process's flight-recorder spans (pump,
        prefill, read-back, first tokens) in one file, on one clock."""
        import jax

        jax.profiler.start_trace(log_dir)
        self._trace_dir = log_dir
        self.engine.mark_state()  # the trace says what engine it is of
        # ... and what its bring-up cost, minutes before the capture
        _fr.mark("serve", "serve.setup", flush=False,
                 attrs=self.setup_record())
        return True

    def stop_trace(self) -> bool:
        """End the capture, then write ``program_parts.json`` beside it:
        which part of the model every operation of the engine's programs
        came from (``models/program_parts.py``), the map a reader puts
        the trace's ``XLA Ops`` events through. Made here, behind the
        traced part, on the caller's thread, from the executables the
        engine's calls already hold: nothing is compiled, and without a
        capture nothing of it exists."""
        import json
        import os

        import jax

        from ray_tpu.models import program_parts

        jax.profiler.stop_trace()
        log_dir, self._trace_dir = self._trace_dir, None
        t0 = time.monotonic()
        programs = self.engine.program_parts()
        with open(os.path.join(log_dir, program_parts.FILE), "w") as f:
            json.dump({"engine": self.engine.name,
                       "vocabulary": list(program_parts.VOCABULARY),
                       # what the map cost, for whoever reads it
                       "seconds": round(time.monotonic() - t0, 3),
                       "programs": programs}, f)
        return True

    def stats(self) -> dict:
        with self._lock:
            st = self.engine.stats()
            st["draining"] = self._draining
            st["stream_polls"] = self._poll_rpcs
        from ray_tpu._private import accelerator

        # platform/kind/count as THIS process sees them, its compile
        # counters and peak device memory
        st["device"] = accelerator.device_report()
        st["setup"] = self.setup_record()
        return st

    def health(self) -> bool:
        return not self._stop

    # -- lifecycle --

    def shutdown(self, drain_s: float = 30.0) -> bool:
        """Deterministic teardown for graceful replica drain (the pool
        calls this on downscale): reject new admits, let in-flight
        streams finish (bounded by drain_s), then stop and join the
        pump thread. Returns True when everything drained in time."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + max(0.0, drain_s)
        drained = True
        while time.monotonic() < deadline:
            with self._lock:
                busy = (self.engine.queue
                        or any(s is not None
                               for s in self.engine.slot_stream)
                        or self._done_events or self._stream_sids)
            if not busy:
                break
            time.sleep(0.02)
        else:
            drained = False
        self._stop = True
        self._pump_thread.join(timeout=10.0)
        return drained and not self._pump_thread.is_alive()

    def __del__(self):
        self._stop = True
