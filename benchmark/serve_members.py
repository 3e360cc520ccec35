"""Thin subclasses of the product's ``LLMPool`` and ``LLMServer``.

They exist because two things cannot be had without a program change
(both are listed in PERF.md as "what the program must offer instead"):

1. a published configuration reaches ``build_model`` only as a name of
   the repo's own Llama table, so the subclasses resolve the
   configuration's name to a ``LlamaConfig`` inside the pool's and the
   replica's process, and the replica makes its weights on its own chip
   in one jitted call from the seed (the pool's CPU build and its
   object-store put of the f32 tree are skipped: that is the pool's
   START, not the request path);
2. only the process that holds the chip can trace it or run the
   reference on it, so the replica starts and stops ``jax.profiler`` and
   runs the correctness check on request.

Nothing on the request path is touched: proxy -> pool admission and
routing -> replica pump -> ``RaggedDecoder`` are the product's.
"""

from __future__ import annotations

from unittest import mock

import ray_tpu
from ray_tpu.serve import llm as _llm
from ray_tpu.serve import llm_pool as _llm_pool

from benchmark.manifest import model_fields


def _build_on_device(model, *, max_len=512, vocab_size=None, seed=0,
                     params_blob=None):
    """Stands in for ``serve.llm.build_model`` in the replica: f32
    masters made on this process's device from the seed, in one call."""
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(**model_fields(model), max_seq_len=max_len,
                            remat=False)
    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return jax.jit(lambda k: llama.init_params(cfg, k))(key), cfg


class BenchServer(_llm.LLMServer):
    """The product's decode replica, built from a configuration name."""

    def __init__(self, model_size, **kw):
        kw["params_blob"] = None  # the pool publishes an empty tree
        with mock.patch.object(_llm, "build_model", _build_on_device):
            super().__init__(model_size, **kw)
        self._model = model_fields(model_size)

    def reference_check(self, prompt: list, tokens: list) -> dict:
        from benchmark import reference

        return reference.check_served_tokens(
            self.engine.params, prompt, tokens, self._model)

    def start_trace(self, log_dir: str) -> bool:
        import jax

        jax.profiler.start_trace(log_dir)
        return True

    def stop_trace(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True


_BenchReplica = ray_tpu.remote(num_cpus=0)(BenchServer)


class BenchPool(_llm_pool.LLMPool):
    """The product's pool; replicas are ``BenchServer``s."""

    def __init__(self, model_size, **kw):
        # the pool itself needs no weights: an empty tree is published
        with mock.patch.object(_llm_pool, "build_model",
                               lambda *a, **k: ({}, None)):
            super().__init__(model_size, **kw)

    def _spawn_replica(self):  # LLMPool._spawn_replica with our class
        self._n_spawned += 1
        name = f"decode-{self._n_spawned}"
        with self._lock:
            ref, version = self._params_ref, self._weights_version
        h = _BenchReplica.options(
            max_concurrency=self._max_inflight + 8,
            num_tpus=self._member_tpus,
        ).remote(**self._replica_kwargs, params_blob=ref,
                 engine_name=name, weights_version=version)
        return _llm_pool._Replica(h, name)

    def bench_call(self, method: str, *args):
        """Forward a benchmark-only call to the (one) replica."""
        h = self._replicas[0].handle
        return ray_tpu.get(getattr(h, method).remote(*args), timeout=900)
