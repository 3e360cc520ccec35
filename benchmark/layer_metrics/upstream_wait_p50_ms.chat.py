"""Proxy and pool: median time from the proxy's parsed body to
``engine.submit`` in the replica (``upstream_ms`` of the first-token
marks, from the request's birth stamps). Its three parts are printed."""
import statistics
import sys

from benchmark import span_reduce

METRIC = "upstream_wait_p50_ms.chat"
PARTS = ("proxy_to_pool_ms", "admission_wait_ms", "pool_to_replica_ms")


def read(facts):
    for part in PARTS:
        xs = [ev[3][part] for ev in span_reduce.named(
            span_reduce.spans(facts), "serve.first_token")
            if part in ev[3]]
        if xs:
            print(f"benchmark: {METRIC}: {part} median "
                  f"{statistics.median(xs):.3f} over {len(xs)}",
                  file=sys.stderr, flush=True)
    return span_reduce.attr_median(facts, "serve.first_token",
                                   "upstream_ms", metric=METRIC)
