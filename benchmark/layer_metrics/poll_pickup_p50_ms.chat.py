"""Proxy and pool: how long a stream's first token lay in the replica
before the pool's poll fetched it, median (``pickup_ms`` of the
``serve.poll_pickup`` marks that took a first token)."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.attr_median(
        facts, "serve.poll_pickup", "pickup_ms",
        metric="poll_pickup_p50_ms.chat", where=lambda a: a.get("first"))
