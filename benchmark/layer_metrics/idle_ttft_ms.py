"""Proxy and pool, timed from outside: one-token requests on the idle
system before load, client clock, median. Goes when spans replace it."""
from benchmark.metric_lib import client_ms


def read(facts):
    return client_ms(facts, "idle_ttft_s")
