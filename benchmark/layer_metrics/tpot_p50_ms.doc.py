"""Recorded, never judged, above the knee: client clock, median."""
from benchmark.metric_lib import client_ms


def read(facts):
    return client_ms(facts, "tpot_s")
