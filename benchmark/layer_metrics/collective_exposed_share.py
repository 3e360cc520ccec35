"""Time in collectives during which no compute runs on that device,
over the traced window, mean over the chips."""
from benchmark import trace_reduce


def read(facts):
    share = trace_reduce.exposed_collective_share(facts["trace"])
    return None if share is None else 100.0 * share
