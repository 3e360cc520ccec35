"""Load generator: how late it sent, sent - due, 95th percentile."""
from benchmark.metric_lib import client_ms


def read(facts):
    return client_ms(facts, "lateness_s", 95)
