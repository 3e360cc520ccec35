"""Engine: median wait of a request between ``engine.submit`` and the
pump that granted it a slot (``queue_wait_ms`` of the first-token marks)."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.attr_median(
        facts, "serve.first_token", "queue_wait_ms",
        metric="engine_queue_wait_p50_ms.chat")
