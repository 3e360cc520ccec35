"""Engine: median time from the slot's grant (prefill dispatched) to the
first token stamped on the host: the prefill call and the decode chunk
whose read-back it rides (``prefill_to_token_ms``)."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.attr_median(
        facts, "serve.first_token", "prefill_to_token_ms",
        metric="prefill_to_first_token_p50_ms.chat")
