"""Engine step: the prompts' real tokens over the rows x bucket
positions the prefill calls of the traced part computed."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.prefill_token_use_share(
        facts, "prefill_token_use_share.chat")
