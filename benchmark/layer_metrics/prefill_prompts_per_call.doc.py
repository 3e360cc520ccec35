"""Engine step: real prompts per call of the static-width prefill
program, mean over the traced part: the engine's own count at the call
(``engine.prefill`` spans), not an estimate from the clients."""
from benchmark import span_reduce


def read(facts):
    return span_reduce.prefill_prompts_per_call(
        facts, "prefill_prompts_per_call.doc")
