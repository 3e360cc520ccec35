"""Engine step: prompts admitted per execution of the static-width
prefill program. Admissions are the clients' over the whole window,
scaled to the traced part of it; executions are counted in the trace."""
from benchmark import trace_reduce
from benchmark.metric_lib import PREFILL


def read(facts):
    durs = trace_reduce.program_durations(facts["trace"]).get(PREFILL)
    if not durs:
        return None
    rate = facts["client"]["admitted"] / facts["window_s"]
    return rate * facts["device"]["window_s"] / len(durs)
