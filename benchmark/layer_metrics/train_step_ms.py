"""Median device duration of the train step program."""
from benchmark.metric_lib import program_median_ms, step_program


def read(facts):
    prog = step_program(facts)
    return None if prog is None else program_median_ms(facts, prog)
