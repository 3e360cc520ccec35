"""Median device duration of one decode chunk program."""
from benchmark.metric_lib import DECODE, program_median_ms


def read(facts):
    return program_median_ms(facts, DECODE)
