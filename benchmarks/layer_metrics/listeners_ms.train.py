"""Median of the window's `fit.listeners` spans: host time a step spends
in listener fan-out and `after_step`. A median: in the traced run the
profiler's stop is one listener call of seconds. Read out of the program's
span store; no value from a program that keeps none."""

from benchmarks import span_reduce


def read(facts):
    return span_reduce.program_span_metric("listeners_ms.train")
