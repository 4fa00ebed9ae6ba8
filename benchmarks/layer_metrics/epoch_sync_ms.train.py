"""The window's `fit.epoch_sync` span: how long the epoch's one host sync
(`LossTracker.materialize()`) waited for the device to drain its queue at
the end of the window. Read out of the program's span store; no value from
a program that keeps none."""

from benchmarks import span_reduce


def read(facts):
    return span_reduce.program_span_metric("epoch_sync_ms.train")
