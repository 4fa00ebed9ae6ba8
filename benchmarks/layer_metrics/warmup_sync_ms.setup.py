"""The `fit.epoch_sync` span of the first `fit` root: how long the warm-up
`fit()`'s one host sync waited for the device, so the device's own time
over the warm-up steps less what the dispatches overlapped. No value
from a program that does not time its set-up."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("warmup_sync_ms.setup")
