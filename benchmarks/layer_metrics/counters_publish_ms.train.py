"""The window's `fit.counters` span, in ms: what reading the layers'
counters and their sums off the device (one `jax.device_get`, after the
epoch's sync) and publishing them costs INSIDE the timed window. Out of
the window's spans; no value from a program without the span (before
PR 52)."""

from benchmarks import span_reduce


def read(facts):
    return sum(span_reduce.durations_ms(facts["spans"] or [],
                                        "fit.counters")) or None
