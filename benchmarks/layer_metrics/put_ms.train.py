"""Median of the window's `data.put` spans: host time to hand one batch to
`device_put` (under a mesh, `MeshContext.put_batch`) in the prefetch
iterator. Near the step time it is back-pressure of a full transfer queue;
with `etl_wait_ms.train` high and this low the feed itself is slow. Read
out of the program's span store; no value from a program that keeps none."""

from benchmarks import span_reduce


def read(facts):
    return span_reduce.program_span_metric("put_ms.train")
