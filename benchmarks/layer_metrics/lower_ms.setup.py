"""Set-up's `xla.lower` spans, the union of their intervals: jaxpr to MLIR
module. The probe's own lowering and the traced run's beacon are left
out. No value from a program without the spans."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("lower_ms.setup")
