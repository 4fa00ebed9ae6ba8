"""Set-up's `xla.trace` spans, the union of their intervals: JAX tracing
the program's jitted functions to jaxprs (the train step's first, the
traced `init()`, small eager programs). The probe's second trace and the
traced run's beacon are left out. No value from a program without the
spans."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("trace_ms.setup")
