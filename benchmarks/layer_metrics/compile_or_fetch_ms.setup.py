"""Set-up's `xla.compile` spans, the union of their intervals: XLA's
compile of each program, or its fetch from the persistent cache
(`xla_compiles.setup` says which a run paid). The probe's own compile
and the traced run's beacon are left out. No value from a program
without the spans."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("compile_or_fetch_ms.setup")
