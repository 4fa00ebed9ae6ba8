"""Set-up's `compile.probe` spans, summed: what the watchdog's cost and
comm ledgers cost a cached step's first call (a second lowering of the
step, its compile, XLA's cost analysis, the compiled module's text and
its parse). No value from a program without the span."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("compile_probe_ms.setup")
