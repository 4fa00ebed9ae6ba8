"""Real compiles (`xla.compile` spans with `fetched` false) inside the
window's `fit` subtree, by the program's own listener: the in-program
twin of `xla_compiles_in_window.train`, with the compiled function's
name and the step it fell in on each span. Should read 0. No value from
a program that records no compile."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("xla_compile_spans_in_window.train")
