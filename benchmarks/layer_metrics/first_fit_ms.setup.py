"""The FIRST `fit` root of the process: the warm-up `fit()` of the
traffic mix's `warmup_steps` steps, with the step's build, trace,
lowering, compile or fetch and probe inside its first dispatch. No value
from a program that does not time its set-up."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("first_fit_ms.setup")
