"""Set-up's `net.init` spans and the wrapper's `wrapper.init`: building the
net's state. The harness calls `net.init()` inside a `jax.jit`, so in a
cell this is the time to TRACE `init()` (and lies inside that program's
`xla.trace`), plus, under `ParallelWrapper`, mesh, shardings and the
state's placement. No value from a program without the spans."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("init_ms.setup")
