"""`attention_time_share.train` in a looped model: the scope
`attention_core` in every application of every block, forward, recomputed
and backward. The accepted reader itself, under a name of this cell's,
because a metric's cells are listed in its own `BENCHMARK.json` entry and
an accepted entry is not edited. A `benchmark` PR that appends the cell to
that entry deletes this file."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "attention_time_share.train.py").read
