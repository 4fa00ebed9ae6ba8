"""`ssm_time_share.train` in a model whose Mamba-2 layers have several
groups of B and C and a gated norm per group, one group held: the scope
`ssm_core`, the chunked scan alone, at 16 heads and chunks of 128. The
accepted reader itself, under a name of this cell's, because a metric's
cells are listed in its own `BENCHMARK.json` entry and an accepted entry
is not edited. A `benchmark` PR that appends the cell to that entry
deletes this file."""

from benchmarks import harness

read = harness.load_module("layer_metrics", "ssm_time_share.train.py").read
