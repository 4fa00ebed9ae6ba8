"""`flash_attention_roofline.train` in a looped model, where every block
application calls the three flash kernels at the configuration's heads
and head size (32 calls of each a step where 8 blocks run 4 passes): the
accepted reader itself, under a name of this cell's, because a metric's
cells are listed in its own `BENCHMARK.json` entry and an accepted entry
is not edited. A `benchmark` PR that appends the cell to that entry
deletes this file."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "flash_attention_roofline.train.py").read
