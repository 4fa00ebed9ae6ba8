"""`ssm_chunk_carry_share.train` in a model whose Mamba-2 layers have
several groups of B and C, one group held: how much of a state survives
one chunk (128 tokens here), in %, the mean over the five mixers of the
gauge `ssm_chunk_carry{layer=}`. The accepted reader itself, under a name
of this cell's, as `grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "ssm_chunk_carry_share.train.py").read
