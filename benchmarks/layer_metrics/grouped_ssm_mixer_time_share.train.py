"""`ssm_mixer_time_share.train` in a model whose Mamba-2 layers have
several groups of B and C, one group held: the scope `ssm_mixer` round the
whole mixer (`in_proj`, the convolution, the scan, the per-group gated
norm, `out_proj`). The accepted reader itself, under a name of this
cell's, as `grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "ssm_mixer_time_share.train.py").read
