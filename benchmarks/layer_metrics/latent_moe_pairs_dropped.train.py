"""`moe_pairs_dropped.train` in a model whose experts live in a latent:
(token, expert) pairs that fell on an expert held and were not computed,
all LatentMoE layers together. The dispatch has no capacity, so this is 0
by construction, at 180,224 pairs a layer too. The accepted reader itself,
under a name of this cell's, as `grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics", "moe_pairs_dropped.train.py").read
