"""`moe_held_pair_share.train` in a model whose experts live in a latent:
of the (token, expert) pairs the routers chose in the last step before
`fit()` synchronised, the share, in %, on experts held here, all LatentMoE
layers together (the prediction module's with the trunk's). Uniform
routing over 512 experts of which 16 are held gives 3.125. The accepted
reader itself, under a name of this cell's, as
`grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "moe_held_pair_share.train.py").read
