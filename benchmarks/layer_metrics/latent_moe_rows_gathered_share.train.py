"""`topk_moe_rows_gathered_share.train` in a model whose experts live in a
latent: of the rows of the tier (`moe_rows_tier{layer=}`), the share, in
%, that the layer's gather moved (`moe_rows_gathered{layer=}`: the row
tiles that hold a pair, where `ops/row_gather.take_rows` runs), all
LatentMoE layers together; the rows are 1,024 wide here and gathered once
(a relu2 expert has one first matrix). The accepted reader itself, under a
name of this cell's, as `grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "topk_moe_rows_gathered_share.train.py").read
