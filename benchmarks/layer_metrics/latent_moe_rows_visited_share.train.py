"""`topk_moe_rows_visited_share.train` in a model whose experts live in a
latent: of the rows of the tier a layer's grouped products ran over
(`moe_rows_tier{layer=}`, 131,072 a layer: all that can fall on 16 experts
held), the share, in %, that the products multiplied
(`moe_rows_visited{layer=}`), all LatentMoE layers together. Some 5 where
5,632 pairs land on 16 groups. The accepted reader itself (it asks for an
`ssm_chunk_carry` gauge, which this model's mixers publish), under a name
of this cell's, as `grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "topk_moe_rows_visited_share.train.py").read
