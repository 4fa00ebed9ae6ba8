"""`embedding_time_share.train` in a model that reads the embedding's
table twice a step: the share of device op time under the FIRST layer's
scope, the trunk's lookup and its half of the table's gradient
(`ops/embedding.py`'s grouped product over the sorted ids). The second
lookup, of the labels, and its half of the gradient lie under the
prediction module's scope and in `mtp_time_share.train`. The accepted
reader itself, under a name of this cell's, as
`grouped_ssm_time_share.train.py` says."""

from benchmarks import harness

read = harness.load_module("layer_metrics",
                           "embedding_time_share.train.py").read
