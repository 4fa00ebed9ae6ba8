"""(token, expert) pairs that fell on an expert held and were not
computed, in the last step before `fit()` synchronised, all expert layers
of a model with state-space mixers together: `moe_pairs_dropped.train`'s
reading (`moe_pairs_dropped{layer=}`). The dispatch has no capacity, so
this is 0 by construction, at 81,920 pairs a layer too; the counter is
there to say so of every run. No value where the program has no
`ssm_chunk_carry` gauge: no layer is a `SelectiveStateSpace`."""

from benchmarks import harness, kernel_counts


def read(facts):
    if not kernel_counts.gauges("ssm_chunk_carry"):
        return None
    return harness.load_module("layer_metrics",
                               "moe_pairs_dropped.train.py").read(facts)
