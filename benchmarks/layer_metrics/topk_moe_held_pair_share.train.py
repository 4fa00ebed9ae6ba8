"""Of the (token, expert) pairs the routers chose in the last step before
`fit()` synchronised, the share, in %, that fell on experts held here,
all expert layers together, in a model with state-space mixers:
`moe_held_pair_share.train`'s reading (`moe_pairs_held{layer=}` over
`moe_pairs_routed{layer=}`). Uniform routing over 72 experts of which 9
are held gives 12.5. No value where the program has no `ssm_chunk_carry`
gauge: no layer is a `SelectiveStateSpace`."""

from benchmarks import harness, kernel_counts


def read(facts):
    if not kernel_counts.gauges("ssm_chunk_carry"):
        return None
    return harness.load_module("layer_metrics",
                               "moe_held_pair_share.train.py").read(facts)
