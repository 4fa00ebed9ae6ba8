"""Of the (token, expert) pairs the group-limited routers chose in the
last step before `fit()` synchronised, the share, in %, that fell on
experts held here, all expert layers together: `moe_held_pair_share.train`'s
reading (`moe_pairs_held{layer=}` over `moe_pairs_routed{layer=}`). Uniform
routing over 160 experts of which 8 are held gives 5. No value where the
program has no `moe_tokens_held` gauge: no layer routes by groups."""

from benchmarks import harness, kernel_counts


def read(facts):
    if not kernel_counts.gauges("moe_tokens_held"):
        return None
    return harness.load_module("layer_metrics",
                               "moe_held_pair_share.train.py").read(facts)
