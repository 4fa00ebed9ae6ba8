"""(token, expert) pairs that fell on an expert held and were not
computed, in the last step before `fit()` synchronised, all group-routed
expert layers together: `moe_pairs_dropped.train`'s reading
(`moe_pairs_dropped{layer=}`). The dispatch has no capacity, so this is 0
by construction, under group limits too, where a chip's load swings more;
the counter is there to say so of every run. No value where the program
has no `moe_tokens_held` gauge: no layer routes by groups."""

from benchmarks import harness, kernel_counts


def read(facts):
    if not kernel_counts.gauges("moe_tokens_held"):
        return None
    return harness.load_module("layer_metrics",
                               "moe_pairs_dropped.train.py").read(facts)
