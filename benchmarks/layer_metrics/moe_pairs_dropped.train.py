"""(token, expert) pairs that fell on an expert held and were not
computed, in the last step before `fit()` synchronised, all expert layers
together: the program's gauges `moe_pairs_dropped{layer=}`. The dispatch
has no capacity, so this is 0 by construction; the counter is there to
say so of every run. No value where the program has no such gauge."""

from benchmarks import kernel_counts


def read(facts):
    values = kernel_counts.gauges("moe_pairs_dropped")
    return sum(values) if values else None
