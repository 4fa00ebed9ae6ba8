"""Of the (token, expert) pairs the routers chose in the last step before
`fit()` synchronised, the share, in %, that fell on experts held here,
all expert layers together: the program's gauges `moe_pairs_held{layer=}`
over `moe_pairs_routed{layer=}`. Uniform routing over 256 experts of
which 8 are held gives 3.1. No value where the program has no such
gauge."""

from benchmarks import kernel_counts


def read(facts):
    held = kernel_counts.gauges("moe_pairs_held")
    routed = sum(kernel_counts.gauges("moe_pairs_routed"))
    return 100.0 * sum(held) / routed if held and routed else None
