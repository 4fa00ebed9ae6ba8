"""Of the (token, expert) pairs the routers chose, the share, in %, that
fell on experts held here, over EVERY step of the window and all expert
layers together: the program's gauges `moe_pairs_held_epoch_mean{layer=}`
over `moe_pairs_routed_epoch_mean{layer=}`, which `fit()` publishes from
sums the step keeps on the device (PR 52). The window is one epoch, so the
epoch's mean is the window's. `moe_held_pair_share.train` and its three
aliases read the window's LAST step. No value where the program has no
such gauge (a program before PR 52, or no expert layer)."""

from benchmarks import kernel_counts


def read(facts):
    held = kernel_counts.gauges("moe_pairs_held_epoch_mean")
    routed = sum(kernel_counts.gauges("moe_pairs_routed_epoch_mean"))
    return 100.0 * sum(held) / routed if held and routed else None
