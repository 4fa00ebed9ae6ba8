"""Of the rows of the tier an expert layer's grouped products ran over,
the share, in %, that the products multiplied, over every step of the
window and all expert layers together: the program's gauges
`moe_rows_visited_epoch_mean{layer=}` over
`moe_rows_tier_epoch_mean{layer=}`. 100 says the products walk the whole tier whatever fell into
it. `topk_moe_rows_visited_share.train` and its alias read the window's
last step. No value where the program has no such gauge."""

from benchmarks import kernel_counts


def read(facts):
    visited = kernel_counts.gauges("moe_rows_visited_epoch_mean")
    tier = sum(kernel_counts.gauges("moe_rows_tier_epoch_mean"))
    return 100.0 * sum(visited) / tier if visited and tier else None
