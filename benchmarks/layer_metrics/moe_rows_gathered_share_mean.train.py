"""Of the rows of the tier an expert layer ran, the share, in %, that its
gather moved, over every step of the window and all expert layers
together: the program's gauges `moe_rows_gathered_epoch_mean{layer=}` over
`moe_rows_tier_epoch_mean{layer=}`. `topk_moe_rows_gathered_share.train`
and its alias read the window's last step. No value where the program has
no such gauge."""

from benchmarks import kernel_counts


def read(facts):
    gathered = kernel_counts.gauges("moe_rows_gathered_epoch_mean")
    tier = sum(kernel_counts.gauges("moe_rows_tier_epoch_mean"))
    return 100.0 * sum(gathered) / tier if gathered and tier else None
