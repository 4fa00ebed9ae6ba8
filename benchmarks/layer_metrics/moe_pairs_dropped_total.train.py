"""(token, expert) pairs that fell on an expert held and were not
computed, summed over EVERY step of the window and all expert layers: the
program's gauges `moe_pairs_dropped_epoch_mean{layer=}` times the steps
the window handed out (it is one epoch). The dispatch has no capacity, so
this is 0 by construction; `moe_pairs_dropped.train` and its aliases say
so of the window's last step alone. No value where the program has no
such gauge."""

from benchmarks import kernel_counts


def read(facts):
    dropped = kernel_counts.gauges("moe_pairs_dropped_epoch_mean")
    return sum(dropped) * facts["run"]["steps"] if dropped else None
