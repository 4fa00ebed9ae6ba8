"""The entropy of the learned exit distribution as a share of the largest
it can be, in %, over every step of the window: the program's gauge
`exit_entropy_epoch_mean{layer=}` over `ln(passes)`, the passes from the
gauge `loop_passes{model=}`. `exit_entropy_share.train` reads the window's
LAST step, by which the gate has long closed on the cell's pool of 8
cycled batches; the mean says how long it stayed open. No value where the
program has no such gauges."""

import math

from benchmarks import kernel_counts


def read(facts):
    entropy = kernel_counts.gauges("exit_entropy_epoch_mean")
    passes = kernel_counts.gauges("loop_passes")
    if not entropy or not passes or max(passes) < 2:
        return None
    return 100.0 * sum(entropy) / len(entropy) / math.log(max(passes))
