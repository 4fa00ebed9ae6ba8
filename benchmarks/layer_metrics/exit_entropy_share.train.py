"""The entropy of the learned exit distribution as a share of the largest
it can be, in %, in the LAST step before `fit()` synchronised, which is the
window's last: the program's gauge `exit_entropy{layer=}` (the mean over
tokens of `-sum_t p_t log p_t`) over `ln(passes)`, the passes from the
gauge `loop_passes{model=}`. It says how far the gate has closed by the
window's end, not that it is live: four exits that start at (1/2, 1/4,
1/8, 1/8) read 87.5, and so do the three steps `correct` compares, but on
the cell's pool of 8 cycled batches the gate commits to one exit within
some fifteen steps of the configuration's Adam line (`assumed.
gate_over_the_window` in `configs/ouro_2_6b.json`), so a run's reading is
a few % or less and carries the seed's noise. A step costs the same at any
reading. No value where the program has no such gauges."""

import math

from benchmarks import kernel_counts


def read(facts):
    entropy = kernel_counts.gauges("exit_entropy")
    passes = kernel_counts.gauges("loop_passes")
    if not entropy or not passes or max(passes) < 2:
        return None
    return 100.0 * sum(entropy) / len(entropy) / math.log(max(passes))
