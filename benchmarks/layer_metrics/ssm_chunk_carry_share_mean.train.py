"""How much of a Mamba-2 state survives one chunk, in %, over every step
of the window: the mean over the state-space layers of the program's gauge
`ssm_chunk_carry_epoch_mean{layer=}`. `ssm_chunk_carry_share.train` and
its alias read the window's last step (and say what the share means). No
value where the program has no such gauge."""

from benchmarks import kernel_counts


def read(facts):
    carried = kernel_counts.gauges("ssm_chunk_carry_epoch_mean")
    return 100.0 * sum(carried) / len(carried) if carried else None
