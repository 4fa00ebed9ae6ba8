"""How much of a Mamba-2 state survives one chunk, in %, in the last step
before `fit()` synchronised: the mean over the state-space layers of the
program's gauge `ssm_chunk_carry{layer=}`, itself the mean over heads and
chunks of `exp(sum of dt A over the chunk)`. It says whether the cell's
scan carries anything between chunks (a trained model's does): at 0 the
state's scan and its backward are arithmetic on zeros. No value where the
program has no such gauge."""

from benchmarks import kernel_counts


def read(facts):
    carried = kernel_counts.gauges("ssm_chunk_carry")
    return 100.0 * sum(carried) / len(carried) if carried else None
