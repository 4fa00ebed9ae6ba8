"""Of the rows of the tier an expert layer ran in the last step before
`fit()` synchronised, the share, in %, that its gather moved, all expert
layers together, in a model with state-space mixers: the program's gauges
`moe_rows_gathered{layer=}` (the row tiles that hold a pair times a tile's
rows where `ops/row_gather.take_rows` runs; the tier wherever XLA's gather
does) over `moe_rows_tier{layer=}`. 100 says the gathers walk the whole
tier whatever fell into it. No value where the program has no such gauge
(a program before PR 44) or no `ssm_chunk_carry` gauge: no layer is a
`SelectiveStateSpace`."""

from benchmarks import kernel_counts


def read(facts):
    if not kernel_counts.gauges("ssm_chunk_carry"):
        return None
    gathered = kernel_counts.gauges("moe_rows_gathered")
    tier = sum(kernel_counts.gauges("moe_rows_tier"))
    return 100.0 * sum(gathered) / tier if gathered and tier else None
