"""Of the rows of the tier an expert layer's grouped products ran over in
the last step before `fit()` synchronised, the share, in %, that the
products multiplied, all expert layers together, in a model with
state-space mixers: the program's gauges `moe_rows_visited{layer=}` (the
row tiles the kernel's schedule visits times a tile's rows; the tier where
every row is in a group or `ragged_dot` runs) over `moe_rows_tier{layer=}`.
100 says the products walk the whole tier whatever fell into it. No value
where the program has no such gauge (a program before PR 43) or no
`ssm_chunk_carry` gauge: no layer is a `SelectiveStateSpace`."""

from benchmarks import kernel_counts


def read(facts):
    if not kernel_counts.gauges("ssm_chunk_carry"):
        return None
    visited = kernel_counts.gauges("moe_rows_visited")
    tier = sum(kernel_counts.gauges("moe_rows_tier"))
    return 100.0 * sum(visited) / tier if visited and tier else None
