"""Share of device op time under the scope `ssm_core`, which
`SelectiveStateSpace` opens round `ops/selective_scan.py`'s chunked scan
alone (the cumulative decay, the chunk's masked `C B^T` product, the
state's scan over the chunks, the skip; the projections, the convolution
and the gated norm lie outside it), forward, recomputed and backward. No
value where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["ssm_core"])
