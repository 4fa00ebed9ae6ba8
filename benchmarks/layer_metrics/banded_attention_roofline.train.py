"""The banded kernels' share of the bf16 peak (or of the HBM peak, were
that nearer): `banded_attention_fwd`, `banded_attention_bwd_dq` and
`banded_attention_bwd_dkdv` of the `sliding_attention` layers together,
operations and bytes from `kernel_counts.attention_calls` over the pairs
inside the window, seconds and calls from the trace. No value where none
of them ran or the configuration has no such layer."""

from benchmarks import kernel_counts


def read(facts):
    return kernel_counts.attention_roofline(facts, "sliding_attention",
                                            "banded_attention")
