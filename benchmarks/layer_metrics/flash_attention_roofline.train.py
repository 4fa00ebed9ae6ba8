"""The full-context flash kernels' share of the bf16 peak (or of the HBM
peak, were that nearer): `flash_attention_fwd`, `flash_attention_bwd_dq`
and `flash_attention_bwd_dkdv` of the `full_attention` layers together,
operations and bytes from `kernel_counts.attention_calls` over the causal
pairs, seconds and calls from the trace. No value where none of them ran
or the configuration has no such layer."""

from benchmarks import kernel_counts


def read(facts):
    return kernel_counts.attention_roofline(facts, "full_attention",
                                            "flash_attention")
