"""The block-sparse attention kernels' share of the bf16 peak (or of the
HBM peak, were that nearer): `sparse_attention_fwd`,
`sparse_attention_bwd_dq` and `sparse_attention_bwd_dkdv` together,
operations and bytes from `sparse_counts.sparse_attention_calls` over the
pairs of the blocks the selection keeps, seconds and calls from the
trace. No value where none of them ran."""

from benchmarks import sparse_counts


def read(facts):
    return sparse_counts.sparse_attention_roofline(facts)
