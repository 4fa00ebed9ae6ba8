"""The latent-attention kernels' share of the bf16 peak (or of the HBM
peak, were that nearer): `latent_attention_fwd`, `latent_attention_bwd_dq`
and `latent_attention_bwd_dkdv` together, operations and bytes from
`latent_counts.latent_attention_calls` over the causal pairs of the heads
held, seconds and calls from the trace. No value where none of them ran."""

from benchmarks import latent_counts


def read(facts):
    return latent_counts.latent_attention_roofline(facts)
