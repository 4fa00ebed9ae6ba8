"""Share of device op time under the scope `linear_attention_core`, which
`LinearAttention` opens around the chunked scan of the decayed linear
attention alone (projections, norms, positions, output norm and gate lie
outside it), forward, recomputed and backward. No value where no op
carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"],
                                     ["linear_attention_core"])
