"""Share of device op time under the scope `attention_core`, which
`MultiHeadAttention` opens around softmax(q k^T) v alone (the projections,
norms, positions and the gate lie outside it), forward, recomputed and
backward, over the cell's chips. No value where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["attention_core"])
