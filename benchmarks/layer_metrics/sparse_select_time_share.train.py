"""Share of device op time under the scope `sparse_select`, which a
`MultiHeadAttention` with a block selection opens around the choice of
key blocks (compressed keys, their scores, the pooling to blocks and the
top-k), forward and recomputed: the choice has no backward. No value
where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["sparse_select"])
