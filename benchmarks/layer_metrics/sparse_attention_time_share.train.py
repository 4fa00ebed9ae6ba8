"""Share of device op time under the scope `sparse_attention_core`, which
a `MultiHeadAttention` with a block selection opens around the attention
over the blocks kept (the three block-sparse kernels and what feeds
them), forward and backward. No value where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"],
                                     ["sparse_attention_core"])
