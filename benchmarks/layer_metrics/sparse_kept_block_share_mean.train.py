"""Of the block visits (token x KV group x block of keys) a dense causal
layer would make, the share, in %, that the selection kept, over every
step of the window and all selecting layers together: the program's gauges
`sparse_blocks_kept_epoch_mean{layer=}` over
`sparse_blocks_causal_epoch_mean{layer=}`. `sparse_kept_block_share.train`
reads the window's last step. No value where the program has no such
gauge."""

from benchmarks import kernel_counts


def read(facts):
    kept = kernel_counts.gauges("sparse_blocks_kept_epoch_mean")
    causal = sum(kernel_counts.gauges("sparse_blocks_causal_epoch_mean"))
    return 100.0 * sum(kept) / causal if kept and causal else None
