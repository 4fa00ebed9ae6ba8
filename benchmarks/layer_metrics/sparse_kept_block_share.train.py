"""Of the block visits (token x KV group x block of keys) a dense causal
layer would make, the share, in %, that the selection kept in the last
step before `fit()` synchronised, all selecting layers together: the
program's gauges `sparse_blocks_kept{layer=}` over
`sparse_blocks_causal{layer=}`. 64 blocks of up to 256 at 16,384 tokens
give 43.7. Higher is better: a change that visits fewer blocks than the
selection asks reads as worse. No value where the program has no such
gauge."""

from benchmarks import kernel_counts


def read(facts):
    kept = kernel_counts.gauges("sparse_blocks_kept")
    causal = sum(kernel_counts.gauges("sparse_blocks_causal"))
    return 100.0 * sum(kept) / causal if kept and causal else None
