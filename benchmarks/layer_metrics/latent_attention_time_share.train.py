"""Share of device op time under the scope `latent_attention_core`, which
a `LatentAttention` opens around its core alone (the three latent-attention
kernels and the layouts that feed them), forward and backward. No value
where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"],
                                     ["latent_attention_core"])
