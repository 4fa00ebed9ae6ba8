"""Share of device op time under the scope `latent_projections`, which a
`LatentAttention` opens around everything but its core: both low-rank
paths, their norms, the rotary positions and the output projection,
forward, recomputed and backward. No value where no op carries that
scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["latent_projections"])
