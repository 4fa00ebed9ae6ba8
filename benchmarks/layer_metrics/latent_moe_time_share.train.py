"""Share of device op time in the LatentMoE layers' own work, the
prediction module's expert layer with the trunk's: all seven scopes that
`parallel/moe.ExpertFeedForward` opens where it has a latent (`router`,
`latent_down`, `dispatch`, `experts_held`, `combine`, `latent_up`,
`shared_expert`), forward, recomputed and backward, at 22 experts a token
of 512. No value where no op carries `latent_down`: the other expert
models have `moe_time_share.train`, `grouped_moe_time_share.train` and
`topk_moe_time_share.train`."""

from benchmarks import kernel_counts

SCOPES = ("router", "latent_down", "dispatch", "experts_held", "combine",
          "latent_up", "shared_expert")


def read(facts, scopes=SCOPES):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    if kernel_counts.inner_share(facts["scopes"], ["latent_down"]) is None:
        return None
    return kernel_counts.inner_share(facts["scopes"], list(scopes))
