"""Share of device op time in the expert layers' own work: the scopes
`router`, `dispatch`, `experts_held`, `combine` and `shared_expert` that
`parallel/moe.ExpertFeedForward` opens, forward, recomputed and backward.
No value where no op carries one of them."""

from benchmarks import kernel_counts

SCOPES = ["router", "dispatch", "experts_held", "combine", "shared_expert"]


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], SCOPES)
