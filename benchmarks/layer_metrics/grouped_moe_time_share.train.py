"""Share of device op time in the group-routed expert layers' own work:
`moe_time_share.train`'s scopes (`router`, with `group_select` inside it,
`dispatch`, `experts_held`, `combine`, `shared_expert`, which
`parallel/moe.ExpertFeedForward` opens), forward, recomputed and backward.
No value where no op carries `group_select`: the layers route flat, and
`moe_time_share.train` is their metric."""

from benchmarks import harness, kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    if kernel_counts.inner_share(facts["scopes"], ["group_select"]) is None:
        return None
    return harness.load_module("layer_metrics",
                               "moe_time_share.train.py").read(facts)
