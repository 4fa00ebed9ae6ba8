"""Share of device op time in the expert layers' own work in a model
whose other half is a state-space mixer: `moe_time_share.train`'s scopes
(`router`, `dispatch`, `experts_held`, `combine`, `shared_expert`, which
`parallel/moe.ExpertFeedForward` opens), forward, recomputed and backward,
at 10 experts a token of 72. No value where no op carries `ssm_mixer`:
`moe_time_share.train` and `grouped_moe_time_share.train` are the other
models' metrics."""

from benchmarks import harness, kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    if kernel_counts.inner_share(facts["scopes"], ["ssm_mixer"]) is None:
        return None
    return harness.load_module("layer_metrics",
                               "moe_time_share.train.py").read(facts)
