"""Share of device op time under the scopes `latent_down` and
`latent_up`: the two dense projections between the model's width and the
latent the routed experts live in (4,096 to 1,024 and back), which every
token pays whole whatever was routed here, forward, recomputed and
backward. No value where no op carries `latent_down`."""

from benchmarks import harness


def read(facts):
    return harness.load_module(
        "layer_metrics", "latent_moe_time_share.train.py").read(
            facts, ("latent_down", "latent_up"))
