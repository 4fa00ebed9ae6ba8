"""Share of device op time in the routed path of the LatentMoE layers:
the scopes `dispatch` (the sort of the pairs and the gather of the held
pairs' latent rows), `experts_held` (the grouped products and the relu2
between them, over the tier's rows) and `combine` (the weighted sum back
to the tokens), forward, recomputed and backward. With 16 of 512 experts
held it is the sort, the gathers and the pass over the tier that cost,
not the products. No value where no op carries `latent_down`."""

from benchmarks import harness


def read(facts):
    return harness.load_module(
        "layer_metrics", "latent_moe_time_share.train.py").read(
            facts, ("dispatch", "experts_held", "combine"))
