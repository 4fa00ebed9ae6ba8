"""Of a step's tokens, the share, in %, with at least one pair on an
expert held here, averaged over the group-routed expert layers, in the
last step before `fit()` synchronised: the program's gauges
`moe_tokens_held{layer=}` over the tokens a chip's batch holds. It is what
the exchange would send this chip, which group limits exist to bound: a
token reaches this chip only if group 0 is among its three. Uniform
routing gives 22.3 (3/8 of the tokens keep the group, and 59% of those
choose one of its 8 held among their 6 of 60). No value where the program has no
such gauge."""

from benchmarks import kernel_counts


def read(facts):
    held, run = kernel_counts.gauges("moe_tokens_held"), facts["run"]
    tokens = (run.get("tokens_per_item", 0) * run["global_batch"]
              // run["chips"])
    return 100.0 * sum(held) / (len(held) * tokens) if held and tokens \
        else None
