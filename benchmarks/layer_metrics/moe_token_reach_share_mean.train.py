"""Of a step's tokens, the share, in %, with at least one pair on an
expert held here, the mean over the group-routed expert layers and over
every step of the window: the program's gauges
`moe_tokens_held_epoch_mean{layer=}` over the tokens a chip's batch holds.
`grouped_moe_token_reach_share.train` reads the window's last step (and
says what the share means). No value where the program has no such gauge:
a program before PR 52, or no layer that routes by groups."""

from benchmarks import kernel_counts


def read(facts):
    held = kernel_counts.gauges("moe_tokens_held_epoch_mean")
    run = facts["run"]
    tokens = (run.get("tokens_per_item", 0) * run["global_batch"]
              // run["chips"])
    return 100.0 * sum(held) / (len(held) * tokens) if held and tokens \
        else None
