"""Share of device op time under the scopes `exit_loss` and `exit_gate`,
which `ExitGatedOutputLayer` opens inside its `loss` scope round the head's
products with every exit's cross-entropy and round the gate's products
with the exit distribution: what scoring every pass costs beside scoring
one (15.6% of the multiply-adds where four exits read a head of 49,152
behind 8 blocks).
No value where no op carries either scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"],
                                     ["exit_loss", "exit_gate"])
