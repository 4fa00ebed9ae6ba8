"""Share of device op time under the scope `mtp`, which
`MultiTokenOutputLayer` opens inside its `loss` scope round the
multi-token-prediction module: the second lookup of the embedding's table,
both norms and `W_eh`, the module's attention and expert sublayers, its
last norm, the head's second pass and its cross-entropy, forward,
recomputed and backward. No value where no op carries that scope."""

from benchmarks import kernel_counts


def read(facts):
    if facts["trace"] is None or not facts["scopes"]:
        return None
    return kernel_counts.inner_share(facts["scopes"], ["mtp"])
