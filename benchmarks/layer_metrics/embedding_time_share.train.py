"""Share of device op time under the first layer's scope where that layer
is an `EmbeddingSequenceLayer` (`layer0_embeddingsequencelayer`, the name
`MultiLayerNetwork` gives it): the lookup forward and the table's gradient
backward, which XLA's scatter-add made the longest single op of
`deepseek_v2_fit` (4.6% of the step, PR 47) and `ops/embedding.py`'s
grouped product over the ids sorted by vocabulary tile replaces. What a
tied head adds to the same leaf lies under the head's `loss` scope, not
here. No value where no op carries that scope (an image net, a net whose
first layer is another)."""

SCOPE = "layer0_embeddingsequencelayer"


def read(facts):
    scopes = facts["scopes"] or {}
    if facts["trace"] is None or SCOPE not in scopes:
        return None
    total = sum(row["s"] for row in scopes.values())
    return 100.0 * scopes[SCOPE]["s"] / total if total else None
