"""`nemotron_3_super`: builds `zoo.HybridLatentExpertTransformer` through
the program's public API from the configuration's published keys, as the
one chip's share the file describes: `heads_held` of every `M` layer's
heads (whole groups of B and C), `attention_heads_held` and
`kv_heads_held` of every `*` layer's, `experts_held` of every `E` layer's
experts and `vocabulary_held` rows of the embedding and columns of the
head, the prediction module's sublayers cut alike."""

from __future__ import annotations


def build(config: dict, seed: int):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.zoo import HybridLatentExpertTransformer

    upd = config["updater"]
    if upd["rule"] != "adam":
        raise KeyError(f"this builder knows the adam rule, not {upd!r}")
    return MultiLayerNetwork(HybridLatentExpertTransformer(
        config, timesteps=config["input_shape"][0],
        heads_held=tuple(config["heads_held"]),
        attention_heads_held=tuple(config["attention_heads_held"]),
        kv_heads_held=tuple(config["kv_heads_held"]),
        experts_held=tuple(config["experts_held"]),
        vocabulary_held=config["vocabulary_held"], dtype=config["dtype"],
        gradient_checkpointing=config["gradient_checkpointing"],
        seed=0,   # of the program's own init, which the harness replaces
        updater=Adam(upd["learning_rate"], upd["beta1"], upd["beta2"],
                     upd["epsilon"])).conf())
