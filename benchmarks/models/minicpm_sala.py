"""`minicpm_sala`: builds `zoo.HybridLinearSparseTransformer` through the
program's public API from the configuration's published keys, as the one
pipeline stage's share the file describes: the published model's first
`num_hidden_layers` layers and `vocabulary_held` rows of the embedding and
the head."""

from __future__ import annotations


def build(config: dict, seed: int):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.zoo import HybridLinearSparseTransformer

    upd = config["updater"]
    if upd["rule"] != "adam":
        raise KeyError(f"this builder knows the adam rule, not {upd!r}")
    return MultiLayerNetwork(HybridLinearSparseTransformer(
        config, timesteps=config["input_shape"][0],
        vocabulary_held=config["vocabulary_held"],
        layers_published=config.get("published", {}).get(
            "num_hidden_layers"),
        dtype=config["dtype"],
        gradient_checkpointing=config["gradient_checkpointing"],
        seed=0,   # of the program's own init, which the harness replaces
        updater=Adam(upd["learning_rate"], upd["beta1"], upd["beta2"],
                     upd["epsilon"])).conf())
