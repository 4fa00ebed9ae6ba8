"""`ouro_2_6b`: builds `zoo.LoopedSandwichTransformer` through the
program's public API from the configuration's published keys, as the one
pipeline stage the file describes: `num_hidden_layers` blocks run
`total_ut_steps` times over shared weights, the whole vocabulary, the exit
gate and the expected loss over the exits."""

from __future__ import annotations


def build(config: dict, seed: int):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.zoo import LoopedSandwichTransformer

    upd = config["updater"]
    if upd["rule"] != "adam":
        raise KeyError(f"this builder knows the adam rule, not {upd!r}")
    if config["vocabulary_held"] != config["vocab_size"]:
        raise KeyError("this configuration holds the whole vocabulary")
    return MultiLayerNetwork(LoopedSandwichTransformer(
        config, timesteps=config["input_shape"][0], dtype=config["dtype"],
        gradient_checkpointing=config["gradient_checkpointing"],
        seed=0,   # of the program's own init, which the harness replaces
        updater=Adam(upd["learning_rate"], upd["beta1"], upd["beta2"],
                     upd["epsilon"])).conf())
