"""`tokens_tiny`: the smallest token model, through the program's public
API: an embedding, `hidden_layers` tanh layers (1 where the configuration
names none) applied at every time step, and a softmax over the vocabulary
scored with integer labels (`sparse_mcxent`). The rehearsal of the
`token_stream` generator and the `adam` rule; wide and deep enough
(`tests/configs/tokens_wide.json`) it is a model whose bytes are weights.

The hidden layer is a `Convolution1DLayer` of kernel 1: the program's
`DenseLayer` after a sequence layer gets only the last time step
(`RnnToFeedForward`). Every layer names its `n_in` and no input type is
declared, as DL4J allows: declared as `InputType.recurrent(1, T)` the
program's input check wants ids as `[B, T, 1]` and refuses the
generator's `[B, T]`."""

from __future__ import annotations


def build(config: dict, seed: int):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.convolution import Convolution1DLayer
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer,
    )
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.optim.updaters import Adam

    upd = config["updater"]
    if upd["rule"] != "adam":
        raise KeyError(f"this builder knows the adam rule, not {upd!r}")
    conf = (NeuralNetConfiguration.builder()
            .seed(0)   # of the program's own init, which the harness replaces
            .updater(Adam(upd["learning_rate"], upd["beta1"], upd["beta2"],
                          upd["epsilon"]))
            .dtype(config["dtype"])
            .list(EmbeddingSequenceLayer(n_in=config["vocabulary_held"],
                                         n_out=config["width"],
                                         activation="identity"),
                  *[Convolution1DLayer(n_in=config["width"],
                                       n_out=config["width"], kernel=1,
                                       activation="tanh")
                    for _ in range(config.get("hidden_layers", 1))],
                  RnnOutputLayer(n_in=config["width"],
                                 n_out=config["vocabulary_held"],
                                 activation="softmax", loss="sparse_mcxent"))
            .build())
    return MultiLayerNetwork(conf)
