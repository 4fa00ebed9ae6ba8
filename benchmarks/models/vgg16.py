"""`vgg16`: builds `zoo.VGG16` through the program's public API. The zoo
model drops 0.5 before both 4096-wide layers, as the paper does; the
configuration's `dropout` (listed under `reduced`, with the reason) puts
that probability at 0, which takes the two masks out of the step."""

from __future__ import annotations

import dataclasses


def build(config: dict, seed: int):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import VGG16

    upd = config["updater"]
    if upd["rule"] != "nesterov":
        raise KeyError(f"this builder knows the nesterov rule, not {upd!r}")
    conf = VGG16(
        num_classes=config["label_shape"][-1],
        input_shape=tuple(config["input_shape"]),
        seed=0,   # of the program's own init, which the harness replaces
        updater=Nesterovs(upd["learning_rate"], upd["momentum"])).conf()
    layers = tuple(dataclasses.replace(
        layer, dropout=config["dropout"] or None)
        if layer.dropout else layer for layer in conf.layers)
    return MultiLayerNetwork(dataclasses.replace(
        conf, layers=layers, dtype=config["dtype"]))
