"""`resnet50`: builds `zoo.ResNet50` through the program's public API."""

from __future__ import annotations

import dataclasses


def build(config: dict, seed: int):
    """The net as a quick-start user builds it, not yet initialised: the
    harness calls `init()` and then places the benchmark's own weights."""
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.optim.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    upd = config["updater"]
    if upd["rule"] != "nesterov":
        raise KeyError(f"this builder knows the nesterov rule, not {upd!r}")
    model = ResNet50(
        num_classes=config["label_shape"][-1],
        input_shape=tuple(config["input_shape"]),
        seed=0,   # of the program's own init, which the harness replaces
        updater=Nesterovs(upd["learning_rate"], upd["momentum"]))
    return ComputationGraph(
        dataclasses.replace(model.conf(), dtype=config["dtype"]))
