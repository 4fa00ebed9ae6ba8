"""Neural-network core: configs, layers, activations, losses, initializers.

Reference parity: deeplearning4j-nn (`nn/conf`, `nn/layers`, `nn/weights`,
`nn/api`). Everything here is config-as-data (JSON-serializable dataclasses)
plus pure functions over pytrees — no mutable layer objects, so the whole
forward/backward compiles to a single XLA computation.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.nn"):
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.losses import LossFunction
    from deeplearning4j_tpu.nn.initializers import WeightInit

__all__ = ["InputType", "Activation", "LossFunction", "WeightInit"]
