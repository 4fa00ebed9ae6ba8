"""Layer base class + registry.

Reference parity: `nn/api/Layer.java:70-310` (activate / backpropGradient /
preOutput) and `nn/conf/layers/Layer.java` (config base with cascaded
activation/weightInit/updater/l1/l2/dropout — see
`NeuralNetConfiguration.Builder`, reference `nn/conf/NeuralNetConfiguration.java:515`).

Differences by design (TPU-first):
- No `backpropGradient`: gradients come from `jax.grad` of the whole network.
- No mutable layer objects: `apply` is pure; BN running stats etc. live in an
  explicit `state` pytree returned alongside activations.
- `dropout` here is the DROP probability (modern convention), not the
  reference's retain probability; inverted dropout scaling matches either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.initializers import WeightInit
from deeplearning4j_tpu.nn.inputs import InputType

LAYER_REGISTRY: Dict[str, type] = {}

Params = Dict[str, jax.Array]
State = Dict[str, jax.Array]


def register_layer(cls):
    """Register a layer class for config serde + custom-layer plug-ins
    (reference seam: custom layer tests `nn/layers/custom/`)."""
    LAYER_REGISTRY[cls.__name__] = cls
    from deeplearning4j_tpu.utils.serde import register_serde

    return register_serde(cls)


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base layer config/impl. All fields optional → cascaded from the global
    builder defaults at build() time (reference: config cloning in
    `MultiLayerConfiguration.Builder`)."""

    # the layer looks its input up as integer ids (an embedding): the
    # models hand such an input on in its own integer dtype
    TAKES_IDS = False

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    updater: Optional[Any] = None          # per-layer updater override
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    dropout: Optional[float] = None        # drop probability (see module doc)
    learning_rate: Optional[Any] = None    # per-layer LR override
    bias_init: Optional[float] = None
    frozen: bool = False                   # transfer-learning freeze flag

    # ---- wiring API ----
    def with_defaults(self, **defaults) -> "Layer":
        """Fill None fields from global defaults (config cascade)."""
        updates = {
            k: v for k, v in defaults.items()
            if v is not None
            and k in {f.name for f in dataclasses.fields(self)}
            and getattr(self, k) is None
        }
        return dataclasses.replace(self, **updates) if updates else self

    def infer_n_in(self, input_type: InputType) -> "Layer":
        """Set n_in-like fields from the incoming InputType (reference:
        `setInputType`/`getPreProcessorForInputType` auto-wiring)."""
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    # ---- runtime API (pure) ----
    def init_params(self, key, input_type: InputType, dtype=jnp.float32
                    ) -> Tuple[Params, State]:
        return {}, {}

    def apply(self, params: Params, x, *, state: Optional[State] = None,
              train: bool = False, rng=None, mask=None) -> Tuple[Any, State]:
        raise NotImplementedError

    # ---- shared helpers ----
    def _act(self, x):
        return Activation.get(self.activation)(x)

    def _winit(self):
        return WeightInit.get(self.weight_init)

    def _maybe_dropout(self, x, train: bool, rng):
        """Inverted dropout on the INPUT activations (reference:
        `BaseLayer.java:535` applyDropOutIfNecessary before preOutput)."""
        p = self.dropout
        if not train or not p or rng is None:
            return x
        keep = 1.0 - p
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)

    def regularization(self, params: Params) -> jax.Array:
        """L1/L2 penalty contribution (reference: `calcL1()`/`calcL2()` summed
        into score in computeGradientAndScore). Bias params get the separate
        l1_bias/l2_bias coefficients, like the reference."""
        total = jnp.asarray(0.0, jnp.float32)
        for k, v in params.items():
            is_bias = k in ("b", "beta", "bias")
            l1 = (self.l1_bias if is_bias else self.l1) or 0.0
            l2 = (self.l2_bias if is_bias else self.l2) or 0.0
            if l1:
                total = total + l1 * jnp.sum(jnp.abs(v))
            if l2:
                total = total + 0.5 * l2 * jnp.sum(jnp.square(v))
        return total

    @property
    def is_output_layer(self) -> bool:
        return False

    @property
    def is_pretrainable(self) -> bool:
        """Layerwise-pretrainable (reference: AutoEncoder/RBM/VAE pretrain)."""
        return False
