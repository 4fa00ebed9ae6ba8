"""Activation functions.

Reference parity: ND4J `IActivation` implementations as consumed by DL4J layer
configs (`nn/conf/NeuralNetConfiguration.java:781-795` sets a default
activation cascaded into every layer). The reference computes activations as
separate eager ops; here each is a pure jax function fused by XLA into the
surrounding matmul, so there is no separate "activation kernel" cost on TPU.

All functions take and return arrays of any shape and are differentiable via
`jax.grad` — the reference's hand-written `backprop(in, epsilon)` methods are
unnecessary under autodiff.
"""

from __future__ import annotations

from typing import Callable, Dict, Union

import jax
import jax.numpy as jnp


def identity(x):
    return x


def relu(x):
    return jax.nn.relu(x)


def relu6(x):
    return jax.nn.relu6(x)


def leakyrelu(x, alpha: float = 0.01):
    return jax.nn.leaky_relu(x, negative_slope=alpha)


def elu(x, alpha: float = 1.0):
    return jax.nn.elu(x, alpha=alpha)


def selu(x):
    return jax.nn.selu(x)


def gelu(x):
    return jax.nn.gelu(x)


def silu(x):
    return jax.nn.silu(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def hardsigmoid(x):
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def tanh(x):
    return jnp.tanh(x)


def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


def rationaltanh(x):
    # Reference: ND4J ActivationRationalTanh — a cheap tanh approximation
    # 1.7159 * tanh_approx(2x/3) where tanh_approx clips via a rational poly.
    a = 0.6666667 * x
    abs_a = jnp.abs(a)
    approx = jnp.sign(a) * (1.0 - 1.0 / (1.0 + abs_a + a * a + 1.41645 * a**4))
    return 1.7159 * approx


def rectifiedtanh(x):
    return jnp.maximum(0.0, jnp.tanh(x))


def softplus(x):
    return jax.nn.softplus(x)


def softsign(x):
    return jax.nn.soft_sign(x)


def cube(x):
    return x * x * x


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return jax.nn.mish(x)


def softmax(x):
    """Softmax over the trailing feature axis (class axis)."""
    return jax.nn.softmax(x, axis=-1)


def log_softmax(x):
    return jax.nn.log_softmax(x, axis=-1)


def exp(x):
    return jnp.exp(x)


def clippedrelu(x, max_value: float = 6.0):
    return jnp.clip(x, 0.0, max_value)


def thresholdedrelu(x, theta: float = 1.0):
    return jnp.where(x > theta, x, 0.0)


# Registry keyed by the lowercase names used in DL4J's `Activation` enum
# (reference: nd4j Activation enum referenced from NeuralNetConfiguration).
_REGISTRY: Dict[str, Callable] = {
    "identity": identity,
    "linear": identity,
    "relu": relu,
    "relu6": relu6,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "gelu": gelu,
    "silu": silu,
    "swish": swish,
    "mish": mish,
    "sigmoid": sigmoid,
    "hardsigmoid": hardsigmoid,
    "tanh": tanh,
    "hardtanh": hardtanh,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "softplus": softplus,
    "softsign": softsign,
    "cube": cube,
    "softmax": softmax,
    "logsoftmax": log_softmax,
    "thresholdedrelu": thresholdedrelu,
    "exp": exp,
    "clippedrelu": clippedrelu,
}


# Piecewise-linear and non-decreasing: such a function commutes with a max
# (`max(f(a), f(b)) == f(max(a, b))`, in floating point too), and the only
# ties it makes between unequal inputs are in its flat parts, where its
# gradient is zero whichever of them a max-pool's backward picks. So a
# convolution may apply one AFTER the max-pool that follows it
# (`nn/layers/convolution.defers_to_pool`). `tanh`, `sigmoid` and the other
# saturating ones commute forward as well, but in bfloat16 their rounded
# outputs tie where the raw values do not and the gradient moves by 3 to
# 10%; the rest (`gelu`, `swish`, `mish`, `softmax`) are not monotone.
_MAX_POOL_COMMUTING: Dict[str, Callable] = {
    "identity": identity,
    "linear": identity,
    "relu": relu,
    "relu6": relu6,
    "leakyrelu": leakyrelu,
}


class Activation:
    """Enum-like accessor mirroring DL4J's `Activation` enum surface."""

    IDENTITY = "identity"
    RELU = "relu"
    RELU6 = "relu6"
    LEAKYRELU = "leakyrelu"
    ELU = "elu"
    SELU = "selu"
    GELU = "gelu"
    SWISH = "swish"
    MISH = "mish"
    SIGMOID = "sigmoid"
    HARDSIGMOID = "hardsigmoid"
    TANH = "tanh"
    HARDTANH = "hardtanh"
    RATIONALTANH = "rationaltanh"
    RECTIFIEDTANH = "rectifiedtanh"
    SOFTPLUS = "softplus"
    SOFTSIGN = "softsign"
    CUBE = "cube"
    SOFTMAX = "softmax"
    LOGSOFTMAX = "logsoftmax"

    @staticmethod
    def get(name_or_fn: Union[str, Callable, None]) -> Callable:
        if name_or_fn is None:
            return identity
        if callable(name_or_fn):
            return name_or_fn
        key = str(name_or_fn).lower()
        if ":" in key:
            # Parametrized form "name:value" (e.g. "leakyrelu:0.2"), kept as a
            # plain string so layer configs stay JSON-serializable. Used by the
            # Keras importer for LeakyReLU/ELU/ThresholdedReLU alpha/theta.
            base, _, arg = key.partition(":")
            if base in _REGISTRY and arg:
                fn, val = _REGISTRY[base], float(arg)
                return lambda x: fn(x, val)
        if key not in _REGISTRY:
            raise ValueError(
                f"Unknown activation {name_or_fn!r}; known: {sorted(_REGISTRY)}"
            )
        return _REGISTRY[key]

    @staticmethod
    def commutes_with_max_pool(name: Union[str, Callable, None]) -> bool:
        """True for the built-in piecewise-linear non-decreasing activations
        (`identity`, `relu`, `relu6`, `leakyrelu` with a slope of 0 or
        more), by NAME: never for a callable or a name registered over."""
        if name is None:
            return True
        if not isinstance(name, str):
            return False
        base, _, arg = name.lower().partition(":")
        fn = _MAX_POOL_COMMUTING.get(base)
        if fn is None or _REGISTRY.get(base) is not fn:
            return False
        return not arg or (base == "leakyrelu" and float(arg) >= 0.0)

    @staticmethod
    def register(name: str, fn: Callable) -> None:
        """Custom-activation plug-in seam (reference: custom IActivation tests)."""
        _REGISTRY[name.lower()] = fn

    @staticmethod
    def names():
        return sorted(_REGISTRY)


def resolve(name_or_fn) -> Callable:
    return Activation.get(name_or_fn)
