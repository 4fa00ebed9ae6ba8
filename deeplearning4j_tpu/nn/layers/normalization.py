"""Normalization layers: BatchNorm, LRN, LayerNorm.

Reference parity: `nn/conf/layers/BatchNormalization.java` + impl
`nn/layers/normalization/BatchNormalization.java` (cuDNN helper seam at
`:56-64,125,307`) and `LocalResponseNormalization.java`. Running mean/var are
NON-trainable state carried explicitly through the train step (the reference
mutates them in place; under jit we return the new state), updated with the
reference's `decay` EMA semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_train(x, gamma, beta, eps):
    """Training batch norm over every axis but the last: `(y, mean, var)`,
    the biased batch statistics in float32 (float64 for a float64 input).

    Two passes over `x` beyond its producer, where `jnp.mean` / `jnp.var`
    under autodiff take five. Forward: `sum(x)` and `sum(x * x)` do not
    depend on each other, so XLA fuses both into the producing convolution;
    one elementwise pass writes `y`. Backward (written out, hence the
    `custom_vjp`: forward-mode differentiation through it is not defined):
    `sum(dy)` and `sum(dy * xhat)` in one reduction, one elementwise pass
    for `dx`. `mean` and `var` feed the running state only; their
    cotangents are ignored.

    Statistics and the normalisation are computed in the accumulator type
    and rounded once, at `y`, to `x.dtype`. `E[x^2] - E[x]^2` loses about
    `2^-24 * (1 + mean^2 / var)` of the variance in float32 for each
    rounding of the sums (the choice Flax makes by default,
    `use_fast_variance`): nothing that shows at |mean| of a few std, 6% a
    rounding at `mean = 1e3 * std`. It is clamped at 0.
    """
    return _batch_norm_fwd(x, gamma, beta, eps)[0]


def _batch_axes(x):
    """(every axis but the last, the count of elements they hold)."""
    return tuple(range(x.ndim - 1)), x.size // x.shape[-1]


def _batch_norm_fwd(x, gamma, beta, eps):
    acc = jnp.promote_types(x.dtype, jnp.float32)
    axes, n = _batch_axes(x)
    xf = x.astype(acc)
    mean = jnp.sum(xf, axis=axes) / n
    var = jnp.maximum(jnp.sum(xf * xf, axis=axes) / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = (xf - mean) * (inv * gamma.astype(acc)) + beta.astype(acc)
    return (y.astype(x.dtype), mean, var), (x, mean, inv, gamma, beta)


def _batch_norm_bwd(eps, residuals, cotangents):
    x, mean, inv, gamma, beta = residuals
    acc = mean.dtype
    axes, n = _batch_axes(x)
    dy = cotangents[0].astype(acc)
    xhat = (x.astype(acc) - mean) * inv
    dbeta = jnp.sum(dy, axis=axes)
    dgamma = jnp.sum(dy * xhat, axis=axes)
    dx = (gamma.astype(acc) * inv) * (dy - dbeta / n - xhat * (dgamma / n))
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype))


batch_norm_train.defvjp(_batch_norm_fwd, _batch_norm_bwd)


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(Layer):
    """Batch norm over the trailing channel/feature axis (NHWC/ BTF / BF).

    Reference: `nn/conf/layers/BatchNormalization.java` (decay `:…`, eps,
    lockGammaBeta) — gamma/beta trainable, global mean/var state."""

    n_out: Optional[int] = None   # feature count, inferred
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    scale: bool = True            # learnable gamma (Keras scale flag)
    center: bool = True           # learnable beta (Keras center flag)

    def infer_n_in(self, input_type: InputType) -> "BatchNormalization":
        if self.n_out is None:
            feat = (input_type.channels if input_type.kind in ("cnn", "cnn3d")
                    else input_type.size if input_type.kind == "rnn"
                    else input_type.flat_size())
            return dataclasses.replace(self, n_out=feat)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        f = self.n_out
        params = {}
        if not self.lock_gamma_beta:
            if self.scale:
                params["gamma"] = jnp.ones((f,), dtype)
            if self.center:
                params["beta"] = jnp.zeros((f,), dtype)
        state = {"mean": jnp.zeros((f,), dtype), "var": jnp.ones((f,), dtype)}
        return params, state

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        learn = not self.lock_gamma_beta
        gamma = params["gamma"] if learn and self.scale else None
        beta = params["beta"] if learn and self.center else None
        if train:
            f = x.shape[-1]
            y, mean, var = batch_norm_train(
                x, jnp.ones((f,), x.dtype) if gamma is None else gamma,
                jnp.zeros((f,), x.dtype) if beta is None else beta, self.eps)
            d = self.decay

            def moved(old, batch):      # in the state's dtype
                return (d * old + (1 - d) * jax.lax.stop_gradient(batch)
                        ).astype(old.dtype)

            return self._act(y), {"mean": moved(state["mean"], mean),
                                  "var": moved(state["var"], var)}
        inv = 1.0 / jnp.sqrt(state["var"] + self.eps)
        y = (x - state["mean"]) * inv
        if gamma is not None:
            y = y * gamma
        if beta is not None:
            y = y + beta
        return self._act(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (AlexNet-era). Reference:
    `nn/conf/layers/LocalResponseNormalization.java` + cuDNN helper
    (`CudnnLocalResponseNormalizationHelper.java`); here a slide over the
    channel axis that XLA fuses — no helper needed."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        # x: NHWC. Sum x^2 over a window of `n` adjacent channels.
        half = self.n // 2
        sq = x * x
        padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, half)))
        c = x.shape[-1]
        acc = sum(padded[..., i:i + c] for i in range(self.n))
        denom = (self.k + (self.alpha / self.n) * acc) ** self.beta
        return x / denom, state


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNormalization(Layer):
    """Layer norm over the trailing feature axis — no reference counterpart
    (DL4J 0.8 predates it); required by the modern model families this
    framework must also serve (transformers, ring attention)."""

    n_out: Optional[int] = None
    eps: float = 1e-6

    def infer_n_in(self, input_type: InputType) -> "LayerNormalization":
        if self.n_out is None:
            feat = input_type.size if input_type.kind == "rnn" else input_type.flat_size()
            return dataclasses.replace(self, n_out=feat)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        f = self.n_out
        return {"gamma": jnp.ones((f,), dtype), "beta": jnp.zeros((f,), dtype)}, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) / jnp.sqrt(var + self.eps)
        return self._act(y * params["gamma"] + params["beta"]), state


@register_layer
@dataclasses.dataclass(frozen=True)
class RMSNormalization(Layer):
    """x / sqrt(mean(x^2) + eps) * gamma over the trailing feature axis:
    no centring and no shift (`nn/layers/attention.rms_norm`). The norm
    before a language model's head."""

    CONSUMES = "any"

    n_out: Optional[int] = None
    eps: float = 1e-5
    scale: Optional[float] = None   # the normed rows times this

    def infer_n_in(self, input_type: InputType) -> "RMSNormalization":
        if self.n_out is None:
            feat = input_type.size if input_type.kind == "rnn" else input_type.flat_size()
            return dataclasses.replace(self, n_out=feat)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_out,), dtype)}, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.layers.attention import rms_norm

        y = rms_norm(x, params["gamma"], self.eps)
        if self.scale is not None:
            y = y * jnp.asarray(self.scale, y.dtype)
        return self._act(y), state
