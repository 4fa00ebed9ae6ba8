"""Feed-forward layers: Dense, Output, Loss, Activation, Dropout, Embedding,
AutoEncoder.

Reference parity: `nn/conf/layers/{DenseLayer,OutputLayer,LossLayer,
ActivationLayer,DropoutLayer,EmbeddingLayer,AutoEncoder}.java` + impls in
`nn/layers/feedforward/` and `nn/layers/BaseLayer.java` (preOutput = W·x+b at
`:384`). Parameter names follow the reference's DefaultParamInitializer
("W", "b"); kernels are stored [n_in, n_out] so the hot op is a single
batch-major matmul on the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, Params, State, register_layer
from deeplearning4j_tpu.nn.losses import LossFunction


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(Layer):
    """Fully connected layer. Reference: `nn/conf/layers/DenseLayer.java`."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType) -> "DenseLayer":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32) -> Tuple[Params, State]:
        assert self.n_in and self.n_out, f"{self.name}: n_in/n_out unset"
        w = self._winit()(key, (self.n_in, self.n_out), dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return params, {}

    def pre_output(self, params: Params, x):
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        return self._act(self.pre_output(params, x)), state


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(DenseLayer):
    """Dense + loss head. Reference: `nn/conf/layers/OutputLayer.java`
    (extends BaseOutputLayer); score computed in
    `MultiLayerNetwork.computeGradientAndScore()` (reference `:2082`)."""

    loss: Any = "mcxent"

    @property
    def is_output_layer(self) -> bool:
        return True

    def score(self, params: Params, x, labels, mask=None):
        """Mean per-example loss from the layer INPUT activations; the loss
        receives pre-activation output so fused stable forms apply."""
        preout = self.pre_output(params, x)
        return LossFunction.get(self.loss)(labels, preout, self.activation, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class LossLayer(Layer):
    """Loss without params (activation + loss only). Reference:
    `nn/conf/layers/LossLayer.java`."""

    loss: Any = "mcxent"

    @property
    def is_output_layer(self) -> bool:
        return True

    def pre_output(self, params, x):
        return x

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self._act(x), state

    def score(self, params: Params, x, labels, mask=None):
        return LossFunction.get(self.loss)(labels, x, self.activation, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """Parameterless activation. Reference: `nn/conf/layers/ActivationLayer.java`."""

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self._act(x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class PReLULayer(Layer):
    """Parametric ReLU with a learnable per-feature slope (reference:
    `nn/conf/layers/PReLULayer` precedent; Keras `PReLU` with
    shared_axes covering all but the last axis). alpha initializes to
    `alpha_init` (Keras default 0)."""

    n_out: Optional[int] = None
    alpha_init: float = 0.0

    def infer_n_in(self, input_type):
        if self.n_out is None:
            # alpha broadcasts over the trailing (feature/channel) axis
            n = (input_type.channels if input_type.kind in ("cnn", "cnn3d")
                 else input_type.size)
            return dataclasses.replace(self, n_out=n)
        return self

    def init_params(self, key, input_type, dtype=jnp.float32):
        n = self.n_out
        if n is None:
            n = (input_type.channels if input_type.kind in ("cnn", "cnn3d")
                 else input_type.size)
        return {"alpha": jnp.full((n,), self.alpha_init, dtype)}, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        a = params["alpha"]
        return jnp.where(x >= 0, x, a * x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout. Reference: `nn/conf/layers/DropoutLayer.java`."""

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self._maybe_dropout(x, train, rng), state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(Layer):
    """Index → vector lookup, one index per example. Reference:
    `nn/conf/layers/EmbeddingLayer.java` (+ feedforward/embedding impl).
    The lookup is a gather (`jnp.take`), which XLA lowers natively, and
    its gradient XLA's scatter-add: no cell runs this layer. The sequence
    layer below shares the forward; its gradient on one TPU device is a
    one-hot product after all, over the ids sorted by vocabulary tile
    (`ops/embedding.py`)."""

    TAKES_IDS = True

    n_in: Optional[int] = None    # vocab size
    n_out: Optional[int] = None
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType) -> "EmbeddingLayer":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        w = self._winit()(key, (self.n_in, self.n_out), dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return params, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        idx = x
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        emb = jnp.take(params["W"], idx.astype(jnp.int32), axis=0)
        if self.has_bias:
            emb = emb + params["b"]
        return self._act(emb), state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(Layer):
    """[batch, time] indices → [batch, time, n_out] vectors (modern
    counterpart of reference EmbeddingSequenceLayer). The lookup is
    `ops/embedding.lookup`: `jnp.take` forward, and where the one-device
    kernels run a grouped product backward in place of XLA's scatter-add."""

    CONSUMES = "rnn"   # sequence input — no RnnToFeedForward before it
    TAKES_IDS = True

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    scale: Optional[float] = None   # rows times this (sqrt(n_out), say)

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {"W": self._winit()(key, (self.n_in, self.n_out), dtype)}, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        if x.ndim == 3 and x.shape[-1] == 1:
            x = x[..., 0]  # [B, T, 1] token-id tensors (InputType.recurrent(1))
        from deeplearning4j_tpu.ops.embedding import lookup

        emb = lookup(params["W"], x)
        if self.scale is not None:
            emb = emb * jnp.asarray(self.scale, emb.dtype)
        return self._act(emb), state


@register_layer
@dataclasses.dataclass(frozen=True)
class AutoEncoder(Layer):
    """Denoising autoencoder, layerwise-pretrainable. Reference:
    `nn/conf/layers/AutoEncoder.java` + `nn/layers/feedforward/autoencoder/`.
    Supervised forward = encoder only (like the reference once pretrained);
    `reconstruction_score` drives unsupervised pretraining."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    corruption_level: float = 0.3
    loss: Any = "mse"

    @property
    def is_pretrainable(self) -> bool:
        return True

    def infer_n_in(self, input_type: InputType) -> "AutoEncoder":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {
            "W": self._winit()(k1, (self.n_in, self.n_out), dtype),
            "b": jnp.zeros((self.n_out,), dtype),
            "vb": jnp.zeros((self.n_in,), dtype),  # visible bias (decoder)
        }, {}

    def encode(self, params, x):
        return self._act(x @ params["W"] + params["b"])

    def decode(self, params, h):
        return self._act(h @ params["W"].T + params["vb"])

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self.encode(params, x), state

    def reconstruction_score(self, params, x, *, rng=None):
        corrupted = x
        if rng is not None and self.corruption_level > 0:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        recon = self.decode(params, self.encode(params, corrupted))
        return LossFunction.get(self.loss)(x, recon, "identity")
