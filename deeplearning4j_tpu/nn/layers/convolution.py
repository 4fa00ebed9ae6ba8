"""Convolution / pooling / padding layers (NHWC, MXU-friendly).

Reference parity: `nn/conf/layers/{ConvolutionLayer,Convolution1DLayer,
SubsamplingLayer,Subsampling1DLayer,ZeroPaddingLayer}.java` + impls in
`nn/layers/convolution/` (im2col path + reflective cuDNN helper dispatch at
`ConvolutionLayer.java:67-77,164,318`). The helper seam is unnecessary here:
`jax.lax.conv_general_dilated` lowers straight to the TPU MXU, and XLA fuses
bias+activation into the conv — the TPU build's "cuDNN helper" IS the
compiler. ConvolutionMode Strict/Truncate/Same (reference
`nn/conf/ConvolutionMode.java`) maps to explicit VALID/SAME padding.

Layout: activations NHWC, kernels HWIO — the layouts XLA/TPU prefers (the
reference is NCHW/OIHW; translating that would cost transposes on TPU).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import Activation, identity
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, Params, register_layer
from deeplearning4j_tpu.observe.registry import get_registry


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _out_size(size: int, k: int, s: int, p: int, mode: str) -> int:
    if mode == "same":
        return -(-size // s)  # ceil
    if mode == "strict":
        if (size + 2 * p - k) % s != 0:
            raise ValueError(
                f"ConvolutionMode=strict: (size {size} + 2*pad {p} - kernel {k}) "
                f"not divisible by stride {s} (reference: ConvolutionMode.Strict)"
            )
        return (size + 2 * p - k) // s + 1
    # truncate (reference default tolerates remainder)
    return (size + 2 * p - k) // s + 1


def _padding_2d(mode: str, kernel, stride, pad) -> Any:
    if mode == "same":
        return "SAME"
    kh, kw = _pair(kernel)
    ph, pw = _pair(pad)
    return [(ph, ph), (pw, pw)]


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(Layer):
    """2-D convolution. Reference: `nn/conf/layers/ConvolutionLayer.java`,
    impl `nn/layers/convolution/ConvolutionLayer.java` (im2col+gemm or cuDNN
    helper — here one `lax.conv_general_dilated` on the MXU).

    In front of a max-pool (`defers_to_pool`) the models' forward loops run
    the bias add and the activation on the pool's OUTPUT, a quarter of the
    elements, with the same result; `feed_forward` is unaffected."""

    n_in: Optional[int] = None       # input channels
    n_out: Optional[int] = None      # output channels
    kernel: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    dilation: Any = (1, 1)
    convolution_mode: str = "truncate"   # strict | truncate | same
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType) -> "ConvolutionLayer":
        if self.n_in is None and input_type.kind in ("cnn", "cnn_flat"):
            return dataclasses.replace(self, n_in=input_type.channels)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        m = self.convolution_mode
        h = _out_size(input_type.height, kh, sh, ph, m)
        w = _out_size(input_type.width, kw, sw, pw, m)
        return InputType.convolutional(h, w, self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel)
        w = self._winit()(key, (kh, kw, self.n_in, self.n_out), dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return params, {}

    def product(self, params: Params, x):
        return lax.conv_general_dilated(
            x, params["W"],
            window_strides=_pair(self.stride),
            padding=_padding_2d(self.convolution_mode, self.kernel, self.stride, self.padding),
            rhs_dilation=_pair(self.dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def pre_output(self, params: Params, x):
        y = self.product(params, x)
        return y + params["b"] if self.has_bias else y

    def split(self, params, x, *, train=False, rng=None):
        """`apply` in two halves: the product, and the function of it that
        adds the bias and applies the activation (per channel, so a
        max-pool may run between the two: `defers_to_pool`)."""
        def tail(y):
            return self._act(y + params["b"] if self.has_bias else y)

        return self.product(params, self._maybe_dropout(x, train, rng)), tail

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y, tail = self.split(params, x, train=train, rng=rng)
        return tail(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Deconvolution2DLayer(ConvolutionLayer):
    """Transposed convolution (reference: Deconvolution2D config)."""

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            h, w = input_type.height * sh, input_type.width * sw
        else:
            h = sh * (input_type.height - 1) + kh - 2 * ph
            w = sw * (input_type.width - 1) + kw - 2 * pw
        return InputType.convolutional(h, w, self.n_out)

    def product(self, params: Params, x):
        pad = ("SAME" if self.convolution_mode == "same"
               else [(p, p) for p in _pair(self.padding)])
        return lax.conv_transpose(
            x, params["W"],
            strides=_pair(self.stride),
            padding=pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


@register_layer
@dataclasses.dataclass(frozen=True)
class DepthwiseConvolution2DLayer(Layer):
    """Depthwise conv (reference: DepthwiseConvolution2D). Implemented via
    feature_group_count = n_in, which XLA lowers efficiently on TPU."""

    n_in: Optional[int] = None
    depth_multiplier: int = 1
    kernel: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.channels)
        return self

    @property
    def n_out(self):
        return self.n_in * self.depth_multiplier

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        m = self.convolution_mode
        return InputType.convolutional(
            _out_size(input_type.height, kh, sh, ph, m),
            _out_size(input_type.width, kw, sw, pw, m),
            self.n_out,
        )

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel)
        w = self._winit()(key, (kh, kw, 1, self.n_out), dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y = lax.conv_general_dilated(
            x, params["W"],
            window_strides=_pair(self.stride),
            padding=_padding_2d(self.convolution_mode, self.kernel, self.stride, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.n_in,
        )
        if self.has_bias:
            y = y + params["b"]
        return self._act(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SeparableConvolution2DLayer(Layer):
    """Depthwise-separable conv (reference: SeparableConvolution2D)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    depth_multiplier: int = 1
    kernel: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.channels)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        m = self.convolution_mode
        return InputType.convolutional(
            _out_size(input_type.height, kh, sh, ph, m),
            _out_size(input_type.width, kw, sw, pw, m),
            self.n_out,
        )

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel)
        k1, k2 = jax.random.split(key)
        mid = self.n_in * self.depth_multiplier
        params = {
            "dW": self._winit()(k1, (kh, kw, 1, mid), dtype),
            "pW": self._winit()(k2, (1, 1, mid, self.n_out), dtype),
        }
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y = lax.conv_general_dilated(
            x, params["dW"],
            window_strides=_pair(self.stride),
            padding=_padding_2d(self.convolution_mode, self.kernel, self.stride, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.n_in,
        )
        y = lax.conv_general_dilated(
            y, params["pW"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            y = y + params["b"]
        return self._act(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(Layer):
    """Spatial pooling. Reference: `nn/conf/layers/SubsamplingLayer.java`
    (PoolingType MAX/AVG/SUM/PNORM), impl `nn/layers/convolution/subsampling/`.
    One `lax.reduce_window` — no cuDNN helper needed.

    A max-pool directly behind a `ConvolutionLayer` (`defers_to_pool`) gets
    the convolution's bare product from the models' forward loops, which add
    the bias and activate on its output; `feed_forward` is unaffected."""

    pooling: str = "max"             # max | avg | sum | pnorm
    kernel: Any = (2, 2)
    stride: Any = (2, 2)
    padding: Any = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        m = self.convolution_mode
        return InputType.convolutional(
            _out_size(input_type.height, kh, sh, ph, m),
            _out_size(input_type.width, kw, sw, pw, m),
            input_type.channels,
        )

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            ph, pw = _pair(self.padding)
            pad = [(0, 0), (ph, ph), (pw, pw), (0, 0)]
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        p = self.pooling.lower()
        if p == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        elif p == "sum":
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
        elif p == "avg":
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pad)
            y = s / cnt
        elif p == "pnorm":
            s = lax.reduce_window(
                jnp.abs(x) ** self.pnorm, 0.0, lax.add, dims, strides, pad
            )
            y = s ** (1.0 / self.pnorm)
        else:
            raise ValueError(f"Unknown pooling {self.pooling!r}")
        return y, state


def defers_to_pool(producer: Layer, consumer: Layer) -> bool:
    """Whether `producer`'s bias add and activation may run on the output of
    `consumer` instead of its input: the one rule both models' forward
    loops ask, over nothing but the two layers' configurations.

    `maxpool(act(conv(x, W) + b)) == act(maxpool(conv(x, W)) + b)` exactly,
    in every dtype, because `fl(a + b)` and `act` are non-decreasing in `a`
    and a pooling window never crosses channels. In the second order the
    bias and the activation touch a quarter of the elements, the convolution
    writes one output, its backward convolutions read the gradient with no
    mask beside it, and the bias gradient is a sum over the POOLED gradient
    (VGG16's step compiled for a v5e: 68.4 to 54.4 GB accessed). It holds
    for a 2-D `ConvolutionLayer` or `Deconvolution2DLayer` that has a bias
    or an activation to defer, the activation one of
    `Activation.commutes_with_max_pool`'s, in front of a max-pooling
    `SubsamplingLayer` with no dropout of its own. A batch norm in front of
    a pool has no second output and no bias gradient to lose (ResNet-50's
    stem reads 5.50 GB so against 5.40), and is left alone. What lies
    BETWEEN the two (a preprocessor, a second consumer, a caller that
    collects every activation) is the loops' to see."""
    return (type(producer) in (ConvolutionLayer, Deconvolution2DLayer)
            and type(consumer) is SubsamplingLayer
            and consumer.pooling.lower() == "max"
            and not consumer.dropout
            and Activation.commutes_with_max_pool(producer.activation)
            and (producer.has_bias
                 or Activation.get(producer.activation) is not identity))


def record_deferred_pairs(model, pairs: int) -> None:
    """Gauge `conv_pool_pairs_deferred{model=<class>}`: how many
    (convolution, max-pool) pairs the forward pass that `model` traced last
    ran in the deferred order (5 for VGG16, 0 for ResNet-50)."""
    get_registry().gauge("conv_pool_pairs_deferred",
                         model=type(model).__name__).set(pairs)


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPaddingLayer(Layer):
    """Reference: `nn/conf/layers/ZeroPaddingLayer.java`."""

    pad: Any = (1, 1)  # (ph, pw) or ((top,bottom),(left,right))

    def _pads(self):
        p = self.pad
        if isinstance(p, (tuple, list)) and len(p) == 2 and isinstance(p[0], (tuple, list)):
            return tuple(p[0]), tuple(p[1])
        ph, pw = _pair(p)
        return (ph, ph), (pw, pw)

    def output_type(self, input_type: InputType) -> InputType:
        (pt, pb), (pl, pr) = self._pads()
        return InputType.convolutional(
            input_type.height + pt + pb, input_type.width + pl + pr, input_type.channels
        )

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        (pt, pb), (pl, pr) = self._pads()
        return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0))), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping2DLayer(Layer):
    """Reference: Cropping2D config."""

    crop: Any = (0, 0)

    def _crops(self):
        c = self.crop
        if isinstance(c, (tuple, list)) and len(c) == 2 and isinstance(c[0], (tuple, list)):
            return tuple(c[0]), tuple(c[1])
        ch, cw = _pair(c)
        return (ch, ch), (cw, cw)

    def output_type(self, input_type: InputType) -> InputType:
        (ct, cb), (cl, cr) = self._crops()
        return InputType.convolutional(
            input_type.height - ct - cb, input_type.width - cl - cr, input_type.channels
        )

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        (ct, cb), (cl, cr) = self._crops()
        h, w = x.shape[1], x.shape[2]
        return x[:, ct:h - cb, cl:w - cr, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling2DLayer(Layer):
    """Nearest-neighbor upsampling (reference: Upsampling2D)."""

    size: Any = (2, 2)

    def output_type(self, input_type: InputType) -> InputType:
        sh, sw = _pair(self.size)
        return InputType.convolutional(
            input_type.height * sh, input_type.width * sw, input_type.channels
        )

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        sh, sw = _pair(self.size)
        return jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SpaceToDepthLayer(Layer):
    """Fold `block`×`block` spatial tiles into channels:
    [B, H, W, C] -> [B, H/b, W/b, b*b*C], channel order (dy, dx, c).

    TPU-native extension (no counterpart in the 0.9-era reference; later
    DL4J adds SpaceToDepthLayer): the MXU reads 128-channel tiles, so a
    stem conv over 3-channel images wastes >95% of the systolic array —
    folding space into channels first (with the stem kernel folded to
    match, see zoo/resnet.py `fold_stem_kernel`) is the standard MLPerf
    ResNet optimization."""

    block: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        b = self.block
        if input_type.height % b or input_type.width % b:
            raise ValueError(
                f"SpaceToDepth block {b} must divide spatial dims "
                f"({input_type.height}x{input_type.width})")
        return InputType.convolutional(
            input_type.height // b, input_type.width // b,
            input_type.channels * b * b)

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        b = self.block
        B, H, W, C = x.shape
        y = x.reshape(B, H // b, b, W // b, b, C)
        y = y.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, H // b, W // b, b * b * C)
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class Convolution1DLayer(Layer):
    """1-D (temporal) conv over [batch, time, features]. Reference:
    `nn/conf/layers/Convolution1DLayer.java`."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    kernel: int = 3
    stride: int = 1
    padding: int = 0
    convolution_mode: str = "same"
    has_bias: bool = True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        if t is not None:
            t = _out_size(t, self.kernel, self.stride, self.padding, self.convolution_mode)
        return InputType.recurrent(self.n_out, t)

    def init_params(self, key, input_type, dtype=jnp.float32):
        w = self._winit()(key, (self.kernel, self.n_in, self.n_out), dtype)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        pad = ("SAME" if self.convolution_mode == "same"
               else [(self.padding, self.padding)])
        y = lax.conv_general_dilated(
            x, params["W"], window_strides=(self.stride,), padding=pad,
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        if self.has_bias:
            y = y + params["b"]
        return self._act(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Subsampling1DLayer(Layer):
    """1-D pooling over [batch, time, features]. Reference:
    `nn/conf/layers/Subsampling1DLayer.java`."""

    pooling: str = "max"
    kernel: int = 2
    stride: int = 2
    padding: int = 0
    convolution_mode: str = "truncate"

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps
        if t is not None:
            t = _out_size(t, self.kernel, self.stride, self.padding, self.convolution_mode)
        return InputType.recurrent(input_type.size, t)

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            pad = [(0, 0), (self.padding, self.padding), (0, 0)]
        dims, strides = (1, self.kernel, 1), (1, self.stride, 1)
        if self.pooling == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pad)
        else:
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, dims, strides, pad)
            y = s / cnt
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class FusedConvBNLayer(Layer):
    """Conv + batch norm + activation as ONE fused op (Pallas): the
    BN batch statistics are accumulated inside the conv kernel while
    the output tile is in VMEM, saving a full HBM sweep per conv+BN pair
    (see `ops/conv_fused.py`). This is the framework's answer to the
    reference's cuDNN helper seam (`ConvolutionLayer.java:67-77`,
    `CudnnBatchNormalizationHelper.java`). Two kernel shapes are fused:
    (1, 1) any stride (the ResNet bottleneck reduce/expand/projection
    matmuls) and (3, 3) stride-1 SAME (the bottleneck middle convs).

    Parameters: W [kh, kw, n_in, n_out] (HWIO, same shape as
    ConvolutionLayer's), gamma/beta; state: running mean/var. Equivalent
    to ConvolutionLayer(kernel, has_bias=False, activation=identity)
    followed by BatchNormalization(activation=...), to float32 accuracy.
    """

    CONSUMES = "cnn"   # drives preprocessor auto-insertion (NHWC input)

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    kernel: Any = (1, 1)
    stride: Any = (1, 1)
    decay: float = 0.9
    eps: float = 1e-5

    def __post_init__(self):
        k = _pair(self.kernel)
        if k not in ((1, 1), (3, 3)):
            raise ValueError(f"FusedConvBNLayer supports kernels (1,1) "
                             f"and (3,3), got {k}")
        if k == (3, 3) and _pair(self.stride) != (1, 1):
            raise ValueError("the fused 3x3 path is stride-1 SAME only")

    def infer_n_in(self, input_type: InputType) -> "FusedConvBNLayer":
        if self.n_in is None and input_type.kind in ("cnn", "cnn_flat"):
            return dataclasses.replace(self, n_in=input_type.channels)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        # (1,1): stride applies as input subsampling, out = ceil(in/s),
        # identical to a VALID-padded strided 1x1 conv. (3,3): stride-1
        # SAME, spatial dims unchanged.
        sh, sw = _pair(self.stride)
        return InputType.convolutional(
            -(-input_type.height // sh), -(-input_type.width // sw),
            self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kh, kw = _pair(self.kernel)
        w = self._winit()(key, (kh, kw, self.n_in, self.n_out), dtype)
        params = {
            "W": w,
            "gamma": jnp.ones((self.n_out,), dtype),
            "beta": jnp.zeros((self.n_out,), dtype),
        }
        state = {"mean": jnp.zeros((self.n_out,), jnp.float32),
                 "var": jnp.ones((self.n_out,), jnp.float32)}
        return params, state

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.conv_fused import (
            conv1x1_bn_act, conv3x3_bn_act)

        x = self._maybe_dropout(x, train, rng)
        act = self.activation or "identity"
        relu = act == "relu"
        interpret = jax.default_backend() != "tpu"
        is3x3 = _pair(self.kernel) == (3, 3)
        if train:
            if is3x3:
                out, m, v = conv3x3_bn_act(
                    x, params["W"], params["gamma"], params["beta"],
                    eps=self.eps, relu=relu, train=True,
                    interpret=interpret)
            else:
                out, m, v = conv1x1_bn_act(
                    x, params["W"][0, 0], params["gamma"], params["beta"],
                    stride=_pair(self.stride), eps=self.eps, relu=relu,
                    train=True, interpret=interpret)
            d = self.decay
            new_state = {
                "mean": d * state["mean"] + (1 - d) * m,
                "var": d * state["var"] + (1 - d) * v,
            }
        else:
            if is3x3:
                out = conv3x3_bn_act(
                    x, params["W"], params["gamma"], params["beta"],
                    mean=state["mean"], var=state["var"],
                    eps=self.eps, relu=relu, train=False)
            else:
                out = conv1x1_bn_act(
                    x, params["W"][0, 0], params["gamma"], params["beta"],
                    mean=state["mean"], var=state["var"],
                    stride=_pair(self.stride), eps=self.eps, relu=relu,
                    train=False)
            new_state = state
        if not relu and act != "identity":
            out = self._act(out)
        return out, new_state
