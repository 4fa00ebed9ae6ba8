"""Plain reference: VGG16 (Simonyan and Zisserman 2014, arXiv:1409.1556,
configuration D) training with softmax cross-entropy and Nesterov momentum.

Straightforward `jax.numpy` / `lax.conv_general_dilated` in float32 at
matmul precision "highest". Imports nothing of the program; makes its own
weights from the seed under the program's layer names (`layer<i>_<type>`).
NHWC, 3x3 convolutions of padding 1 with bias and ReLU, 2x2/2 max pooling,
two 4096-wide ReLU layers and the classifier. No dropout (see the
configuration's `assumed`). Modes as in `resnet50.py`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.arithmetic import operands, relu, stored

BLOCKS = ((64, 64), (128, 128), (256, 256, 256), (512, 512, 512),
          (512, 512, 512))
HIDDEN = 4096


def layer_table(cfg):
    """(name, kind, c_in, c_out, output side) in forward order; kinds are
    'conv', 'pool' and 'dense'."""
    rows, i = [], 0
    side, _, c_in = cfg["input_shape"]
    for widths in BLOCKS:
        for c_out in widths:
            rows.append((f"layer{i}_convolutionlayer", "conv", c_in, c_out,
                         side))
            c_in, i = c_out, i + 1
        side //= 2
        rows.append((f"layer{i}_subsamplinglayer", "pool", c_in, c_in, side))
        i += 1
    flat = side * side * c_in
    for c_out in (HIDDEN, HIDDEN):
        rows.append((f"layer{i}_denselayer", "dense", flat, c_out, 1))
        flat, i = c_out, i + 1
    rows.append((f"layer{i}_outputlayer", "dense", flat,
                 cfg["label_shape"][-1], 1))
    return rows


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one image's forward pass, from the shapes."""
    return sum((9 if kind == "conv" else 1) * ci * co * side * side
               for _, kind, ci, co, side in layer_table(cfg)
               if kind != "pool")


def init_params(seed: int, cfg):
    """Xavier-normal kernels (variance 2 / (fan_in + fan_out), the zoo
    model's rule) and zero biases, from the seed."""
    rows = layer_table(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, kind, ci, co, _) in enumerate(rows):
            if kind == "pool":
                continue
            k = 3 if kind == "conv" else 1
            shape = (3, 3, ci, co) if kind == "conv" else (ci, co)
            std = math.sqrt(2.0 / (k * k * ci + k * k * co))
            params[name] = {
                "W": std * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32),
                "b": jnp.zeros((co,), jnp.float32)}
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def loss_fn(params, x, y, mode="float32"):
    """Mean softmax cross-entropy of one batch. x: [B, H, W, C] float32."""
    h = x
    rows = layer_table({"input_shape": x.shape[1:],
                        "label_shape": y.shape[1:]})
    for name, kind, _, _, _ in rows:
        if kind == "pool":
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
            continue
        if kind == "conv":
            a, w, precision = operands(h, params[name]["W"], mode)
            h = stored(lax.conv_general_dilated(
                a, w, (1, 1), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision, preferred_element_type=jnp.float32),
                mode)
        else:
            h = h.reshape(h.shape[0], -1)
            a, w, precision = operands(h, params[name]["W"], mode)
            h = jnp.dot(a, w, precision=precision,
                        preferred_element_type=jnp.float32)
            if name != rows[-1][0]:
                h = stored(h, mode)
        h = h + params[name]["b"]
        if name != rows[-1][0]:
            h = relu(h)
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(h, axis=-1), axis=-1))
