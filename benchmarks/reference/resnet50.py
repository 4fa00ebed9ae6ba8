"""Plain reference: ResNet-50 (He et al. 2015, arXiv:1512.03385, table 1)
training with softmax cross-entropy and Nesterov momentum.

Straightforward `jax.numpy` / `lax.conv_general_dilated` in float32 at
matmul precision "highest", `jax.grad`, and the update rule written out.
It imports nothing of the program and takes nothing the program made: it
makes its own weights from the seed, and the harness hands THOSE to the
program. Departures from the paper, all to match what `zoo.ResNet50`
states it computes: NHWC; the stride of a down-sampling block sits on its
first 1x1 convolution (the paper's v1 placement); convolutions have no
bias; batch norm uses the batch's biased variance with eps 1e-5; the
update is ND4J's Nesterov rule, v' = mu v - lr g, p += mu v' - lr g.

`mode` is one of `arithmetic.py`'s: float32, bfloat16, float8 (the control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference.arithmetic import operands, relu, stored

STAGES = (("res2", (64, 64, 256), 3, 1), ("res3", (128, 128, 512), 4, 2),
          ("res4", (256, 256, 1024), 6, 2), ("res5", (512, 512, 2048), 3, 2))
BN_EPS = 1e-5


def conv_table(cfg):
    """Every weighted layer in forward order as (name, kernel, c_in, c_out,
    stride, output side); the last row is the classifier (kernel 0)."""
    side, _, c_in = cfg["input_shape"]
    rows = []
    side = (side + 6 - 7) // 2 + 1
    rows.append(("stem", 7, c_in, 64, 2, side))
    side, c_in = -(-side // 2), 64
    for stage, (f1, f2, f3), blocks, stride in STAGES:
        for b in range(blocks):
            name = f"{stage}{chr(97 + b)}"
            s = stride if b == 0 else 1
            out = (side - 1) // s + 1
            rows.append((f"{name}_a", 1, c_in, f1, s, out))
            rows.append((f"{name}_b", 3, f1, f2, 1, out))
            rows.append((f"{name}_c", 1, f2, f3, 1, out))
            if b == 0:
                rows.append((f"{name}_sc", 1, c_in, f3, s, out))
            side, c_in = out, f3
    rows.append(("output", 0, c_in, cfg["label_shape"][-1], 1, 1))
    return rows


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one image's forward pass, from the shapes."""
    return sum((k * k or 1) * ci * co * side * side
               for _, k, ci, co, _, side in conv_table(cfg))


def init_params(seed: int, cfg):
    """He-normal kernels, zero shifts and bias, unit scales (except the
    last of every residual block: `residual_gamma`), from the seed, as
    {vertex name: {leaf: float32 array}} under the zoo model's names."""
    rows = conv_table(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, k, ci, co, _, _) in enumerate(rows):
            sub = jax.random.fold_in(key, i)
            if k == 0:
                params[name] = {
                    "W": jax.random.normal(sub, (ci, co), jnp.float32)
                    * math.sqrt(2.0 / ci),
                    "b": jnp.zeros((co,), jnp.float32)}
                continue
            params[f"{name}_conv"] = {
                "W": jax.random.normal(sub, (k, k, ci, co), jnp.float32)
                * math.sqrt(2.0 / (k * k * ci))}
            gamma = cfg.get("residual_gamma", 1.0) if name.endswith("_c") \
                else 1.0
            params[f"{name}_bn"] = {
                "gamma": jnp.full((co,), gamma, jnp.float32),
                "beta": jnp.zeros((co,), jnp.float32)}
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _conv(x, w, stride, pad, mode):
    x, w, precision = operands(x, w, mode)
    return stored(lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
        preferred_element_type=jnp.float32), mode)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def loss_fn(params, x, y, mode="float32"):
    """Mean softmax cross-entropy of one batch in training mode (batch
    statistics in every batch norm). x: [B, H, W, C] float32, y: one-hot."""
    def unit(h, name, stride, pad, use_relu=True):
        h = _bn(_conv(h, params[f"{name}_conv"]["W"], stride, pad, mode),
                params[f"{name}_bn"])
        return relu(h) if use_relu else h

    h = unit(x, "stem", 2, 3)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for stage, _, blocks, stride in STAGES:
        for b in range(blocks):
            name = f"{stage}{chr(97 + b)}"
            s = stride if b == 0 else 1
            r = unit(h, f"{name}_a", s, 0)
            r = unit(r, f"{name}_b", 1, 1)
            r = unit(r, f"{name}_c", 1, 0, use_relu=False)
            if b == 0:
                h = unit(h, f"{name}_sc", s, 0, use_relu=False)
            h = stored(relu(r + h), mode)
    h = jnp.mean(h, axis=(1, 2))
    a, w, precision = operands(h, params["output"]["W"], mode)
    logits = jnp.dot(a, w, precision=precision,
                     preferred_element_type=jnp.float32)
    logits = logits + params["output"]["b"]
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits, axis=-1),
                             axis=-1))
