"""The arithmetic the plain references share: the modes of a matrix
product, the control's fp8 rounding, and the relu convention. Imports
nothing of the program.

`mode` selects the arithmetic of every convolution and matrix product:
"float32" (the reference proper, precision "highest"), "bfloat16" (what the
configurations state) and "float8" (the control: an fp8 step, matmul
operands and the activations kept between ops rounded to float8_e4m3fn and
their cotangents to float8_e5m2 under per-tensor scales, accumulation in
float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def relu(x):
    """max(x, 0) with derivative 0 AT 0, the usual convention (and
    `jax.nn.relu`'s). `jnp.maximum(x, 0)` would split the tie and give
    0.5, which shows wherever a pre-activation is exactly 0: in every
    residual sum of a net whose blocks' last scales start at 0."""
    return jnp.where(x > 0, x, 0.0)


def _round_scaled(a, dtype, top):
    scale = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(a):
    """An fp8 step's rounding of one tensor: the value to float8_e4m3fn,
    its cotangent to float8_e5m2, each under a per-tensor scale."""
    return _round_scaled(a, jnp.float8_e4m3fn, E4M3_MAX)


fp8.defvjp(lambda a: (fp8(a), None),
           lambda _, ct: (_round_scaled(ct, jnp.float8_e5m2, E5M2_MAX),))


def stored(h, mode):
    """An activation as the step keeps it between two ops: the control
    keeps it in fp8 too, which is what would tempt a step bound by the
    bytes its elementwise passes move."""
    return fp8(h) if mode == "float8" else h


def operands(a, b, mode):
    """(a, b, precision) of one matrix product or convolution."""
    if mode == "float32":
        return a, b, lax.Precision.HIGHEST
    if mode == "float8":
        a, b = fp8(a), fp8(b)
    # operands rounded to bfloat16, products summed in float32: one pass
    # of the matrix unit. Kept as float32 arrays so that `jax.grad` gets
    # float32 cotangents (rounded to bfloat16 on their way back).
    return (a.astype(jnp.bfloat16).astype(jnp.float32),
            b.astype(jnp.bfloat16).astype(jnp.float32),
            lax.Precision.DEFAULT)
