"""Decayed linear attention: the constant-decay case of the selective scan.

The mixer of a `lightning-attn` layer (Lightning Attention-2, MiniMax-01,
MiniCPM-SALA): a linear recurrence with one decay a head over a
[Dh, Dh] state,

    S_t = lambda_h S_{t-1} + k_t^T v_t,      o_t = q_t S_t * scale,

that is `o_t = scale * sum_{s<=t} lambda_h^(t-s) (q_t . k_s) v_s`: no
softmax, no normaliser. It is `ops/selective_scan.py`'s recurrence with a
step of `scale` at every token, `A_h = log(lambda_h) / scale` (so that
`dt A = log lambda_h` and `dt x = scale v`), `x = v`, `B = k`, `C = q` and
a group of B and C a head, and runs as that op's chunked scan: the
quadratic form inside a chunk, a `lax.scan` over the chunks' states whose
reverse, under `jax.grad`, is the backward pass through the carried
state, every decay an exponential of a difference that is never
positive, in float32 like the state. A scan of its own, with the chunk's
decay matrix one constant for all chunks, read 9.69 against this one's
9.76 ms forward and backward at 16,384 tokens and 32 heads of 128 in
bfloat16 on the chip (`PERF.md` section 6, PR 42): one stands.

No Pallas call here, so no roofline metric: the trace's own counts stand
for these products (scope `linear_attention_core`, opened by the layer).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.selective_scan import (
    selective_scan, selective_scan_recurrence,
)


def decay_rates(num_heads: int, layer_index: int, num_layers: int):
    """`-log lambda_h` of a layer's heads, h = 1..H: the slopes
    `2^(-8h/H)` of Lightning Attention-2 times the layer's factor
    `1 - l/(L-1) + 1e-5`, `l` the layer's index among `L` (float32
    numpy: a constant of the model, not a parameter)."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    slopes = 2.0 ** (-8.0 * h / num_heads)
    factor = 1.0 - layer_index / max(num_layers - 1, 1) + 1e-5
    return (slopes * factor).astype(np.float32)


def _as_scan(scan, q, k, v, rates, scale, **kw):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    steps = jnp.full(q.shape[:3], scale, jnp.float32)
    return scan(v, steps, -jnp.asarray(rates, jnp.float32) / scale, k, q,
                **kw)


def linear_attention(q, k, v, rates, *, scale=None, chunk: int = 256):
    """q, k, v [B, T, H, Dh]; `rates` [H] = -log lambda_h. Returns
    o [B, T, H, Dh] in q's dtype. `T` need not divide into chunks."""
    return _as_scan(selective_scan, q, k, v, rates, scale, chunk=chunk)


def linear_attention_recurrence(q, k, v, rates, *, scale=None):
    """The recurrence itself, a token at a time in float32: the oracle
    the chunked form is held to in the tests."""
    return _as_scan(selective_scan_recurrence, q, k, v, rates, scale)


__all__ = ["decay_rates", "linear_attention", "linear_attention_recurrence"]
