"""Decayed linear attention as a chunked scan (XLA's own products).

The mixer of a `lightning-attn` layer (Lightning Attention-2, MiniMax-01,
MiniCPM-SALA): a linear recurrence with one decay a head over a
[Dh, Dh] state,

    S_t = lambda_h S_{t-1} + k_t^T v_t,      o_t = q_t S_t * scale,

that is `o_t = scale * sum_{s<=t} lambda_h^(t-s) (q_t . k_s) v_s`: no
softmax, no normaliser. Trained, it runs a chunk of `chunk` tokens at a
time: inside a chunk the quadratic form `((Q K^T) * D) V` with
`D_ij = lambda^(i-j)` for `i >= j`, between chunks the state. All chunks'
inner products and all chunks' summaries `sum_j lambda^(C-1-j) k_j^T v_j`
are batched products; only the [Dh, Dh] recurrence over the summaries is
a `lax.scan`, and its reverse, under `jax.grad`, is the backward pass
through the carried state. Every decay factor is a power with a
non-negative exponent (never `lambda^-j`, which overflows for the fast
heads), computed in float32 like the state; the result is the
recurrence's own, not a truncated decay's.

No Pallas call here, so no roofline metric: the trace's own counts stand
for these products (scope `linear_attention_core`, opened by the layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def decay_rates(num_heads: int, layer_index: int, num_layers: int):
    """`-log lambda_h` of a layer's heads, h = 1..H: the slopes
    `2^(-8h/H)` of Lightning Attention-2 times the layer's factor
    `1 - l/(L-1) + 1e-5`, `l` the layer's index among `L` (float32
    numpy: a constant of the model, not a parameter)."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    slopes = 2.0 ** (-8.0 * h / num_heads)
    factor = 1.0 - layer_index / max(num_layers - 1, 1) + 1e-5
    return (slopes * factor).astype(np.float32)


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def linear_attention(q, k, v, rates, *, scale=None, chunk: int = 256):
    """q, k, v [B, T, H, Dh]; `rates` [H] = -log lambda_h. Returns
    o [B, T, H, Dh] in q's dtype. `T` need not divide into chunks: the
    tail is padded with zero keys and values, which add nothing."""
    b, t, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    c = min(chunk, t)
    n = -(-t // c)
    if n * c != t:
        pad = ((0, 0), (0, n * c - t), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    qc, kc, vc = (a.reshape(b, n, c, h, d) for a in (q, k, v))
    prec = _prec(q.dtype)
    rates = jnp.asarray(rates, jnp.float32)                  # [H]
    i = jnp.arange(c, dtype=jnp.float32)
    gap = i[:, None] - i[None, :]                            # i - j
    inside = jnp.where(gap >= 0,
                       jnp.exp(-rates[:, None, None] * jnp.maximum(gap, 0)),
                       0.0)                                  # [H, C, C]
    to_end = jnp.exp(-rates[None, :] * (c - 1 - i)[:, None])  # [C, H]
    from_start = jnp.exp(-rates[None, :] * (i + 1)[:, None])  # [C, H]
    whole = jnp.exp(-rates * c)                              # [H]

    s = jnp.einsum("bnihd,bnjhd->bnhij", qc, kc, precision=prec,
                   preferred_element_type=jnp.float32) * inside
    o = jnp.einsum("bnhij,bnjhd->bnihd", s.astype(v.dtype), vc,
                   precision=prec, preferred_element_type=jnp.float32)
    # a chunk's own sum of k^T v, decayed to the chunk's last token
    k_end = (kc.astype(jnp.float32) * to_end[:, :, None]).astype(k.dtype)
    summary = jnp.einsum("bnjhd,bnjhe->nbhde", k_end, vc, precision=prec,
                         preferred_element_type=jnp.float32)

    def carry(state, own):
        return whole[:, None, None] * state + own, state

    _, before = jax.lax.scan(carry, jnp.zeros((b, h, d, d), jnp.float32),
                             summary)                        # [N, B, H, D, D]
    q_start = (qc.astype(jnp.float32)
               * from_start[:, :, None]).astype(q.dtype)
    o = o + jnp.einsum("bnihd,nbhde->bnihe", q_start,
                       before.astype(q.dtype), precision=prec,
                       preferred_element_type=jnp.float32)
    o = (o * scale).astype(q.dtype).reshape(b, n * c, h, d)
    return o[:, :t]


def linear_attention_recurrence(q, k, v, rates, *, scale=None):
    """The recurrence itself, a token at a time in float32: the oracle
    the chunked form is held to in the tests."""
    b, t, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    lam = jnp.exp(-jnp.asarray(rates, jnp.float32))[None, :, None, None]
    hi = jax.lax.Precision.HIGHEST

    def step(state, qkv):
        qt, kt, vt = qkv                                     # [B, H, D]
        state = lam * state + jnp.einsum("bhd,bhe->bhde", kt, vt,
                                         precision=hi)
        return state, jnp.einsum("bhd,bhde->bhe", qt, state, precision=hi)

    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), jnp.float32),
                        (f32(q), f32(k), f32(v)))
    return jnp.moveaxis(o, 0, 1) * scale


__all__ = ["decay_rates", "linear_attention", "linear_attention_recurrence"]
