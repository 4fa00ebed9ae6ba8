"""Mamba-2's selective state-space scan (SSD) as a chunked scan (XLA's own
products).

The core of a `mamba` layer (Mamba-2; Granite-4.0-H, Nemotron-H, Zamba2):
a linear recurrence whose decay is DATA, a per-token, per-head
`exp(dt_t,h A_h)` with `A_h < 0`, over a [P, N] state a head,

    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h B_t^T,
    y_t,h = S_t C_t + D_h x_t,h,

with B and C [N] vectors shared by the heads of a group (`n_groups`; one
group for all heads as Granite publishes it). Trained, it runs a chunk of
`chunk` tokens at a time. Inside a chunk `cs = cumsum(dt A)` per head and
`L_ij = exp(cs_i - cs_j)` for `i >= j`; the chunk's own part is the
quadratic form `((C B^T) * L) (dt x)`, the `C B^T` product made once a
chunk and group, not once a head. Between chunks the state: every chunk's
summary `sum_j exp(cs_last - cs_j) dt_j x_j B_j^T` is a batched product,
only the [H, P, N] recurrence over the summaries is a `lax.scan`, and its
reverse, under `jax.grad`, is the backward pass through the carried
state; a token reads the state carried into its chunk through `exp(cs_i)`.
Every exponent is a difference of the cumulative sum that is never
positive (never `exp(-cs_j)`, which overflows for a fast head), computed
in float32 like the state; the result is the recurrence's own, not a
truncated decay's.

`ops/linear_attention.py` runs decayed linear attention as the constant
case of this op (`dt = scale`, `A = -rate / scale`, `x = v`, `B = k`,
`C = q`, a group a head).

No Pallas call here, so no roofline metric: the trace's own counts stand
for these products (scope `ssm_core`, opened by the layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def selective_scan(x, dt, a, b, c, d=None, *, chunk: int = 256):
    """x [B, T, H, P]; dt [B, T, H], the step sizes (after their
    softplus); `a` [H], negative; b, c [B, T, G, N] with G dividing H
    (head h reads group `h // (H / G)`); `d` [H], the skip, or None.
    Returns y [B, T, H, P] in x's dtype. `T` need not divide into chunks:
    the tail is padded with zero steps, which neither decay the state nor
    add to it."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    per = h // g
    size = min(chunk, t)
    nc = -(-t // size)
    dt = dt.astype(jnp.float32)
    skip = x
    if nc * size != t:
        pad = lambda v: jnp.pad(
            v, ((0, 0), (0, nc * size - t)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = pad(x), pad(dt), pad(b), pad(c)
    prec = _prec(x.dtype)
    a = jnp.asarray(a, jnp.float32)
    xc = x.reshape(bsz, nc, size, g, per, p)
    bc, cc = (v.reshape(bsz, nc, size, g, n) for v in (b, c))
    dtc = dt.reshape(bsz, nc, size, g, per)
    cs = jnp.cumsum(dtc * a.reshape(g, per), axis=2)     # [B, n, C, G, h]
    # dt x: the step's size on the input, once, not on every score
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(x.dtype)

    i = jnp.arange(size)
    heads_first = jnp.moveaxis(cs, 2, -1)                # [B, n, G, h, C]
    gap = heads_first[..., :, None] - heads_first[..., None, :]
    inside = jnp.exp(jnp.where(i[:, None] >= i[None, :], gap, -jnp.inf))
    cb = jnp.einsum("bnigk,bnjgk->bngij", cc, bc, precision=prec,
                    preferred_element_type=jnp.float32)
    s = (cb[:, :, :, None] * inside).astype(x.dtype)     # [B, n, G, h, i, j]
    y = jnp.einsum("bnghij,bnjghp->bnighp", s, xdt, precision=prec,
                   preferred_element_type=jnp.float32)
    # a chunk's own sum of dt x B^T, decayed to the chunk's last token
    last = cs[:, :, -1]                                  # [B, n, G, h]
    x_end = (xdt.astype(jnp.float32)
             * jnp.exp(last[:, :, None] - cs)[..., None]).astype(x.dtype)
    summary = jnp.einsum("bnjghp,bnjgk->nbghpk", x_end, bc, precision=prec,
                         preferred_element_type=jnp.float32)
    whole = jnp.moveaxis(jnp.exp(last), 1, 0)            # [n, B, G, h]

    def carry(state, own):
        kept, summary = own
        return kept[..., None, None] * state + summary, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((bsz, g, per, p, n), jnp.float32), (whole, summary))
    read = jnp.einsum("bnigk,nbghpk->bnighp", cc, before.astype(x.dtype),
                      precision=prec, preferred_element_type=jnp.float32)
    y = y + read * jnp.exp(cs)[..., None]
    y = y.reshape(bsz, nc * size, h, p)[:, :t]
    if d is not None:
        y = y + skip.astype(jnp.float32) * jnp.asarray(
            d, jnp.float32)[:, None]
    return y.astype(x.dtype)


def chunk_carry(dt, a, *, chunk: int = 256):
    """The mean over heads and chunks of `exp(sum of dt A over the
    chunk)`: how much of a state survives one chunk (float32 scalar, no
    gradient). dt [B, T, H] after its softplus, `a` [H] negative."""
    bsz, t, h = dt.shape
    size = min(chunk, t)
    whole = t // size * size            # a ragged tail is no whole chunk
    da = jax.lax.stop_gradient(dt[:, :whole].astype(jnp.float32)
                               * jnp.asarray(a, jnp.float32))
    return jnp.mean(jnp.exp(jnp.sum(
        da.reshape(bsz, whole // size, size, h), axis=2)))


def selective_scan_recurrence(x, dt, a, b, c, d=None):
    """The recurrence itself, a token at a time in float32: the oracle
    the chunked form is held to in the tests."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    per = h // g
    hi = jax.lax.Precision.HIGHEST
    a = jnp.asarray(a, jnp.float32).reshape(g, per)

    def step(state, row):
        xt, dtt, bt, ct = row       # [B, G, h, P], [B, G, h], [B, G, N] x 2
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + jnp.einsum("bghp,bgk->bghpk", xt * dtt[..., None], bt,
                              precision=hi))
        return state, jnp.einsum("bghpk,bgk->bghp", state, ct, precision=hi)

    f32 = lambda v: jnp.moveaxis(v.astype(jnp.float32), 1, 0)
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, g, per, p, n), jnp.float32),
        (f32(x).reshape(t, bsz, g, per, p), f32(dt).reshape(t, bsz, g, per),
         f32(b), f32(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t, h, p)
    if d is not None:
        y = y + x.astype(jnp.float32) * jnp.asarray(d, jnp.float32)[:, None]
    return y


__all__ = ["chunk_carry", "selective_scan", "selective_scan_recurrence"]
