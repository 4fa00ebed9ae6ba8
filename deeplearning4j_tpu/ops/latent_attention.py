"""Causal attention over a compressed latent's keys (multi-head latent
attention, MLA: the attention of the `deepseek_v2` family), kernels and
dense oracle.

A head's score is the sum of two products of different widths against
operands of different kinds:

    s_h[i, j] = (q_nope_h[i] . k_nope_h[j] + q_rope_h[i] . k_rope[j]) * scale
    o_h[i]    = sum_{j <= i} softmax_j(s_h[i, :]) v_h[j]

`k_nope_h` and `v_h` are the head's own (`Dn` and `Dv` wide, 128 and 128
as published), `k_rope` is ONE for all heads (`Dr` wide, 64: the part of
the key that carries the rotary positions, never compressed and never
expanded per head). So a query is `Dn + Dr` = 192 wide where a value is
128, and one operand is indexed without the head: neither fits
`ops/attention._run_flash`, which gives q, k and v one width and one key
head a query group.

The kernels are the flash kernels of `ops/attention.py` with those two
things changed. q comes as one `[.., Dn + Dr]` operand, nope lanes first;
the key tile is put together in VMEM from the head's `[keys, Dn]` tile and
the shared `[keys, Dr]` tile, whose index map drops the head (a tile the
grid step before already holds is not fetched again), so scores are one
product over 192 lanes and nothing `[H, T, Dr]` exists in HBM. Values,
accumulator and output are `Dv` wide, never padded to the query's width.
The online softmax is `ops/attention._softmax_update`, the backward's tile
`_dq_step` and `_dkdv_step` (dK/dV key-major), each kernel's tile its own
(`_pick_tile`), interior tiles build no mask (`_on_tiles`), dead steps of
the causal grid name the last live tile again. The dK/dV kernel writes the
head's `k_nope` gradient, and its part of the shared rope key's gradient
in float32, `[B H, T, Dr]`: the sum over the heads held is one reduction
after the kernel (67 MB written and read at 32 heads and 8,192 tokens, a
sixtieth of the kernel's own time on the chip; in the kernel it would tie
every head of a K tile to one sweep). The forward names its output and
log-sum-exp (`ops/attention.name_residuals`), so a checkpointed layer
keeps them and the forward kernel runs once a step.

`dense_latent_attention` is the oracle and the path off the TPU or where
the sequence does not tile (`kernel_defaults.latent_policy`): it makes
`[H, T, T]` scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention import (
    _LSE_LANES, _NEG_INF, _NT, _STAT_ROWS, _causal_kind, _causal_mask,
    _causal_tiles, _dkdv_step, _dq_step, _fit_block, _fold3, _last_live,
    _on_tiles, _pick_tile, _prec, _publish_bwd_steps, _softmax_finish,
    _softmax_init, _softmax_scratch, _softmax_update, _stat_lanes,
    _stat_rows, _tile_params, _unfold3, name_residuals,
)


def dense_latent_attention(q, k_nope, k_rope, v, scale=None):
    """The core by whole `[H, T, T]` scores, accumulated in float32: q
    [B, T, H, Dn + Dr], k_nope [B, T, H, Dn], k_rope [B, T, Dr], v
    [B, T, H, Dv] -> o [B, T, H, Dv]."""
    t, dn = q.shape[1], k_nope.shape[-1]
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    dot = functools.partial(jnp.einsum, precision=_prec(q.dtype),
                            preferred_element_type=jnp.float32)
    s = (dot("bqhd,bkhd->bhqk", q[..., :dn], k_nope)
         + dot("bqhd,bkd->bhqk", q[..., dn:], k_rope)) * scale
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return dot("bhqk,bkhd->bqhd", w.astype(v.dtype), v).astype(q.dtype)


def latent_eligible(t: int) -> bool:
    """The sequences the kernels tile: whole 128-lane tiles."""
    return t >= 128 and t % 128 == 0


def _keys(kn_ref, kr_ref):
    """A K tile as the scores read it: the head's nope lanes, then the
    shared rope lanes."""
    return jnp.concatenate([kn_ref[0], kr_ref[0]], axis=-1)


def _fwd_kernel(q_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref, acc_scr, m_scr,
                l_scr, *, scale):
    qb, kb = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], kn_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        _softmax_init(acc_scr, m_scr, l_scr)

    def tile(masked):
        q = q_ref[0]
        prec = _prec(q.dtype)
        s = jax.lax.dot_general(q, _keys(kn_ref, kr_ref), _NT,
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale
        if masked:
            # key 0 is live for every row, so the bias alone will do
            s = jnp.where(_causal_mask(qb, kb, bq, bk, False), s, _NEG_INF)
        _softmax_update(s, None, v_ref[0], acc_scr, m_scr, l_scr, prec)

    _on_tiles(tile, *_causal_kind(qb, kb, bq, bk))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        o, lse = _softmax_finish(acc_scr, m_scr, l_scr, bk)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = lse


def _bwd_dq_kernel(q_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale):
    qb, kb = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], kn_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(masked):
        mask = _causal_mask(qb, kb, bq, bk, False) if masked else None
        _dq_step(dq_scr, q_ref[0], _keys(kn_ref, kr_ref), v_ref[0], do_ref[0],
                 lse_ref[0], delta_ref[0], mask, scale)

    _on_tiles(tile, *_causal_kind(qb, kb, bq, bk))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(q_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dkn_ref, dkr_ref, dv_ref, dk_scr, dv_scr, *,
                     scale):
    """Grid (batch x heads, K tiles, Q tiles): the K tile's gradient
    gathers in scratch over the head's Q tiles, all 192 lanes of it; the
    rope lanes leave as this head's part of the shared key's gradient."""
    kb, qb = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], kn_ref.shape[1]
    dn = kn_ref.shape[2]

    @pl.when(qb == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(masked):
        mask = _causal_mask(qb, kb, bq, bk, True) if masked else None
        _dkdv_step(dk_scr, dv_scr, q_ref[0], _keys(kn_ref, kr_ref), v_ref[0],
                   do_ref[0], lse_ref[0, :1], delta_ref[0, :1], mask, scale)

    _on_tiles(tile, *_causal_kind(qb, kb, bq, bk))

    @pl.when(qb == pl.num_programs(2) - 1)
    def _():
        dkn_ref[0] = dk_scr[:, :dn].astype(dkn_ref.dtype)
        dkr_ref[0] = dk_scr[:, dn:]
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _tile(kernel: str, t: int, heads: int, block_q: int, block_k: int,
          interpret: bool):
    """(block_q, block_k) of one of the three kernels, its own
    (`ops/attention._pick_tile`), over the causal triangle; a backward
    kernel publishes its grid (`attention_bwd_steps`)."""
    if not latent_eligible(t) and not interpret:
        raise ValueError(f"latent attention cannot tile T={t}")
    fit = (lambda b: min(b, t)) if interpret else (
        lambda b: _fit_block(b, t))
    bq, bk = _pick_tile(
        "latent_attention", kernel, fit(block_q), fit(block_k),
        interpret=interpret, tiles=lambda bq, bk: _causal_tiles(t, bq, bk),
        steps=None if kernel == "fwd" else (
            lambda bq, bk: (t // bq) * (t // bk)),
        legal=lambda bq, bk: t % bq == 0 and t % bk == 0)
    if t % bq or t % bk:
        raise ValueError(f"tiles of {bq} x {bk} do not divide T={t}")
    if kernel != "fwd":
        nq, nk = t // bq, t // bk
        interior = sum(min(nk, (i * bq + 1) // bk) for i in range(nq))
        _publish_bwd_steps("latent_attention", kernel, heads, nq * nk,
                           _causal_tiles(t, bq, bk), interior)
    return bq, bk


def _run_fwd(q3, kn3, kr, v3, *, scale, block_q, block_k, interpret):
    """q3 [B H, T, Dn + Dr], kn3 [B H, T, Dn], kr [B, T, Dr], v3
    [B H, T, Dv] -> (o3 [B H, T, Dv], lse [B H, T])."""
    bh, t, d = q3.shape
    heads, dn, dr, dv = bh // kr.shape[0], kn3.shape[2], kr.shape[2], \
        v3.shape[2]
    bq, bk = _tile("fwd", t, bh, block_q, block_k, interpret)
    # a dead step names the block before it again: nothing is fetched
    key = lambda i, j: jnp.minimum(j, _last_live(i, bq, bk))
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        name="latent_attention_fwd",
        grid=(bh, t // bq, t // bk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, bk, dn), lambda b, i, j: (b, key(i, j), 0)),
            # the rope key: one for all heads, read by the batch alone
            pl.BlockSpec((1, bk, dr),
                         lambda b, i, j: (b // heads, key(i, j), 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, key(i, j), 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, bq, _LSE_LANES),
                                lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, dv), q3.dtype),
                   jax.ShapeDtypeStruct((bh, t, _LSE_LANES), jnp.float32)],
        scratch_shapes=_softmax_scratch(bq, dv),
        compiler_params=_tile_params(bq, bk, d, q3.dtype.itemsize),
        interpret=interpret,
    )(q3, kn3, kr, v3)
    return o, lse[..., 0]


def _run_bwd(q3, kn3, kr, v3, o3, lse, do3, *, scale, block_q, block_k,
             interpret):
    """dq3, dkn3, dkr (summed over the heads, [B, T, Dr]) and dv3 from
    the residuals."""
    bh, t, d = q3.shape
    batch = kr.shape[0]
    heads, dn, dr, dv = bh // batch, kn3.shape[2], kr.shape[2], v3.shape[2]
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    params = functools.partial(_tile_params, d=d,
                               itemsize=q3.dtype.itemsize, backward=True)

    bq, bk = _tile("dkdv", t, bh, block_q, block_k, interpret)
    # causal: no earlier than the first Q tile with a row at or after K
    # tile j's first key
    row = lambda j, i: jnp.maximum(i, j * bk // bq)
    wide = lambda w: pl.BlockSpec((1, bq, w),
                                  lambda b, j, i: (b, row(j, i), 0))
    stat = pl.BlockSpec((1, _STAT_ROWS, bq),
                        lambda b, j, i: (b, 0, row(j, i)))
    key = lambda w: pl.BlockSpec((1, bk, w), lambda b, j, i: (b, j, 0))
    shared = pl.BlockSpec((1, bk, dr), lambda b, j, i: (b // heads, j, 0))
    dkn, dkr, dvv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale),
        name="latent_attention_bwd_dkdv",
        grid=(bh, t // bk, t // bq),
        in_specs=[wide(d), key(dn), shared, key(dv), wide(dv), stat, stat],
        out_specs=[key(dn), key(dr), key(dv)],
        out_shape=[jax.ShapeDtypeStruct(kn3.shape, kn3.dtype),
                   jax.ShapeDtypeStruct((bh, t, dr), jnp.float32),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params(bq, bk), interpret=interpret,
    )(q3, kn3, kr, v3, do3, _stat_rows(lse), _stat_rows(delta))
    dkr = jnp.sum(dkr.reshape(batch, heads, t, dr), axis=1).astype(kr.dtype)

    bq, bk = _tile("dq", t, bh, block_q, block_k, interpret)
    live = lambda i, j: jnp.minimum(j, _last_live(i, bq, bk))
    wide = lambda w: pl.BlockSpec((1, bq, w), lambda b, i, j: (b, i, 0))
    stat = pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0))
    key = lambda w: pl.BlockSpec((1, bk, w),
                                 lambda b, i, j: (b, live(i, j), 0))
    shared = pl.BlockSpec((1, bk, dr),
                          lambda b, i, j: (b // heads, live(i, j), 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale),
        name="latent_attention_bwd_dq",
        grid=(bh, t // bq, t // bk),
        in_specs=[wide(d), key(dn), shared, key(dv), wide(dv), stat, stat],
        out_specs=wide(d),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params(bq, bk), interpret=interpret,
    )(q3, kn3, kr, v3, do3, _stat_lanes(lse), _stat_lanes(delta))
    return dq, dkn, dkr, dvv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def latent_attention(q, k_nope, k_rope, v, scale=None, block_q: int = 512,
                     block_k: int = 512, interpret: bool = False):
    """The core by the kernels: q [B, T, H, Dn + Dr] (a head's nope lanes
    first, then its rope lanes, positions on), k_nope [B, T, H, Dn],
    k_rope [B, T, Dr] (positions on), v [B, T, H, Dv] -> o [B, T, H, Dv].
    `scale` defaults to `(Dn + Dr) ** -0.5`. T has to be
    `latent_eligible` (any T whole tiles divide in interpret mode)."""
    return _latent_fwd(q, k_nope, k_rope, v, scale, block_q, block_k,
                       interpret)[0]


def _latent_fwd(q, k_nope, k_rope, v, scale, block_q, block_k, interpret):
    s = q.shape[-1] ** -0.5 if scale is None else scale
    q3, shape_q = _fold3(q)
    kn3, shape_k = _fold3(k_nope)
    v3, shape_v = _fold3(v)
    o3, lse = _run_fwd(q3, kn3, k_rope, v3, scale=s, block_q=block_q,
                       block_k=block_k, interpret=interpret)
    o3, lse = name_residuals(o3, lse)
    return (_unfold3(o3, shape_v),
            (q3, kn3, k_rope, v3, o3, lse, shape_q, shape_k, shape_v))


def _latent_bwd(scale, block_q, block_k, interpret, res, do):
    q3, kn3, kr, v3, o3, lse, shape_q, shape_k, shape_v = res
    s = q3.shape[-1] ** -0.5 if scale is None else scale
    do3, _ = _fold3(do)
    dq, dkn, dkr, dv = _run_bwd(q3, kn3, kr, v3, o3, lse, do3, scale=s,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)
    return (_unfold3(dq, shape_q), _unfold3(dkn, shape_k), dkr,
            _unfold3(dv, shape_v))


latent_attention.defvjp(_latent_fwd, _latent_bwd)
