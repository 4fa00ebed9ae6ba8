"""Blockwise fused attention kernel (Pallas TPU).

No reference counterpart (DL4J predates attention — SURVEY §5 "no attention
layers at all"); this backs the framework's transformer extension
(`nn/layers/attention.py`, `parallel/ring_attention.py`) the way cuDNN
helpers backed conv layers in the reference (SURVEY §2.3 seam).

Design: classic flash-attention forward — grid over (batch·heads, q blocks,
K blocks); one [Bk, D] K/V tile is resident in VMEM at a time, with the
online-softmax statistics (running max m, normalizer l, accumulator) carried
in VMEM scratch across the innermost K grid dimension, so neither the
[T, T] score matrix nor the full K/V sequence ever sits in VMEM/HBM at
once. Causal masking skips dead K blocks' FLOPs via block-index
comparison.

Backward (FlashAttention-2 style, `backward="pallas"`): the
forward rule additionally saves the per-row log-sum-exp L = m + log(l)
(O(T) residual memory — q/k/v/o/L, never the [T, T] scores). Two Pallas
kernels then rematerialize score tiles blockwise: a dK/dV kernel with the
K/V tile pinned in VMEM scratch while sweeping Q blocks, and a dQ kernel
with the Q tile pinned while sweeping K blocks, using the softmax-vjp
identity ds = p * (dp - Δ) with Δ = rowsum(do · o) precomputed by XLA.
`backward="dense"` keeps the previous whole-[T, T] XLA recompute as a
fallback/oracle path. The default (`backward=None`) resolves from the
measured-winner table in `ops/kernel_defaults.py` — see that module for
the dispatch policy and its env escape hatches.

Under gradient checkpointing: the forward rules of this kernel and of
`ops/banded_attention.py`'s name the kernel's own output and the one-lane
L (`name_residuals`: the checkpoint names `attention_out` and
`attention_lse`, `RESIDUAL_NAMES`), and a checkpointed layer's policy
keeps exactly those (`models/multilayer._checkpointed`). They are what the
two backward kernels read of the forward and what only a second run of the
forward kernel could remake: one hidden-sized tensor and T floats a head.
`flash_attention_with_lse` names nothing: under a ring that would keep
every ring step's pair.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LSE_LANES = 128   # lane width for per-row statistics outputs (TPU tiling)

# the checkpoint names of a forward kernel's output (in the layout its
# backward reads) and of its rows' one-lane log-sum-exp
RESIDUAL_NAMES = ("attention_out", "attention_lse")


class _Named(threading.local):
    calls = 0


_named = _Named()


def residuals_named() -> int:
    """How many forward kernel calls this thread has traced so far with
    `name_residuals`; a checkpointed layer reads it before and after its
    own trace."""
    return _named.calls


def name_residuals(o, lse):
    """`o` and `lse` under `RESIDUAL_NAMES`, for a forward rule whose
    backward is the Pallas one. A no-op without a policy that keeps the
    names; the primal output has to be made from the named `o`."""
    _named.calls += 1
    return (checkpoint_name(o, RESIDUAL_NAMES[0]),
            checkpoint_name(lse, RESIDUAL_NAMES[1]))


def _dense_attention(q, k, v, causal: bool, scale: float):
    """Reference O(T^2) attention used for the recompute backward."""
    scores = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), jnp.bool_))
        scores = jnp.where(mask[None], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v)


def _prec(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool,
                  scale: float, with_lse: bool):
    """Grid = (batch·heads, q blocks, K blocks): the K/V HBM→VMEM transfer
    is blocked by the grid itself (one [Bk, D] tile resident at a time),
    with the online-softmax state carried in VMEM scratch across the
    innermost (K) grid dimension. With `with_lse` the per-row
    log-sum-exp L = m + log(l) is emitted too (the training-path residual
    the Pallas backward rematerializes scores from)."""
    if with_lse:
        lse_ref, acc_scr, m_scr, l_scr = rest
    else:
        acc_scr, m_scr, l_scr = rest
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    q = q_ref[0]                                  # [Bq, D]
    bq, d = q.shape
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Causal: K blocks strictly above this Q block's last row are dead —
    # skip their FLOPs (the DMA still happens; acceptable at Bk=128).
    relevant = (kb * block_k <= (qb + 1) * bq - 1) if causal else (kb >= 0)

    @pl.when(relevant)
    def _():
        k = k_ref[0]                              # [Bk, D]
        v = v_ref[0]
        prec = _prec(q.dtype)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        if causal:
            q_ids = (qb * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0))
            k_ids = (kb * block_k
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1))
            s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=prec)

    @pl.when(kb == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if with_lse:
            # Per-row scalar broadcast across a 128-lane last dim — the
            # narrowest output layout Mosaic accepts for row statistics
            # (cf. MIN_BLOCK_SIZE in jax's in-tree TPU flash kernel).
            lse_ref[0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                          (bq, _LSE_LANES))


def _fit_block(block: int, t: int) -> int:
    """Largest block <= requested that divides t (t must be a multiple of
    the 128-lane minimum). Block size is the decisive perf lever on TPU;
    the production sizes come from the measured-winner table in
    ops/kernel_defaults.py, populated by tools/kernel_bench.py."""
    block = min(block, t)
    while block > 128 and t % block:
        block -= 128
    if t % block:
        raise ValueError(f"seq len {t} not divisible by any block <= "
                         f"{block} (need a multiple of 128)")
    return block


def _group(q, k) -> int:
    """Query heads per KV head of folded [B·H, T, D] queries against
    [B·Hkv, T, D] keys: row b of q reads row b // g of k and v."""
    if q.shape[0] % k.shape[0]:
        raise ValueError(f"{q.shape[0]} query rows over {k.shape[0]} KV "
                         f"rows: Hkv must divide H")
    return q.shape[0] // k.shape[0]


def _run_flash(q, k, v, *, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: bool, with_lse: bool = False):
    bh, tq, d = q.shape
    tk = k.shape[1]
    if causal and tq != tk:
        raise ValueError(
            f"causal attention requires Tq == Tk (got {tq} vs {tk}); "
            "cross-attention is non-causal")
    g = _group(q, k)
    block_q = _fit_block(block_q, tq)
    block_k = _fit_block(block_k, tk)
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               with_lse=with_lse)
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, tq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, block_q, _LSE_LANES),
                                      lambda b, i, j: (b, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, tq, _LSE_LANES), jnp.float32))
    out = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # GQA: query head b reads KV head b // g, never a copy of it
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=tuple(out_shape) if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        # batch/Q-block dims have no cross-step state -> Mosaic may
        # parallelize and pipeline them; the K sweep carries scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    if with_lse:
        o, lse = out
        # Keep only one lane of the lane-broadcast row stats: residual
        # memory between forward and backward is O(T), not O(128*T).
        return o, lse[..., 0]
    return out, None


# ----------------------------------------------------- blockwise backward
def _bwd_tile(q, k, v, do, lse_col, delta_col, qb, kb, bq, block_k, causal,
              scale):
    """Shared score-tile rematerialization for both backward kernels:
    p = exp(s - L) row-wise, ds = p * (do·vᵀ - Δ) * scale."""
    prec = _prec(q.dtype)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                precision=prec) * scale
    if causal:
        q_ids = (qb * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0))
        k_ids = (kb * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1))
        s = jnp.where(q_ids >= k_ids, s, _NEG_INF)
    p = jnp.exp(s - lse_col)                       # [Bq, Bk] f32
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32,
                 precision=prec)
    ds = p * (dp - delta_col) * scale
    return p, ds


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                           scale: float, nq: int):
    """Grid = (batch·KV heads, K blocks, group x Q blocks): the K/V tile's
    gradient accumulates in VMEM scratch across the innermost sweep, which
    takes the `nq` Q blocks of each query head of the group in turn."""
    kb = pl.program_id(1)
    step = pl.program_id(2)
    last = pl.num_programs(2) - 1
    qb = step % nq
    q = q_ref[0]
    bq = q.shape[0]
    block_k = k_ref.shape[1]

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Causal: Q blocks entirely above this K block's first row are dead.
    relevant = ((qb + 1) * bq - 1 >= kb * block_k) if causal else (qb >= 0)

    @pl.when(relevant)
    def _():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        prec = _prec(q.dtype)
        p, ds = _bwd_tile(q, k, v, do, lse_ref[0, :, 0:1],
                          delta_ref[0, :, 0:1], qb, kb, bq, block_k,
                          causal, scale)
        dv_scr[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32, precision=prec)
        dk_scr[:] += jnp.dot(ds.astype(q.dtype).T, q,
                             preferred_element_type=jnp.float32, precision=prec)

    @pl.when(step == last)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, scale: float):
    """Grid = (batch·heads, Q blocks, K blocks): the Q tile's gradient
    accumulates in VMEM scratch across the innermost K sweep."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    q = q_ref[0]
    bq = q.shape[0]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    relevant = (kb * block_k <= (qb + 1) * bq - 1) if causal else (kb >= 0)

    @pl.when(relevant)
    def _():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        prec = _prec(q.dtype)
        _, ds = _bwd_tile(q, k, v, do, lse_ref[0, :, 0:1],
                          delta_ref[0, :, 0:1], qb, kb, bq, block_k,
                          causal, scale)
        dq_scr[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32, precision=prec)

    @pl.when(kb == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _run_flash_bwd(q, k, v, o, lse, do, *, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   dlse=None):
    """Blockwise dq/dk/dv from O(T) residuals (q, k, v, o, L).

    `lse` is the narrow [BH, Tq] log-sum-exp saved by the forward; both
    row stats are re-broadcast here to the lane-wide layout the kernels
    read. `dlse` (optional, [BH, Tq]) is the cotangent of the emitted
    log-sum-exp when the caller exposes it as an output (ring attention's
    merge does): since dL/ds_ij = p_ij, it folds into the softmax-vjp
    identity as a shift on Δ — ds = p * (dp - (Δ - dL)).
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    g = _group(q, k)
    block_q = _fit_block(block_q, tq)
    block_k = _fit_block(block_k, tk)
    nq = tq // block_q
    lse = jnp.broadcast_to(lse[..., None], (bh, tq, _LSE_LANES))
    # Δ = rowsum(do · o): one cheap fused elementwise+reduce in XLA.
    delta2 = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                     axis=-1, keepdims=True)
    if dlse is not None:
        delta2 = delta2 - dlse.astype(jnp.float32)[..., None]
    delta = jnp.broadcast_to(delta2, (bh, tq, _LSE_LANES))
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, block_q, _LSE_LANES),
                            lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0))
    # dK/dV: K/V tile pinned (grid dim 1); the innermost dim sweeps the
    # group's query heads and, within each, its Q blocks
    q_spec_t = pl.BlockSpec((1, block_q, d),
                            lambda b, j, i: (b * g + i // nq, i % nq, 0))
    row_spec_t = pl.BlockSpec((1, block_q, _LSE_LANES),
                              lambda b, j, i: (b * g + i // nq, i % nq, 0))
    kv_spec_t = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, causal=causal, scale=scale,
                          nq=nq),
        name="flash_attention_bwd_dkdv",
        grid=(bh // g, tk // block_k, g * nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale),
        name="flash_attention_bwd_dq",
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def flash_eligible(tq: int, tk: Optional[int] = None, *,
                   min_t: int = 512) -> bool:
    """SHAPE eligibility for the flash kernel: TPU backend and
    128-lane-tileable sequence lengths. `min_t` is a PERF floor, not a
    capability one — the kernel runs from 128 up, but below ~512 it
    cannot amortize its block machinery, so the default floor suits
    structural users (ring attention's lse merge) that gate on this
    alone. The measured flash-vs-dense verdict, block sizes, and
    backward selection live in `kernel_defaults.attention_policy`,
    which consults capability (min_t=128) for the memory-necessity
    path."""
    tk = tq if tk is None else tk
    return (jax.default_backend() == "tpu" and tq % 128 == 0
            and tk % 128 == 0 and min(tq, tk) >= min_t)


def _fold3(x):
    """[B, T, H, D] → [BH, T, D] (identity for 3-D inputs)."""
    if x.ndim == 3:
        return x, None
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d), (b, t, h, d)


def _unfold3(x, shape):
    if shape is None:
        return x
    b, t, h, d = shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _resolve_backward(backward: Optional[str], tq: int, tk: int) -> str:
    """None -> the measured-winner default (kernel_defaults). Resolved
    ONCE, in the forward rule; the backward rule keys off whether lse
    was actually saved, so a mid-process env flip can never make the
    two rules disagree."""
    if backward is not None:
        return backward
    from deeplearning4j_tpu.ops.kernel_defaults import attention_backward

    return attention_backward(tq, tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False,
                    backward: Optional[str] = None):
    """Fused attention. q/k/v: [B, T, H, D] or [BH, T, D]; returns q's
    layout. k and v may have fewer heads (GQA, Hkv dividing H): query
    head h reads KV head h // (H // Hkv) in place, forward and backward.

    Residual memory of the forward is O(T) either way: the forward rule
    saves q/k/v/o and the per-row log-sum-exp. `backward` selects how
    dq/dk/dv are produced: "pallas" rematerializes score tiles blockwise
    in two Pallas kernels — the [T, T] matrix never exists; "dense" is
    the whole-matrix XLA recompute kept as the oracle/fallback path.
    None (default) resolves to the measured winner via
    `kernel_defaults.attention_backward` (env hatch:
    DL4J_TPU_ATTN_BACKWARD)."""
    s = scale if scale is not None else q.shape[-1] ** -0.5
    q3, shape = _fold3(q)
    k3, _ = _fold3(k)
    v3, _ = _fold3(v)
    o, _ = _run_flash(q3, k3, v3, causal=causal, scale=s, block_q=block_q,
                      block_k=block_k, interpret=interpret)
    return _unfold3(o, shape)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               backward):
    backward = _resolve_backward(backward, q.shape[1], k.shape[1])
    s = scale if scale is not None else q.shape[-1] ** -0.5
    q3, shape_q = _fold3(q)
    k3, shape_k = _fold3(k)   # cross-attention: Tk may differ from Tq
    v3, _ = _fold3(v)
    o3, lse = _run_flash(q3, k3, v3, causal=causal, scale=s,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret,
                         with_lse=(backward == "pallas"))
    if lse is not None:
        o3, lse = name_residuals(o3, lse)
    return _unfold3(o3, shape_q), (q3, k3, v3, o3, lse, shape_q, shape_k)


def _flash_bwd(causal, scale, block_q, block_k, interpret, backward, res,
               do):
    q3, k3, v3, o3, lse, shape_q, shape_k = res
    if backward is None:
        # Follow the forward rule's resolved choice (visible as whether
        # it saved the lse residual) rather than re-consulting the env —
        # re-resolving could pick "pallas" with lse=None after a
        # mid-process DL4J_TPU_ATTN_BACKWARD flip.
        backward = "pallas" if lse is not None else "dense"
    s = scale if scale is not None else q3.shape[-1] ** -0.5
    do3, _ = _fold3(do)
    if backward == "pallas":
        dq, dk, dv = _run_flash_bwd(q3, k3, v3, o3, lse, do3, causal=causal,
                                    scale=s, block_q=block_q,
                                    block_k=block_k, interpret=interpret)
    else:
        g = _group(q3, k3)
        _, vjp = jax.vjp(
            lambda qq, kk, vv: _dense_attention(
                qq, jnp.repeat(kk, g, axis=0), jnp.repeat(vv, g, axis=0),
                causal, s),
            q3, k3, v3)
        dq, dk, dv = vjp(do3)
    return (_unfold3(dq, shape_q), _unfold3(dk, shape_k),
            _unfold3(dv, shape_k))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: int = 512, block_k: int = 512,
                             interpret: bool = False):
    """Fused attention over 3-D [BH, T, D] inputs returning
    (o [BH, T, D], lse [BH, T]) — the building block for attention
    protocols that merge partial results across K/V shards (ring
    attention): two shards' outputs combine exactly via
    lse' = logaddexp(lse_a, lse_b), o' = o_a·e^{lse_a−lse'} +
    o_b·e^{lse_b−lse'}. Differentiable in both outputs (the lse
    cotangent rides the Pallas backward's Δ term)."""
    s = scale if scale is not None else q.shape[-1] ** -0.5
    o, lse = _run_flash(q, k, v, causal=causal, scale=s, block_q=block_q,
                        block_k=block_k, interpret=interpret, with_lse=True)
    return o, lse


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    s = scale if scale is not None else q.shape[-1] ** -0.5
    o, lse = _run_flash(q, k, v, causal=causal, scale=s, block_q=block_q,
                        block_k=block_k, interpret=interpret, with_lse=True)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    do, dlse = cts
    q, k, v, o, lse = res
    s = scale if scale is not None else q.shape[-1] ** -0.5
    dq, dk, dv = _run_flash_bwd(q, k, v, o, lse, do, causal=causal,
                                scale=s, block_q=block_q, block_k=block_k,
                                interpret=interpret, dlse=dlse)
    return dq, dk, dv


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)
