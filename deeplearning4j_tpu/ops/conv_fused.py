"""Fused conv + batch-norm statistics (Pallas TPU) — the conv-epilogue
fusion targeting the HBM-bound BN sweeps of ResNet-style bottlenecks.

STATUS: FROZEN/EXPERIMENTAL (2026-07-31) — measured 2x SLOWER than XLA
on the flagship (PERF_NOTES "DECISION"); kept opt-in for numerics and
as the cuDNN-helper-seam analogue. No new feature work; prefer deletion
over rework if a layer change would require touching the kernels.
Two kernel shapes are fused: 1x1 any stride (`conv1x1_bn_act`, a matmul)
and 3x3 stride-1 SAME (`conv3x3_bn_act`, nine shifted matmuls over a
VMEM halo) — together they cover every conv+BN pair in a ResNet-50
bottleneck; only the 7x7 stem stays on plain XLA.

Reference parity: the cuDNN helper seam
(`nn/layers/convolution/ConvolutionLayer.java:67-77` +
`CudnnBatchNormalizationHelper.java`) — DL4J points conv/BN at hand-fused
vendor kernels; here the vendor kernel is written in Pallas. PERF_NOTES
sink #2: at b128 every unfused BN costs a full read+write sweep of the
activation (819 GB/s HBM on v5e), and training-mode BN needs the batch
stats BEFORE it can normalize, forcing XLA into
    conv -> write y -> read y (stats reduce) -> read y -> write out
(= 2 reads + 2 writes of the activation per conv+BN pair). The kernel
below computes the matmul AND the per-channel sum / sum-of-squares in one
pass while the output tile is still in VMEM:
    pass 1 (Pallas) -> write y + tiny partials ; pass 2 (XLA, fused
    normalize+activation) -> read y, write out
(= 1 read + 2 writes) — the stats sweep rides the matmul for free, ~25%
of the epilogue traffic saved per conv+BN. A 1x1 conv over NHWC IS a
matmul [B*H*W, C_in] @ [C_in, C_out] — exactly what the MXU wants; the
ResNet-50 bottleneck 1x1s (reduce/expand/projection) carry ~2/3 of its
conv FLOPs.

Backward is `jax.custom_vjp` with the standard BN-through-matmul formulas
in plain XLA (two matmuls + fused elementwise; Pallas buys nothing there
because every term is already a single fused sweep).

On non-TPU backends the kernel runs in interpret mode (tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _divisor_block(n: int, candidates) -> Optional[int]:
    for c in candidates:
        if n % c == 0:
            return c
    return None


def pick_blocks(m: int, k: int, n: int
                ) -> Optional[Tuple[int, int, int]]:
    """Block sizes (bm, bk, bn) that exactly tile [m, k] @ [k, n], or None
    if the shape does not tile cleanly (caller falls back to XLA)."""
    bm = _divisor_block(m, (512, 256, 128, 64, 32, 16, 8))
    bk = _divisor_block(k, (512, 256, 128, 64, 32, 16, 8, 4, 2, 1))
    bn = _divisor_block(n, (256, 128, 64, 32, 16, 8))
    if bm is None or bk is None or bn is None:
        return None
    return bm, bk, bn


def _mm_stats_kernel(x_ref, w_ref, y_ref, s_ref, q_ref, acc,
                     acc_dtype=jnp.float32):
    """One (i, j) output tile: accumulate over k in VMEM, then emit the
    y tile plus its per-channel partial sum / sum-of-squares."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jnp.dot(x_ref[:], w_ref[:],
                      preferred_element_type=acc_dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        t = acc[:]
        y_ref[:] = t.astype(y_ref.dtype)
        s_ref[:] = t.sum(axis=0, keepdims=True)[None]
        q_ref[:] = (t * t).sum(axis=0, keepdims=True)[None]


def _acc_dtype(dtype):
    """f32 accumulation normally; f64 when the inputs are f64 (the
    gradient-check path runs the whole net in double precision)."""
    return jnp.promote_types(dtype, jnp.float32)


def matmul_with_channel_stats(x2d, w, *, interpret: bool = False):
    """y = x2d @ w plus per-output-channel (sum, sum_of_squares) of y,
    computed inside the matmul kernel. Returns (y [M,N] in x2d.dtype,
    sums [N], sumsqs [N] in the accumulation dtype — f32, or f64 under
    double precision). Falls back to plain XLA when the shape does not
    tile."""
    m, k = x2d.shape
    k2, n = w.shape
    assert k == k2, (x2d.shape, w.shape)
    acc = _acc_dtype(x2d.dtype)
    blocks = pick_blocks(m, k, n)
    if blocks is None:
        y = jnp.dot(x2d, w, preferred_element_type=acc)
        return (y.astype(x2d.dtype), jnp.sum(y, axis=0),
                jnp.sum(y * y, axis=0))
    bm, bk, bn = blocks
    nm, nn, nk = m // bm, n // bn, k // bk
    y, ps, pq = pl.pallas_call(
        functools.partial(_mm_stats_kernel, acc_dtype=acc),
        name="matmul_channel_stats",
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            # per-(i, j) partials, reduced over i below — each grid step
            # owns its own block, no cross-step output revisiting
            pl.BlockSpec((1, 1, bn), lambda i, j, kk: (i, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x2d.dtype),
            jax.ShapeDtypeStruct((nm, 1, n), acc),
            jax.ShapeDtypeStruct((nm, 1, n), acc),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        interpret=interpret,
    )(x2d, w)
    return y, ps.sum(axis=(0, 1)), pq.sum(axis=(0, 1))


# ----------------------------------------------------- 3x3 conv variant
def _pick_conv3_blocks(b: int, h: int, w: int, cin: int, cout: int,
                       itemsize: int) -> Optional[Tuple[int, int]]:
    """(nb, bn) batch-group / cout-tile sizes for the 3x3 kernel, or None
    to fall back to XLA. nb groups images so the matmul M-dim (nb*h*w)
    feeds the MXU properly even at late-stage 7x7 maps; the VMEM guard
    keeps xpad + weight + accumulator tiles comfortably on-core."""
    nb = None
    for cand in (1, 2, 4, 8, 16, 32):
        if b % cand == 0 and cand * h * w >= 256:
            nb = cand
            break
    if nb is None:
        nb = b
    bn = _divisor_block(cout, (256, 128, 64, 32, 16, 8))
    if bn is None:
        return None
    xblk = nb * h * w * cin * itemsize
    wblk = 9 * cin * bn * itemsize
    yblk = nb * h * w * bn * itemsize
    xpad = nb * (h + 2) * (w + 2) * cin * itemsize
    acc = nb * h * w * bn * jnp.dtype(jnp.float32).itemsize
    # in/out blocks are double-buffered by the pipeline; scratch and the
    # accumulator temp are not. Budget well under the ~16MB/core VMEM.
    if 2 * (xblk + wblk + yblk) + xpad + acc > 10 * 1024 * 1024:
        return None
    return nb, bn


def _conv3_stats_kernel(x_ref, w_ref, y_ref, s_ref, q_ref, xpad,
                        acc_dtype=jnp.float32):
    """One (batch-group i, cout-tile j) step: zero-padded halo copy of the
    input group into VMEM, nine shifted matmuls (the 3x3 taps), then the
    output tile plus its per-channel partial sum / sum-of-squares — the
    BN statistics ride the conv exactly as in the 1x1 kernel."""
    nb, h, w, cin = x_ref.shape
    bn = w_ref.shape[3]

    # j (cout tiles) is the innermost grid axis and the x block depends
    # only on i, so the halo copy persists in scratch across the j sweep
    @pl.when(pl.program_id(1) == 0)
    def _():
        xpad[:] = jnp.zeros(xpad.shape, xpad.dtype)
        xpad[:, 1:h + 1, 1:w + 1, :] = x_ref[:]

    m = nb * h * w
    tot = jnp.zeros((m, bn), acc_dtype)
    for dh in range(3):
        for dw in range(3):
            xs = xpad[:, dh:dh + h, dw:dw + w, :].reshape(m, cin)
            tot += jnp.dot(xs, w_ref[dh, dw],
                           preferred_element_type=acc_dtype)
    y_ref[:] = tot.reshape(nb, h, w, bn).astype(y_ref.dtype)
    s_ref[:] = tot.sum(axis=0, keepdims=True)[None]
    q_ref[:] = (tot * tot).sum(axis=0, keepdims=True)[None]


def _conv3_xla(x, w, acc_dtype):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=acc_dtype)


def conv3x3_with_channel_stats(x, w, *, interpret: bool = False):
    """y = conv2d(x, w, stride 1, SAME) plus per-output-channel
    (sum, sum_of_squares) of y computed inside the conv kernel.
    x: [B, H, W, C_in] NHWC; w: [3, 3, C_in, C_out] HWIO. Returns
    (y in x.dtype, sums [C_out], sumsqs [C_out] in the accumulation
    dtype). Falls back to XLA conv + XLA reductions when the shape does
    not tile or would overflow VMEM."""
    b, h, wd, cin = x.shape
    assert w.shape[:2] == (3, 3) and w.shape[2] == cin, (x.shape, w.shape)
    cout = w.shape[3]
    acc = _acc_dtype(x.dtype)
    blocks = _pick_conv3_blocks(b, h, wd, cin, cout, x.dtype.itemsize)
    if blocks is None:
        y = _conv3_xla(x, w, acc)
        return (y.astype(x.dtype), jnp.sum(y, axis=(0, 1, 2)),
                jnp.sum(y * y, axis=(0, 1, 2)))
    nb, bn = blocks
    nm, nn = b // nb, cout // bn
    y, ps, pq = pl.pallas_call(
        functools.partial(_conv3_stats_kernel, acc_dtype=acc),
        name="conv3x3_channel_stats",
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((nb, h, wd, cin), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, bn), lambda i, j: (0, 0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((nb, h, wd, bn), lambda i, j: (i, 0, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, wd, cout), x.dtype),
            jax.ShapeDtypeStruct((nm, 1, cout), acc),
            jax.ShapeDtypeStruct((nm, 1, cout), acc),
        ],
        scratch_shapes=[pltpu.VMEM((nb, h + 2, wd + 2, cin), x.dtype)],
        interpret=interpret,
    )(x, w)
    return y, ps.sum(axis=(0, 1)), pq.sum(axis=(0, 1))


# --------------------------------------------------- shared BN epilogue
def _bn_train_epilogue(y, s, q, mval, gamma, beta, eps, relu, acc):
    """Normalize a linear-op output y from its in-kernel (sum, sumsq)
    partials: returns (out in acc dtype, batch mean, biased clamped
    batch var). Shared by the 1x1 (reduce over rows) and 3x3 (reduce
    over B,H,W) paths — the per-channel stats broadcast identically."""
    mean = s / mval
    var = jnp.maximum(q / mval - mean * mean, 0.0)  # biased, clamped
    inv = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(acc) * inv
    shift = beta.astype(acc) - mean * scale
    pre = y.astype(acc) * scale + shift
    out = jnp.maximum(pre, 0.0) if relu else pre
    return out, mean, var


def _bn_eval_fold(y, gamma, beta, mean, var, eps, relu, acc, out_dtype):
    """Eval-mode fold: running stats become one affine(+relu) epilogue
    on the linear-op output (XLA fuses this into the producing kernel).
    Shared by the 1x1 and 3x3 eval paths."""
    inv = jax.lax.rsqrt(var.astype(acc) + eps)
    scale = gamma.astype(acc) * inv
    shift = beta.astype(acc) - mean.astype(acc) * scale
    pre = y * scale + shift
    if relu:
        pre = jnp.maximum(pre, 0.0)
    return pre.astype(out_dtype)


def _bn_backward(dout, y, gamma, beta, mean, var, eps, relu, axes, mval,
                 ct):
    """Training-mode BN backward through the epilogue: returns (dy wrt
    the linear-op output, dgamma, dbeta) in the accumulation dtype; the
    caller finishes with the linear op's own transpose (matmul or conv
    VJP). `axes` are the reduction axes of the batch statistics, whose
    mean/var depend on every element of the reduction group."""
    inv = jax.lax.rsqrt(var + eps)
    xhat = (y.astype(ct) - mean) * inv
    g = dout.astype(ct)
    if relu:
        g = g * ((gamma.astype(ct) * xhat + beta.astype(ct)) > 0)
    dbeta = g.sum(axis=axes)
    dgamma = (g * xhat).sum(axis=axes)
    dxhat = g * gamma.astype(ct)
    dy = inv * (dxhat - dxhat.sum(axis=axes) / mval
                - xhat * (dxhat * xhat).sum(axis=axes) / mval)
    return dy, dgamma, dbeta


# ------------------------------------------------------------- train path
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv1x1_bn_train(x2d, w, gamma, beta, eps, relu, interpret):
    out, _, mean, var = _train_fwd_impl(x2d, w, gamma, beta, eps, relu,
                                        interpret)
    return out, mean, var


def _train_fwd_impl(x2d, w, gamma, beta, eps, relu, interpret):
    acc = _acc_dtype(x2d.dtype)
    y, s, q = matmul_with_channel_stats(x2d, w, interpret=interpret)
    out, mean, var = _bn_train_epilogue(y, s, q, x2d.shape[0], gamma,
                                        beta, eps, relu, acc)
    return out.astype(x2d.dtype), y, mean, var


def _train_vjp_fwd(x2d, w, gamma, beta, eps, relu, interpret):
    out, y, mean, var = _train_fwd_impl(x2d, w, gamma, beta, eps, relu,
                                        interpret)
    return (out, mean, var), (x2d, w, gamma, beta, y, mean, var)


def _train_vjp_bwd(eps, relu, interpret, res, cts):
    # cotangents for (out, mean, var); the layer stop-gradients the
    # running-stat outputs, so d_mean/d_var are structurally zero here
    dout = cts[0]
    x2d, w, gamma, beta, y, mean, var = res
    ct = _acc_dtype(x2d.dtype)
    dy, dgamma, dbeta = _bn_backward(dout, y, gamma, beta, mean, var,
                                     eps, relu, (0,), x2d.shape[0], ct)
    dx = jnp.dot(dy, w.astype(ct).T,
                 preferred_element_type=ct).astype(x2d.dtype)
    dw = jnp.dot(x2d.astype(ct).T, dy,
                 preferred_element_type=ct).astype(w.dtype)
    return dx, dw, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype)


_conv1x1_bn_train.defvjp(_train_vjp_fwd, _train_vjp_bwd)


# ------------------------------------------------------------ public API
def conv1x1_bn_act(x, w, gamma, beta, *, mean=None, var=None,
                   stride=(1, 1), eps: float = 1e-5, relu: bool = True,
                   train: bool = False, interpret: bool = False):
    """Fused 1x1-conv + batch norm + (optional) ReLU over NHWC input.

    x: [B, H, W, C_in]; w: [C_in, C_out]; gamma/beta: [C_out].
    train=True  -> (out, batch_mean, batch_var) — stats computed inside
                   the matmul kernel; running-stat update is the caller's
                   (they carry no gradient).
    train=False -> out, normalized with the provided running mean/var as
                   one folded scale/shift epilogue (plain XLA: a matmul
                   with a fused affine+relu consumer is already a single
                   kernel — Pallas buys nothing in eval mode).
    """
    sh, sw = stride
    if (sh, sw) != (1, 1):
        x = x[:, ::sh, ::sw, :]
    b, h, wd, c = x.shape
    n = w.shape[1]
    x2d = x.reshape(b * h * wd, c)
    if train:
        out2d, bmean, bvar = _conv1x1_bn_train(
            x2d, w, gamma, beta, eps, relu, interpret)
        return (out2d.reshape(b, h, wd, n),
                jax.lax.stop_gradient(bmean),
                jax.lax.stop_gradient(bvar))
    acc = _acc_dtype(x.dtype)
    pre = jnp.dot(x2d, w, preferred_element_type=acc)
    return _bn_eval_fold(pre, gamma, beta, mean, var, eps, relu, acc,
                         x.dtype).reshape(b, h, wd, n)


# --------------------------------------------- 3x3 train path + public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv3x3_bn_train(x, w, gamma, beta, eps, relu, interpret):
    out, _, mean, var = _conv3_train_fwd_impl(x, w, gamma, beta, eps,
                                              relu, interpret)
    return out, mean, var


def _conv3_train_fwd_impl(x, w, gamma, beta, eps, relu, interpret):
    b, h, wd, _ = x.shape
    acc = _acc_dtype(x.dtype)
    y, s, q = conv3x3_with_channel_stats(x, w, interpret=interpret)
    out, mean, var = _bn_train_epilogue(y, s, q, b * h * wd, gamma,
                                        beta, eps, relu, acc)
    return out.astype(x.dtype), y, mean, var


def _conv3_vjp_fwd(x, w, gamma, beta, eps, relu, interpret):
    out, y, mean, var = _conv3_train_fwd_impl(x, w, gamma, beta, eps,
                                              relu, interpret)
    return (out, mean, var), (x, w, gamma, beta, y, mean, var)


def _conv3_vjp_bwd(eps, relu, interpret, res, cts):
    # shared BN backward, then the conv's own VJP instead of the matmul
    # transposes (XLA derives the flipped-kernel conv for dx and the
    # patch correlation for dw)
    dout = cts[0]
    x, w, gamma, beta, y, mean, var = res
    b, h, wd, _ = x.shape
    ct = _acc_dtype(x.dtype)
    dy, dgamma, dbeta = _bn_backward(dout, y, gamma, beta, mean, var,
                                     eps, relu, (0, 1, 2), b * h * wd, ct)
    _, conv_vjp = jax.vjp(
        lambda xx, ww: _conv3_xla(xx, ww, ct),
        x.astype(ct), w.astype(ct))
    dx, dw = conv_vjp(dy)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype))


_conv3x3_bn_train.defvjp(_conv3_vjp_fwd, _conv3_vjp_bwd)


def conv3x3_bn_act(x, w, gamma, beta, *, mean=None, var=None,
                   eps: float = 1e-5, relu: bool = True,
                   train: bool = False, interpret: bool = False):
    """Fused 3x3 stride-1 SAME conv + batch norm + (optional) ReLU over
    NHWC input — the 3x3 sibling of `conv1x1_bn_act`, covering the
    remaining third of ResNet-50's conv FLOPs (the bottleneck middle
    convs are all 3x3/1/SAME). Same contract: train=True returns
    (out, batch_mean, batch_var) with the statistics accumulated inside
    the conv kernel; train=False folds the running stats into one XLA
    conv+affine(+relu) epilogue."""
    if train:
        out, bmean, bvar = _conv3x3_bn_train(x, w, gamma, beta, eps,
                                             relu, interpret)
        return (out, jax.lax.stop_gradient(bmean),
                jax.lax.stop_gradient(bvar))
    acc = _acc_dtype(x.dtype)
    return _bn_eval_fold(_conv3_xla(x, w, acc), gamma, beta, mean, var,
                         eps, relu, acc, x.dtype)
