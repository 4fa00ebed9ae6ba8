"""Fused LSTM sequence kernel (Pallas TPU).

Reference parity: `nn/layers/recurrent/LSTMHelpers.java` — the hand-fused
forward (`:62`) and backward (`:291`) passes DL4J wrote because eager
op-at-a-time execution of the recurrence was too slow; SURVEY §7 names the
fused LSTM cell as the framework's Pallas obligation.

Design:
- The big input projection x@W+b for ALL timesteps happens OUTSIDE the
  kernel as one [B*T, F]@[F, 4H] MXU matmul (XLA's strength). The kernel
  fuses what XLA cannot: the sequential recurrence. It runs a grid over
  timesteps keeping h/c resident in VMEM scratch, so each step is one
  small [B,H]@[H,4H] MXU matmul plus VPU gate math — no HBM round-trip for
  the carry between steps, no per-step kernel launch.
- Backward is a hand-written reverse-time Pallas kernel wired up via
  `jax.custom_vjp`, accumulating dRW/dP in VMEM scratch across the grid
  (the moral equivalent of LSTMHelpers' backpropGradientHelper). dW/dx/db
  fall out of autodiff OUTSIDE the kernel since xw is the custom-vjp input.
- Gate order i,f,g,o; sigmoid gates, tanh cell — matching
  `layers/recurrent.py` (which matches GravesLSTMParamInitializer).
  Peepholes (GravesLSTM) are supported branch-free: P=zeros disables them.
- Per-timestep masking holds the carry where mask==0 (reference
  variable-length semantics).

On non-TPU backends the kernels run in interpret mode (tests) or layers
fall back to the lax.scan path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def fused_lstm_available(gate_activation: str, activation: str) -> bool:
    return gate_activation == "sigmoid" and activation == "tanh"


def _sigmoid(x):
    return jax.nn.sigmoid(x)


# --------------------------------------------------------------- forward
def _cell(xw_t, h_prev, c_prev, rw, p):
    """Shared gate math for both forward kernel variants. Everything
    past the loads is f32 whatever the storage dtype: Mosaic requires a
    32-bit matmul accumulator, and the recurrence compounds rounding
    over T steps. Callers cast back on store."""
    hsz = h_prev.shape[-1]
    f32 = jnp.float32
    gates = xw_t.astype(f32) + jnp.dot(h_prev, rw,
                                       preferred_element_type=f32)
    c_prev = c_prev.astype(f32)
    p = p.astype(f32)
    i = _sigmoid(gates[:, :hsz] + c_prev * p[0:1, :])
    f = _sigmoid(gates[:, hsz:2 * hsz] + c_prev * p[1:2, :])
    g = jnp.tanh(gates[:, 2 * hsz:3 * hsz])
    c_new = f * c_prev + i * g
    o = _sigmoid(gates[:, 3 * hsz:] + c_new * p[2:3, :])
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new, i, f, g, o


def _fwd_kernel(xw_ref, rw_ref, p_ref, h0_ref, c0_ref, m_ref,
                hs_ref, cs_ref, gates_ref, hT_ref, cT_ref,
                h_scr, c_scr):
    """Training forward: also emits the cs/gates residuals for backward."""
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h_prev, c_prev = h_scr[:], c_scr[:]
    h_new, c_new, i, f, g, o = _cell(
        xw_ref[0], h_prev, c_prev, rw_ref[:], p_ref[:])
    m = jnp.transpose(m_ref[pl.ds(t, 1), :])    # [B, 1] f32
    dt = h_scr.dtype
    h = (m * h_new + (1.0 - m) * h_prev).astype(dt)
    c = (m * c_new + (1.0 - m) * c_prev).astype(dt)

    h_scr[:] = h
    c_scr[:] = c
    hs_ref[0] = h
    cs_ref[0] = c
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=-1).astype(dt)

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _fwd_kernel_inference(xw_ref, rw_ref, p_ref, h0_ref, c0_ref, m_ref,
                          hs_ref, hT_ref, cT_ref, h_scr, c_scr):
    """Inference forward: writes only hs/h_T/c_T — ~5x less HBM output
    bandwidth than the training variant (no cs/gates residuals)."""
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h_prev, c_prev = h_scr[:], c_scr[:]
    h_new, c_new, _, _, _, _ = _cell(
        xw_ref[0], h_prev, c_prev, rw_ref[:], p_ref[:])
    m = jnp.transpose(m_ref[pl.ds(t, 1), :])
    dt = h_scr.dtype
    h = (m * h_new + (1.0 - m) * h_prev).astype(dt)
    c = (m * c_new + (1.0 - m) * c_prev).astype(dt)
    h_scr[:] = h
    c_scr[:] = c
    hs_ref[0] = h

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _run_forward(xw, rw, p, h0, c0, mask, *, interpret: bool,
                 with_residuals: bool = True):
    T, B, H4 = xw.shape
    H = H4 // 4
    dt = xw.dtype
    res_out = [
        jax.ShapeDtypeStruct((T, B, H), dt),    # cs
        jax.ShapeDtypeStruct((T, B, H4), dt),   # activated gates
    ] if with_residuals else []
    res_spec = [
        pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
        pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
    ] if with_residuals else []
    out = pl.pallas_call(
        _fwd_kernel if with_residuals else _fwd_kernel_inference,
        name="lstm_fwd" if with_residuals else "lstm_fwd_inference",
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((3, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((T, B), lambda t: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, B, H), lambda t: (t, 0, 0))] + res_spec
        + [
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_shape=tuple([jax.ShapeDtypeStruct((T, B, H), dt)] + res_out + [
            jax.ShapeDtypeStruct((B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        ]),
        scratch_shapes=[
            pltpu.VMEM((B, H), dt),
            pltpu.VMEM((B, H), dt),
        ],
        interpret=interpret,
    )(xw, rw, p, h0, c0, mask.astype(jnp.float32))
    if with_residuals:
        return out  # (hs, cs, gates, hT, cT)
    hs, hT, cT = out
    return hs, None, None, hT, cT


# -------------------------------------------------------------- backward
def _bwd_kernel(dhs_ref, gates_ref, cs_ref, csp_ref, hsp_ref, rw_ref, p_ref,
                m_ref, dhT_ref, dcT_ref, h0_ref, c0_ref,
                dxw_ref, dh0_ref, dc0_ref, drw_ref, dp_ref,
                dh_scr, dc_scr, drw_scr, dp_scr):
    idx = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(idx == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(dh_scr.dtype)
        dc_scr[:] = dcT_ref[:].astype(dc_scr.dtype)
        drw_scr[:] = jnp.zeros_like(drw_scr)
        dp_scr[:] = jnp.zeros_like(dp_scr)

    # f32 from the loads on (see _cell); only the MXU operands h_prev /
    # dgates / rw stay in the storage dtype
    f32 = jnp.float32
    gates = gates_ref[0].astype(f32)
    hsz = gates.shape[-1] // 4
    i = gates[:, :hsz]
    f = gates[:, hsz:2 * hsz]
    g = gates[:, 2 * hsz:3 * hsz]
    o = gates[:, 3 * hsz:]
    c_t = cs_ref[0].astype(f32)
    # csp/hsp alias cs/hs with a t-1 index map (clamped at 0); the true t=0
    # predecessors are the initial carry.
    t_is_0 = idx == T - 1
    c_prev = jnp.where(t_is_0, c0_ref[:], csp_ref[0]).astype(f32)
    h_prev = jnp.where(t_is_0, h0_ref[:], hsp_ref[0])
    p = p_ref[:].astype(f32)
    m = jnp.transpose(m_ref[pl.ds(T - 1 - idx, 1), :])   # [B, 1] f32

    dh_in = dhs_ref[0].astype(f32) + dh_scr[:]
    dh_t = m * dh_in            # grad into the freshly computed h at step t
    pass_h = (1.0 - m) * dh_in  # grad flowing straight to h_{t-1} (mask hold)

    tanh_c = jnp.tanh(c_t)
    do_pre = dh_t * tanh_c * o * (1.0 - o)
    dc_new = (m * dc_scr[:] + dh_t * o * (1.0 - tanh_c * tanh_c)
              + do_pre * p[2:3, :])
    di_pre = dc_new * g * i * (1.0 - i)
    df_pre = dc_new * c_prev * f * (1.0 - f)
    dg_pre = dc_new * i * (1.0 - g * g)
    dc_prev = (dc_new * f + (1.0 - m) * dc_scr[:]
               + di_pre * p[0:1, :] + df_pre * p[1:2, :])

    dgates = jnp.concatenate(
        [di_pre, df_pre, dg_pre, do_pre], axis=-1).astype(dxw_ref.dtype)
    dh_prev = jnp.dot(dgates, rw_ref[:].T,
                      preferred_element_type=f32) + pass_h

    dxw_ref[0] = dgates
    drw_scr[:] = drw_scr[:] + jnp.dot(
        h_prev.T, dgates, preferred_element_type=f32)
    dp_scr[0:1, :] = dp_scr[0:1, :] + jnp.sum(di_pre * c_prev, axis=0,
                                               keepdims=True)
    dp_scr[1:2, :] = dp_scr[1:2, :] + jnp.sum(df_pre * c_prev, axis=0,
                                              keepdims=True)
    dp_scr[2:3, :] = dp_scr[2:3, :] + jnp.sum(do_pre * c_t, axis=0,
                                              keepdims=True)
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(idx == T - 1)
    def _():
        dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)
        drw_ref[:] = drw_scr[:].astype(drw_ref.dtype)
        dp_ref[:] = dp_scr[:].astype(dp_ref.dtype)


def _run_backward(res, dhs, dhT, dcT, *, interpret: bool):
    rw, p, mask, hs, cs, gates, h0, c0 = res
    T, B, H = hs.shape
    H4 = 4 * H
    dt = hs.dtype
    rev = lambda t: (T - 1 - t, 0, 0)
    # Previous-step blocks read from hs/cs themselves (no shifted copies):
    # grid step i handles t = T-1-i and wants index t-1, clamped at 0 (the
    # clamped read is discarded in-kernel in favour of h0/c0).
    rev_prev = lambda t: (jnp.maximum(T - 2 - t, 0), 0, 0)
    out_shape = (
        jax.ShapeDtypeStruct((T, B, H4), dt),   # dxw
        jax.ShapeDtypeStruct((B, H), dt),       # dh0
        jax.ShapeDtypeStruct((B, H), dt),       # dc0
        jax.ShapeDtypeStruct((H, H4), dt),      # dRW
        jax.ShapeDtypeStruct((3, H), dt),       # dP
    )
    return pl.pallas_call(
        _bwd_kernel,
        name="lstm_bwd",
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H), rev),       # dhs
            pl.BlockSpec((1, B, H4), rev),      # gates
            pl.BlockSpec((1, B, H), rev),       # cs
            pl.BlockSpec((1, B, H), rev_prev),  # cs at t-1
            pl.BlockSpec((1, B, H), rev_prev),  # hs at t-1
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((3, H), lambda t: (0, 0)),
            pl.BlockSpec((T, B), lambda t: (0, 0)),   # mask (full)
            pl.BlockSpec((B, H), lambda t: (0, 0)),   # dh_T
            pl.BlockSpec((B, H), lambda t: (0, 0)),   # dc_T
            pl.BlockSpec((B, H), lambda t: (0, 0)),   # h0
            pl.BlockSpec((B, H), lambda t: (0, 0)),   # c0
        ],
        out_specs=[
            pl.BlockSpec((1, B, H4), rev),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((3, H), lambda t: (0, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[               # f32: accumulated over T steps
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, H4), jnp.float32),
            pltpu.VMEM((3, H), jnp.float32),
        ],
        interpret=interpret,
    )(dhs, gates, cs, cs, hs, rw, p, mask.astype(jnp.float32), dhT, dcT,
      h0, c0)


# ------------------------------------------------------------ public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_lstm(xw, rw, p, h0, c0, mask, interpret=False):
    """Fused LSTM over a whole sequence.

    xw:   [T, B, 4H] precomputed x@W + b (gate order i,f,g,o)
    rw:   [H, 4H] recurrent weights; p: [3, H] peepholes (zeros = none)
    h0/c0:[B, H] initial carry; mask: [T, B] 1=valid (carry held at 0)
    Returns (hs [T, B, H], h_T, c_T).
    """
    hs, _, _, hT, cT = _run_forward(
        xw, rw, p, h0, c0, mask, interpret=interpret, with_residuals=False)
    return hs, hT, cT


def _fused_fwd(xw, rw, p, h0, c0, mask, interpret):
    hs, cs, gates, hT, cT = _run_forward(
        xw, rw, p, h0, c0, mask, interpret=interpret)
    return (hs, hT, cT), (rw, p, mask, hs, cs, gates, h0, c0)


def _fused_bwd(interpret, res, cts):
    dhs, dhT, dcT = cts
    rw, p, mask, hs, cs, gates, h0, c0 = res
    dxw, dh0, dc0, drw, dp = _run_backward(
        res, dhs, dhT, dcT, interpret=interpret)
    return dxw, drw, dp, dh0, dc0, None


fused_lstm.defvjp(_fused_fwd, _fused_bwd)
