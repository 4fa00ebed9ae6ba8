"""One-pass fused optimizer update (Pallas TPU).

The XLA update path walks each leaf three times at the HBM level: the
moment updates and the parameter subtraction are separate read-modify-
write sweeps over tensors that share no compute (`optim/updaters.py`
builds `updates` then the step function applies `params - updates`). At
optimizer-bound scales (large embeddings, f32 moments against bf16
params) that is pure memory-bandwidth waste. These kernels do the whole
read-modify-write in ONE pass per leaf — param + both Adam moments (or
the Nesterov velocity) stream through VMEM once, with
`input_output_aliases` making the update genuinely in-place in HBM.

Layout: every leaf is flattened and tiled to [rows, 128] lanes (zero-
padded; pads compute to zero and are sliced away), so one kernel serves
every parameter shape. The traced scalar coefficient (lr · bias-
correction) rides in as a tiny lane-broadcast array, which keeps the
compiled program independent of step — the train step stays one program.

Dispatch discipline is `kernel_defaults.fused_update_policy`: the XLA
path remains the default until a measured winning row exists
(tools/kernel_bench.py --fused-update); `DL4J_TPU_FUSED_UPDATE=fused`
forces it. `optim/updaters.py::Updater.update_with_params` is the seam.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_LANES = 128
_SUBLANES = 8


def fused_update_available() -> bool:
    """Hardware capability only — whether the fused path WINS is the
    measured question `kernel_defaults.fused_update_policy` answers."""
    return jax.default_backend() == "tpu"


def _adam_kernel(c_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 *, b1: float, b2: float, eps: float):
    """p/m/v read-modify-write in one VMEM residency: m' and v' never
    round-trip to HBM between their update and their use."""
    lrbc = c_ref[0, 0]
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    po_ref[:] = (p - lrbc * m_new
                 / (jnp.sqrt(v_new) + eps)).astype(po_ref.dtype)
    mo_ref[:] = m_new.astype(mo_ref.dtype)
    vo_ref[:] = v_new.astype(vo_ref.dtype)


def _nesterov_kernel(c_ref, p_ref, g_ref, v_ref, po_ref, vo_ref, *,
                     mu: float):
    """ND4J Nesterovs semantics (optim/updaters.py): v' = mu·v - lr·g,
    p' = p + mu·v' - lr·g."""
    lr = c_ref[0, 0]
    g = g_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    v_new = mu * v - lr * g
    po_ref[:] = (p + mu * v_new - lr * g).astype(po_ref.dtype)
    vo_ref[:] = v_new.astype(vo_ref.dtype)


def _tile(x, rows: int):
    flat = x.reshape(-1)
    pad = rows * _LANES - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES)


def _untile(t, shape, size: int):
    return t.reshape(-1)[:size].reshape(shape)


def _geometry(n: int, block_rows: int):
    """Rows padded to the f32 sublane tile and to a whole number of
    blocks, so one BlockSpec covers every leaf size."""
    rows = max(1, -(-n // _LANES))
    rows = -(-rows // _SUBLANES) * _SUBLANES
    block = min(block_rows, rows)
    rows = -(-rows // block) * block
    return rows, block


def _run(kernel, name, coeff, arrays, out_dtypes, *, block_rows: int,
         interpret: bool):
    """Shared driver: tile leaves to [rows, 128], sweep row blocks, alias
    every state input onto its output slot (inputs after the coefficient
    and the gradient are in-place by construction)."""
    n = arrays[0].size
    shape = arrays[0].shape
    rows, block = _geometry(n, block_rows)
    c = jnp.broadcast_to(jnp.asarray(coeff, jnp.float32).reshape(1, 1),
                         (1, _LANES))
    tiles = [_tile(a, rows) for a in arrays]
    row_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    # inputs: [c, p, g, *state]; outputs: [p', *state'] — p and each
    # state tensor alias their output (g and c are read-only)
    aliases = {1: 0}
    for idx in range(3, len(arrays) + 1):
        aliases[idx] = idx - 2
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, 0))]
        + [row_spec] * len(tiles),
        out_specs=[row_spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), d)
                   for d in out_dtypes],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(c, *tiles)
    return tuple(_untile(o, shape, n) for o in out)


def adam_update(p, g, m, v, lrbc, *, beta1: float = 0.9,
                beta2: float = 0.999, eps: float = 1e-8,
                block_rows: int = 512, interpret: bool = False):
    """One-leaf fused Adam step. `lrbc` is the traced scalar
    lr · sqrt(1-β2^t)/(1-β1^t) (the caller owns the schedule and bias
    correction — they are per-step scalars, not per-element work).
    Returns (p', m', v') in the argument dtypes."""
    return _run(functools.partial(_adam_kernel, b1=beta1, b2=beta2,
                                  eps=eps),
                "fused_adam_update", lrbc, [p, g, m, v], [p.dtype, m.dtype, v.dtype],
                block_rows=block_rows, interpret=interpret)


def nesterov_update(p, g, vel, lr, *, momentum: float = 0.9,
                    block_rows: int = 512, interpret: bool = False):
    """One-leaf fused Nesterovs step; returns (p', v')."""
    return _run(functools.partial(_nesterov_kernel, mu=momentum),
                "fused_nesterov_update", lr, [p, g, vel], [p.dtype, vel.dtype],
                block_rows=block_rows, interpret=interpret)
