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
comparison, and their fetches by naming the last live block again.

The online-softmax step is written once, here, for the three forward
kernels (`_softmax_update`: this file's, `ops/banded_attention.py`'s and
`ops/sparse_attention.py`'s). m and l are `[rows, 128]` float32, every lane
of a row holding the row's value as the log-sum-exp output does, so that
`s - m` and `acc * alpha` meet whole registers: kept `[rows, 1]` they took
a register for eight values, were broadcast across lanes at every use, and
cost the forward more than its two matrix products did. l's lanes gather
the 128-lane groups of the weights and are summed across lanes once, at
the sweep's end. Each forward picks its own tile from the policy's blocks
(`_pick_tile`: as many rows as a 3 MiB score tile allows over 512 keys,
where a band's or a causal edge does not make that dearer). On a v5e, bf16,
one call alone (PR 36, PERF.md section 6): this kernel over 48 query heads
of 128 on 8 KV heads at 8,192 tokens 13.7 to 6.7 ms (tile 1,024 x 512),
the banded one at a window of 4,096 14.7 to 5.3 (1,536 x 512), the sparse
one at 32 heads and 16,384 tokens 50.0 to 26.7 (1,024 x 512).

Backward (FlashAttention-2 style, `backward="pallas"`): the
forward rule additionally saves the per-row log-sum-exp L = m + log(l)
(O(T) residual memory — q/k/v/o/L, never the [T, T] scores). Two Pallas
kernels then rematerialize score tiles blockwise: a dK/dV kernel with the
K/V tile pinned in VMEM scratch while sweeping Q blocks, and a dQ kernel
with the Q tile pinned while sweeping K blocks, using the softmax-vjp
identity ds = p * (dp - Δ) with Δ = rowsum(do · o) precomputed by XLA.
`backward="dense"` keeps the previous whole-[T, T] XLA recompute as a
fallback/oracle path. The default (`backward=None`) resolves through
`ops/kernel_defaults.attention_backward` — see that module for the
dispatch policy and its env escape hatches.

The backward's tile is written once, here, for the six backward kernels
of the three families (`_bwd_scores`, `_dq_step`, `_dkdv_step`). dQ
computes it query-major, [rows, keys], and reads the log-sum-exp and Δ
as the [rows, 128] operands they are; dK/dV computes it key-major,
[keys, rows] from k·qᵀ and v·doᵀ, so that p and ds are born in the
orientation dV += p·do and dK += ds·q contract over and nothing is
transposed a tile, with the two statistics as [1, rows] rows. A tile on
which every pair is visible builds no mask (`_on_tiles`), a dead step of
the causal grid names the nearest live tile again and fetches nothing,
and each kernel picks its own tile by the forward's rule with its own
limits (`_pick_tile`, `_TILE_COST`). On a v5e, bf16, one call alone at
the shapes above (PR 39, PERF.md section 6; host clock, delta and the
statistics' layouts included): dQ / dK/dV of this family 13.2 / 17.4 to
9.6 / 10.5 ms (tile 1,024 x 1,024 where the policy's 512 x 512 was the
tile), the banded pair 9.9 / 16.5 to 7.7 / 8.9 (1,536 x 512 from 768 x
256), the sparse pair 41.4 / 55.1 to 31.1 / 35.5 (1,024 x 512 from
256 x 512).

Under gradient checkpointing: the forward rules of this kernel and of
`ops/banded_attention.py`'s name the kernel's own output and the one-lane
L (`name_residuals`: the checkpoint names `attention_out` and
`attention_lse`, `RESIDUAL_NAMES`), and a checkpointed layer's policy
keeps those (`models/multilayer._checkpointed`, which reads `KEPT_NAMES`:
the pair, and what the blocks name themselves). They are what the
two backward kernels read of the forward and what only a second run of the
forward kernel could remake: one hidden-sized tensor and T floats a head.
`flash_attention_with_lse` names nothing: under a ring that would keep
every ring step's pair.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional

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
# and of what a block names (`name_block_residual`): a sub-layer's output
# that a norm reads, the stream between a pre-norm block's halves, a
# block selection, and an expert layer's schedule (the router's choice
# [N, k], the pairs' order, places and sizes by expert and their weights
# in row order: `parallel/moe.route`, `held_experts`). Each is made by a
# sub-layer's LAST product (or by a choice with no backward) and read
# again by the recomputation only as a value: kept, what made it is dead
# there
BLOCK_RESIDUAL_NAMES = ("sublayer_out", "residual_stream",
                        "block_selection", "expert_schedule")
# every name a checkpointed layer's policy keeps
# (`models/multilayer._checkpointed`): the one list
KEPT_NAMES = RESIDUAL_NAMES + BLOCK_RESIDUAL_NAMES


class _Named(threading.local):
    calls = 0
    blocks = 0


_named = _Named()


def residuals_named() -> int:
    """How many forward kernel calls this thread has traced so far with
    `name_residuals`; a checkpointed layer reads it before and after its
    own trace."""
    return _named.calls


def block_residuals_named() -> int:
    """How many values this thread has traced so far with
    `name_block_residual`; read as `residuals_named` is."""
    return _named.blocks


def name_block_residual(x, name: str):
    """`x` (an array, or a tuple of them: each counts) under `name`, one
    of `BLOCK_RESIDUAL_NAMES`, where a block makes a value that its
    recomputation would make again only to read it: bit for bit the same
    value, held from the forward pass to the backward instead (one
    hidden-sized tensor, a selection's bools, a schedule's integers).
    A no-op without a policy that keeps the names; what follows has to
    be made from the named `x`."""
    if name not in BLOCK_RESIDUAL_NAMES:
        raise ValueError(f"{name!r} is not in {BLOCK_RESIDUAL_NAMES}")
    _named.blocks += len(jax.tree_util.tree_leaves(x))
    return checkpoint_name(x, name)


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


def _lanes(x, n: int):
    """A `[rows, 128]` statistic, every lane of a row holding the row's
    value, as `[rows, n]`."""
    if n == _LSE_LANES:
        return x
    if n < _LSE_LANES:
        return x[:, :n]
    whole, rest = divmod(n, _LSE_LANES)
    return jnp.concatenate([x] * whole + ([x[:, :rest]] if rest else []),
                           axis=1)


def _lane_sums(keys: int) -> bool:
    """Whether a sweep of `keys` keys leaves the normalizer's lanes as
    partial sums (whole 128-lane groups) or each as the row's sum."""
    return keys % _LSE_LANES == 0


def _softmax_scratch(rows: int, d: int) -> list:
    """The online softmax's state for a tile of `rows` rows: accumulator,
    running maximum, normalizer (`_softmax_init` and on)."""
    return [pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, _LSE_LANES), jnp.float32),
            pltpu.VMEM((rows, _LSE_LANES), jnp.float32)]


def _softmax_init(acc_scr, m_scr, l_scr):
    acc_scr[:] = jnp.zeros_like(acc_scr)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)


def _softmax_update(s, mask, v, acc_scr, m_scr, l_scr, prec):
    """One step of the online softmax, the one all three forward kernels
    make (`_flash_kernel`, `banded_attention._banded_kernel`,
    `sparse_attention._fwd_kernel`): the scaled float32 scores `s`
    [rows, keys] of one sweep of keys and their values `v` [keys, D] go
    into the running maximum `m_scr`, normalizer `l_scr` (both
    [rows, 128] float32) and accumulator `acc_scr` [rows, D].

    `mask` [rows, keys] says which pairs the softmax has; None means all
    of them, or that the caller has put -1e30 on the others and no row is
    masked whole before its first live key. With a mask the weights are
    zeroed outright and not only biased: a row none of whose keys has
    come yet has m == -1e30, where exp(s - m) is exp(0) = 1 for every
    masked entry (a banded grid's first block can be dead for a live row,
    and a sparse row may list nothing in a visited tile).

    The statistics are a lane wide, every lane of a row holding the row's
    maximum, so `s - m` and `acc * alpha` meet whole registers and nothing
    is broadcast across lanes at a use. Where the sweep is whole 128-lane
    groups of keys the normalizer's lanes hold partial sums: the groups of
    `p` are added lane for lane and `_softmax_finish` makes the one sum
    across lanes, which leaves the row maximum as the step's one
    cross-lane reduction. Any other width (interpret mode's odd blocks)
    sums across lanes here and keeps the whole sum in every lane."""
    keys, d = v.shape
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, keys))
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    if _lane_sums(keys):
        l_new = sum(p[:, c:c + _LSE_LANES]
                    for c in range(0, keys, _LSE_LANES))
    else:
        l_new = jnp.sum(p, axis=-1, keepdims=True)
    m_scr[:] = m_new
    l_scr[:] = l_scr[:] * alpha + l_new
    acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32,
        precision=prec)


def _softmax_finish(acc_scr, m_scr, l_scr, keys: int):
    """The sweep's end: (o [rows, D] float32, lse [rows, 128] float32 with
    the row's log-sum-exp in every lane, the narrowest layout Mosaic
    takes for a row statistic: cf. MIN_BLOCK_SIZE in jax's in-tree TPU
    flash kernel, which keeps its running statistics so too). `keys` is
    the width of the sweeps `_softmax_update` saw."""
    l = l_scr[:]
    l = (jnp.sum(l, axis=-1, keepdims=True) if _lane_sums(keys)
         else l[:, :1])
    l = jnp.maximum(l, 1e-30)
    return acc_scr[:] / l, m_scr[:] + jnp.log(l)


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
        _softmax_init(acc_scr, m_scr, l_scr)

    # Causal: K blocks strictly above this Q block's last row are dead:
    # their FLOPs are skipped, and the index map fetches nothing new.
    relevant = (kb * block_k <= (qb + 1) * bq - 1) if causal else (kb >= 0)

    @pl.when(relevant)
    def _():
        k = k_ref[0]                              # [Bk, D]
        prec = _prec(q.dtype)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        if causal:
            # key 0 is live for every row, so the bias alone will do
            s = jnp.where(_causal_mask(qb, kb, bq, block_k, False), s,
                          _NEG_INF)
        _softmax_update(s, None, v_ref[0], acc_scr, m_scr, l_scr, prec)

    @pl.when(kb == nk - 1)
    def _():
        o, lse = _softmax_finish(acc_scr, m_scr, l_scr, block_k)
        o_ref[0] = o.astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = lse


def _fit_block(block: int, t: int) -> int:
    """Largest block <= requested that divides t (t must be a multiple of
    the 128-lane minimum). Block size is the decisive perf lever on TPU:
    the policies in ops/kernel_defaults.py say where the blocks start,
    and `_pick_tile` picks each kernel's own tile from them."""
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


class _TileCost(NamedTuple):
    """What `_pick_tile` holds a kernel's tile to, and what the tile costs
    beside its pairs, in pairs."""

    rows: int          # rows of a tile at most, a folded group's too
    keys: int          # keys of a tile at most
    tile_bytes: int    # a float32 [rows, keys] tile at most
    update_keys: int   # a row's bookkeeping a tile, as so many more keys
    step_pairs: int    # a grid step, live or dead, as so many pairs


# The forward (the kernels alone on a v5e over tiles of 768 and 1,536 rows
# by 256 and 512 keys, PR 36: PERF.md section 6): 512 keys one update of
# the statistics covers at most, a float32 score tile of 1,536 x 512; an
# update of a row's statistics costs as much as 80 more keys of the row, a
# grid step as 170,000 pairs. The backward kernels (the six alone over
# nine tiles each, PR 39: PERF.md section 6) update nothing a row, so a
# sweep of 1,024 keys costs them nothing beside its pairs; a grid step
# costs dQ as 65,000 to 69,000 pairs and dK/dV as 45,000 to 51,000, and a
# float32 tile of 1,024 x 1,024 is the largest that paid.
_TILE_COST = {
    "fwd": _TileCost(2048, 512, 3 << 20, 80, 170_000),
    "bwd": _TileCost(2048, 1024, 4 << 20, 0, 60_000),
}


def _pick_tile(op: str, kernel: str, block_q: int, block_k: int, *,
               fold: int = 1, interpret: bool = False, legal, tiles,
               steps=None):
    """(block_q, block_k) of the kernel `kernel` ("fwd", "dq" or "dkdv")
    of the family `op`, from the blocks the policy passed (fitted to the
    sequence already), the `fold` query heads a tile's rows hold of each
    token, and what the family says of a tile: `legal(block_q, block_k)`
    (whole tiles, its own conditions), `tiles(block_q, block_k)`, how many
    tiles a head's grid computes, and `steps(block_q, block_k)`, how many
    steps that grid has, dead ones too (a grid is a rectangle; the
    forward's rule was fitted without them and leaves them out).

    The candidates are the passed blocks doubled any number of times, up
    to the kernel's `_TILE_COST`: its most keys, its most rows and its
    largest float32 [rows, keys] tile; a Q block whose tile alone passes
    that is halved first, down to 128 rows a head. Of them the cheapest is
    taken, a tile costing its pairs, `update_keys` keys a row and
    `step_pairs` for each grid step: so tiles grow until what they compute
    past a band's or a causal edge outweighs the steps (and the forward's
    updates) they save, and a narrow band keeps narrow tiles. In interpret
    mode there is no step to save and the passed blocks are the tile (any
    divisor of the sequence: the tests' odd shapes). Sets the gauge
    `attention_fwd_tile{op=, field=rows|keys_per_update}` or
    `attention_bwd_tile{op=, kernel=dq|dkdv, field=rows|keys}` (trace
    time, host side)."""
    limit = _TILE_COST["fwd" if kernel == "fwd" else "bwd"]
    steps = steps or tiles
    fits = lambda bq, bk: (fold * bq <= max(limit.rows, fold * block_q)
                           and fold * bq * bk * 4 <= limit.tile_bytes)
    while not fits(block_q, block_k) and block_q > 128 and not block_q % 2:
        block_q //= 2

    def cost(bq, bk):
        return (tiles(bq, bk) * fold * bq * (bk + limit.update_keys)
                + steps(bq, bk) * limit.step_pairs)

    def doubled(block, ok):
        out = [block]
        while ok(2 * out[-1]):
            out.append(2 * out[-1])
        return out

    best = (block_q, block_k) if interpret else min(
        ((bq, bk)
         for bq in doubled(block_q, lambda bq: fits(bq, block_k)
                           and legal(bq, block_k))
         for bk in doubled(block_k, lambda bk: bk <= limit.keys
                           and fits(bq, bk) and legal(bq, bk))),
        key=lambda tile: cost(*tile))
    from deeplearning4j_tpu.observe import get_registry

    if kernel == "fwd":
        gauge = functools.partial(get_registry().gauge,
                                  "attention_fwd_tile", op=op)
        gauge(field="keys_per_update").set(best[1])
    else:
        gauge = functools.partial(get_registry().gauge,
                                  "attention_bwd_tile", op=op, kernel=kernel)
        gauge(field="keys").set(best[1])
    gauge(field="rows").set(fold * best[0])
    return best


def _publish_bwd_steps(op: str, kernel: str, heads: int, steps: int,
                       live: int, interior: int):
    """Gauge `attention_bwd_steps{op=, kernel=, kind=interior|edge|dead}`:
    the grid a backward kernel builds for one call, from the geometry
    (trace time, host side). `steps`, `live` and `interior` are one head's:
    all steps of its grid, those that compute a tile, and those of them
    whose tile is wholly visible and builds no mask."""
    from deeplearning4j_tpu.observe import get_registry

    gauge = functools.partial(get_registry().gauge, "attention_bwd_steps",
                              op=op, kernel=kernel)
    gauge(kind="interior").set(heads * interior)
    gauge(kind="edge").set(heads * (live - interior))
    gauge(kind="dead").set(heads * (steps - live))


def _last_live(i, block_q: int, block_k: int):
    """The last K block in which a causal Q block `i` has a key."""
    return ((i + 1) * block_q - 1) // block_k


def _causal_tiles(t: int, block_q: int, block_k: int) -> int:
    """Tiles of the causal triangle over `t` tokens, the diagonal's whole."""
    return sum(_last_live(i, block_q, block_k) + 1
               for i in range(t // block_q))


def _tile_params(rows: int, keys: int, d: int, itemsize: int,
                 backward: bool = False):
    """Compiler parameters of a kernel with a [rows, keys] tile: the
    grid's first two dimensions carry no state (Mosaic may run them in any
    order and pipeline them), the sweep carries the scratch. A wide tile's
    float32 temporaries (scores, weights, the mask's iotas; the backward's
    dP and dS too) outgrow the default scoped VMEM of 16 MiB, of the
    chip's 128: the limit is raised to what the tile needs."""
    if backward:
        need = (12 * rows * keys * 4            # s, p, dP, dS, mask, casts
                + 2 * (3 * rows + 4 * keys) * d * itemsize  # q, do, k, v
                + 2 * 2 * rows * _LSE_LANES * 4             # two statistics
                + (rows + 2 * keys) * max(d, _LSE_LANES) * 4)   # scratch
    else:
        need = (8 * rows * keys * 4             # the tile's temporaries
                + 2 * 2 * (rows + 2 * keys) * d * itemsize  # q, k, v twice
                + 3 * rows * max(d, _LSE_LANES) * 4)        # the scratch
    limit = None if need <= (12 << 20) else min(need + (8 << 20), 100 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)


def _run_flash(q, k, v, *, causal: bool, scale: float, block_q: int,
               block_k: int, interpret: bool, with_lse: bool = False):
    bh, tq, d = q.shape
    tk = k.shape[1]
    if causal and tq != tk:
        raise ValueError(
            f"causal attention requires Tq == Tk (got {tq} vs {tk}); "
            "cross-attention is non-causal")
    g = _group(q, k)

    def tiles(bq, bk):
        return (_causal_tiles(tq, bq, bk) if causal
                else (tq // bq) * (tk // bk))

    block_q, block_k = _pick_tile(
        "flash_attention", "fwd", _fit_block(block_q, tq),
        _fit_block(block_k, tk), interpret=interpret, tiles=tiles,
        legal=lambda bq, bk: tq % bq == 0 and tk % bk == 0)
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               with_lse=with_lse)
    if causal:
        # a dead step names the block before it again: nothing is fetched
        kv_index = lambda b, i, j: (
            b // g, jnp.minimum(j, _last_live(i, block_q, block_k)), 0)
    else:
        kv_index = lambda b, i, j: (b // g, j, 0)
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
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=tuple(out_shape) if with_lse else out_shape[0],
        scratch_shapes=_softmax_scratch(block_q, d),
        compiler_params=_tile_params(block_q, block_k, d, q.dtype.itemsize),
        interpret=interpret,
    )(q, k, v)
    if with_lse:
        o, lse = out
        # Keep only one lane of the lane-broadcast row stats: residual
        # memory between forward and backward is O(T), not O(128*T).
        return o, lse[..., 0]
    return out, None


# ----------------------------------------------------- blockwise backward
_NT = (((1,), (1,)), ((), ()))    # both operands contract their last axis
_STAT_ROWS = 8    # sublanes of a row statistic laid along lanes ([8, T])

def _stat_lanes(x):
    """[..., T] -> [..., T, 128]: a row statistic as the dQ kernels read
    it, every lane of a row holding the row's value."""
    return jnp.broadcast_to(x[..., None], x.shape + (_LSE_LANES,))


def _stat_rows(x):
    """[..., T] -> [..., 8, T]: a row statistic as the dK/dV kernels read
    it, the rows along lanes (a key-major tile's columns are the rows)."""
    return jnp.broadcast_to(x[..., None, :],
                            x.shape[:-1] + (_STAT_ROWS, x.shape[-1]))


def _bwd_scores(q, k, v, do, lse, delta, mask, scale: float,
                key_major: bool):
    """One score tile again from the saved log-sum-exp, the one every
    backward kernel of the three families makes: p = exp(s - L) on the
    pairs `mask` has (None: all of them, an interior tile), 0 on the
    others, and ds = p * (do·vᵀ - Δ) * scale, both float32.

    Query-major (dQ): [rows, keys], with `lse` and `delta` the [rows, 128]
    operands they are, a lane wide. Key-major (dK/dV): [keys, rows] from
    k·qᵀ and v·doᵀ, so that p and ds are born in the orientation that
    dV += p·do and dK += ds·q contract over and nothing is transposed a
    tile; `lse` and `delta` are then [1, rows], broadcast down sublanes."""
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=_NT,
        preferred_element_type=jnp.float32, precision=_prec(q.dtype))
    if key_major:
        s, dp = dot(k, q) * scale, dot(v, do)
    else:
        s, dp = dot(q, k) * scale, dot(do, v)
        lse, delta = _lanes(lse, k.shape[0]), _lanes(delta, k.shape[0])
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p, p * (dp - delta) * scale


def _dq_step(dq_scr, q, k, v, do, lse, delta, mask, scale: float):
    """dQ += ds·k for one tile: q, do [rows, D], k, v [keys, D], `lse` and
    `delta` [rows, 128], `mask` [rows, keys] or None."""
    _, ds = _bwd_scores(q, k, v, do, lse, delta, mask, scale, False)
    dq_scr[:] += jnp.dot(ds.astype(k.dtype), k,
                         preferred_element_type=jnp.float32,
                         precision=_prec(k.dtype))


def _dkdv_step(dk_scr, dv_scr, q, k, v, do, lse, delta, mask, scale: float):
    """dV += pᵀ·do and dK += dsᵀ·q for one tile, computed key-major:
    `lse` and `delta` [1, rows], `mask` [keys, rows] or None."""
    prec = _prec(q.dtype)
    p, ds = _bwd_scores(q, k, v, do, lse, delta, mask, scale, True)
    dv_scr[:] += jnp.dot(p.astype(do.dtype), do,
                         preferred_element_type=jnp.float32, precision=prec)
    dk_scr[:] += jnp.dot(ds.astype(q.dtype), q,
                         preferred_element_type=jnp.float32, precision=prec)


def _on_tiles(step, live=None, interior=None):
    """Run `step(masked)` on a grid step's tile: not at all on a dead
    tile, bare (`masked` False) on an interior one, where every pair is
    visible, and with the mask built on an edge tile. `live` None: every
    tile is interior (no mask exists); `interior` None: none is."""
    if live is None:
        step(False)
    elif interior is None:
        pl.when(live)(lambda: step(True))
    else:
        pl.when(live & interior)(lambda: step(False))
        pl.when(live & jnp.logical_not(interior))(lambda: step(True))


def _causal_mask(qb, kb, bq: int, bk: int, key_major: bool):
    """The causal triangle inside Q block `qb` x K block `kb`: [bq, bk],
    or [bk, bq] key-major."""
    shape = (bk, bq) if key_major else (bq, bk)
    q_ids = qb * bq + jax.lax.broadcasted_iota(jnp.int32, shape,
                                               int(key_major))
    k_ids = kb * bk + jax.lax.broadcasted_iota(jnp.int32, shape,
                                               int(not key_major))
    return q_ids >= k_ids


def _causal_kind(qb, kb, bq: int, bk: int):
    """(live, interior) of a causal tile: it has a key at or before its
    last row; all its keys are at or before its first row."""
    return kb * bk <= (qb + 1) * bq - 1, (kb + 1) * bk - 1 <= qb * bq


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                           scale: float, nq: int):
    """Grid = (batch·KV heads, K blocks, group x Q blocks): the K/V tile's
    gradient accumulates in VMEM scratch across the innermost sweep, which
    takes the `nq` Q blocks of each query head of the group in turn."""
    kb = pl.program_id(1)
    step = pl.program_id(2)
    qb = step % nq
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(masked):
        mask = _causal_mask(qb, kb, bq, bk, True) if masked else None
        _dkdv_step(dk_scr, dv_scr, q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                   lse_ref[0, :1], delta_ref[0, :1], mask, scale)

    # Causal: Q blocks entirely above this K block's first row are dead.
    _on_tiles(tile, *(_causal_kind(qb, kb, bq, bk) if causal else ()))

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal: bool, scale: float):
    """Grid = (batch·heads, Q blocks, K blocks): the Q tile's gradient
    accumulates in VMEM scratch across the innermost K sweep."""
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(masked):
        mask = _causal_mask(qb, kb, bq, bk, False) if masked else None
        _dq_step(dq_scr, q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
                 delta_ref[0], mask, scale)

    _on_tiles(tile, *(_causal_kind(qb, kb, bq, bk) if causal else ()))

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _run_flash_bwd(q, k, v, o, lse, do, *, causal: bool, scale: float,
                   block_q: int, block_k: int, interpret: bool,
                   dlse=None):
    """Blockwise dq/dk/dv from O(T) residuals (q, k, v, o, L).

    `lse` is the narrow [BH, Tq] log-sum-exp saved by the forward; both
    row stats are laid out here as each kernel reads them (`_stat_lanes`,
    `_stat_rows`). `dlse` (optional, [BH, Tq]) is the cotangent of the
    emitted log-sum-exp when the caller exposes it as an output (ring
    attention's merge does): since dL/ds_ij = p_ij, it folds into the
    softmax-vjp identity as a shift on Δ: ds = p * (dp - (Δ - dL)).

    Each kernel takes its own tile from the passed blocks (`_pick_tile`:
    dQ sweeps keys, dK/dV sweeps rows). A dead step of a causal grid names
    the nearest live tile again, so that it fetches nothing.
    """
    bh, tq, d = q.shape
    tk = k.shape[1]
    g = _group(q, k)

    def tiles(bq, bk):
        return (_causal_tiles(tq, bq, bk) if causal
                else (tq // bq) * (tk // bk))

    def pick(kernel):
        bq, bk = _pick_tile(
            "flash_attention", kernel, _fit_block(block_q, tq),
            _fit_block(block_k, tk), interpret=interpret, tiles=tiles,
            steps=lambda bq, bk: (tq // bq) * (tk // bk),
            legal=lambda bq, bk: tq % bq == 0 and tk % bk == 0)
        nq, nk = tq // bq, tk // bk
        # a Q block's K blocks that lie wholly at or before its first row
        interior = (sum(min(nk, (i * bq + 1) // bk) for i in range(nq))
                    if causal else nq * nk)
        _publish_bwd_steps("flash_attention", kernel, bh, nq * nk,
                           tiles(bq, bk), interior)
        return bq, bk, nq, nk

    # Δ = rowsum(do · o): one cheap fused elementwise+reduce in XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    params = functools.partial(_tile_params, d=d, itemsize=q.dtype.itemsize,
                               backward=True)

    # dK/dV: K/V tile pinned (grid dim 1); the innermost dim sweeps the
    # group's query heads and, within each, its Q blocks
    bq, bk, nq, nk = pick("dkdv")
    # head and Q block of step i; causal: no earlier than the first Q block
    # with a row at or after K block j's first key
    head = lambda b, i: b * g + i // nq
    block = lambda j, i: (jnp.maximum(i % nq, j * bk // bq) if causal
                          else i % nq)
    q_spec = pl.BlockSpec((1, bq, d),
                          lambda b, j, i: (head(b, i), block(j, i), 0))
    row_spec = pl.BlockSpec((1, _STAT_ROWS, bq),
                            lambda b, j, i: (head(b, i), 0, block(j, i)))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, causal=causal, scale=scale,
                          nq=nq),
        name="flash_attention_bwd_dkdv",
        grid=(bh // g, nk, g * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=params(bq, bk),
        interpret=interpret,
    )(q, k, v, do, _stat_rows(lse), _stat_rows(delta))

    bq, bk, nq, nk = pick("dq")
    if causal:
        kv_index = lambda b, i, j: (
            b // g, jnp.minimum(j, _last_live(i, bq, bk)), 0)
    else:
        kv_index = lambda b, i, j: (b // g, j, 0)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), kv_index)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale),
        name="flash_attention_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params(bq, bk),
        interpret=interpret,
    )(q, k, v, do, _stat_lanes(lse), _stat_lanes(delta))
    return dq, dk, dv


def flash_eligible(tq: int, tk: Optional[int] = None, *,
                   min_t: int = 512) -> bool:
    """SHAPE eligibility for the flash kernel: TPU backend and
    128-lane-tileable sequence lengths. `min_t` is a PERF floor, not a
    capability one — the kernel runs from 128 up, but below ~512 it
    cannot amortize its block machinery, so the default floor suits
    structural users (ring attention's lse merge) that gate on this
    alone. The flash-vs-dense verdict, block sizes, and backward
    selection live in `kernel_defaults.attention_policy`, which asks
    for capability (min_t=128): its memory hazard holds from there."""
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
    """None -> the policy's default (kernel_defaults). Resolved
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
    None (default) resolves via
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
