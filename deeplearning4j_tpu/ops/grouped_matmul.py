"""Grouped matrix product over rows sorted by group, kernels.

    out[r] = lhs[r] @ rhs[g(r)]        g(r) the group row r lies in

`lhs` [M, K] holds the rows of group 0, then group 1 and so on,
`group_sizes` [G] says how many each has and `rhs` [G, K, N] holds a
kernel a group: what `jax.lax.ragged_dot` computes, operands in their own
dtype, sums in float32. The rows past `sum(group_sizes)` are in no group
and what stands in them afterwards is undefined, as it is after
`ragged_dot` on the chip.

An expert layer on one TPU device has one tier of rows, sized for the
worst routing (`parallel/moe.held_experts`), and a step fills a
two-hundredth to a third of it, so here the GRID follows the groups: a
schedule made from `group_sizes` on the device and handed to the kernels
by scalar prefetch lists the (row tile, group) visits, a tile that
straddles two groups once for each with the other's rows masked on the
store, `sum over groups of the tiles a group touches` of them, at most
`ceil(live / tile) + G - 1`; and the grid's bound is that sum, a value the
device computes (a dynamic grid dimension), so no step runs past the
schedule's end. A rectangle of `ceil(M / tile) + G - 1` steps whose dead
steps sat under `pl.when` and named the last live step's blocks again
computed the same bits 3 to 5% slower at a seventh of the rows live (11%
for `d_rhs`; chip runs, PR 43). A group's kernel stays in VMEM over the
group's visits: only the rows stream.

Three kernels walk that schedule. `grouped_dot` is the product above;
`grouped_dot_dlhs` is the same body with the contraction over `rhs`'s last
axis (`d_lhs = d_out @ rhs[g]^T`, no transposed copy of the kernels in
HBM); `grouped_dot_drhs` contracts over a group's rows
(`d_rhs[g] = lhs[rows of g]^T @ d_out[rows of g]`) into a float32
accumulator that is written where the schedule leaves the group, and
walks one step more for each group of no rows, which writes that group's
zeros. Each is under a `jax.jit` of its own, so a program with a hundred
calls traces and lowers a body a shape, not a body a call.

A second caller uses `grouped_dot_drhs` on its own: the token embedding's
gradient (`ops/embedding.py`). There a group is a TILE of the vocabulary's
rows, the rows are the step's tokens sorted by id, `lhs` is the one-hot of
an id's place inside its tile and `d_out` the lookup's cotangent in
sorted order, so `d_rhs[g]` is that tile of the table's gradient: fifty
to two hundred groups of some 40 to 230 rows each over 32 or 64
row tiles, most visits a tile that straddles groups, where an expert layer
has eight or nine groups of hundreds to thousands.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention import _NT, _prec

_NN = (((1,), (0,)), ((), ()))      # lhs @ rhs; `_NT` is lhs @ rhs^T
_TN = (((0,), (0,)), ((), ()))      # lhs^T @ rhs
# what a kernel's blocks may take of the chip's 128 MiB of VMEM, and the
# headroom Mosaic's own temporaries get over them
_VMEM_BLOCKS, _VMEM_ROOM, _VMEM_MOST = 40 << 20, 8 << 20, 100 << 20


def tile_rows(m: int) -> int:
    """The rows of a tile, from the shapes: 256, or all there are (in
    sixteens, a bf16 tile's sublanes). On the chip 128, 256 and 512 rows
    lie within 4% of each other wherever a group has a thousand rows or
    more, 256 first or within 2% of it; where it has a few hundred (4,096
    rows in 8 groups) 512 is a fifth slower, most of its visits being
    tiles that straddle two groups (chip runs, PR 43). 256 holds for the
    embedding's gradient too (49 to 192 groups over 32 or 64 row tiles,
    `ops/embedding.py`): the whole gradient read 0.57 to 2.63 ms with it,
    0.03 to 0.09 more than with 128 and 0.09 to 0.30 less than with 512
    (chip runs, PR 48), so the one rule stays."""
    return min(256, -(-m // 16) * 16)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tile", "group", "start", "end", "visits",
                                "empty"], meta_fields=["rows"])
@dataclasses.dataclass(frozen=True)
class Schedule:
    """The visits of a grouped product over tiles of `rows` rows, one
    entry a grid step: each visit's row tile and group (`tile`, `group`),
    after them one step for each group of no rows (that group, and the
    last visit's tile again: nothing is fetched), which only
    `grouped_dot_drhs` walks; each group's first row and the row past its
    last (`start`, `end`); how many visits there are (`visits` [1]) and
    how many groups of no rows (`empty`)."""
    tile: jax.Array
    group: jax.Array
    start: jax.Array
    end: jax.Array
    visits: jax.Array
    empty: jax.Array
    rows: int

    @property
    def prefetched(self):
        return self.tile, self.group, self.start, self.end, self.visits


@functools.partial(jax.jit, static_argnums=(1, 2))
def schedule(group_sizes, m: int, rows: Optional[int] = None) -> Schedule:
    """The `Schedule` of `group_sizes` over `m` rows in tiles of `rows`
    (`tile_rows(m)` where None). The products of one layer share their
    group sizes: made once and passed to each in their place, it is
    computed once. Under a `jax.jit` of its own and in `lax` primitives,
    not `jnp` functions (each of which is a jitted function of its own):
    a checkpointed layer's partial evaluation, transposition and lowering
    walk every equation of what it holds, and thirty small functions a
    layer cost `granite_4_0_h_small_fit` 3 s of set-up (chip runs,
    PR 43)."""
    rows = rows or tile_rows(m)
    sizes = jax.lax.convert_element_type(group_sizes, jnp.int32)
    g, steps = sizes.shape[0], -(-m // rows) + sizes.shape[0] - 1
    i32 = lambda v: jnp.int32(v)
    last = lambda v: jax.lax.index_in_dim(v, g - 1, keepdims=False)
    # [steps, g] -> [steps]: the one entry of `v` that `hit` marks, a step
    pick = lambda hit, v: jax.lax.reduce(
        jax.lax.select(hit, jnp.broadcast_to(v, hit.shape),
                       jnp.zeros(hit.shape, jnp.int32)),
        i32(0), jax.lax.add, (1,))
    end = jax.lax.cumsum(sizes, axis=0)
    start = end - sizes
    first = jax.lax.div(start, i32(rows))
    has_rows = sizes > 0
    touched = jax.lax.select(
        has_rows, jax.lax.div(end - 1, i32(rows)) - first + 1,
        jnp.zeros_like(sizes))
    upto = jax.lax.cumsum(touched, axis=0)
    visits = last(upto)
    step = jax.lax.iota(jnp.int32, steps)
    at = jax.lax.min(step, jax.lax.max(visits - 1, i32(0)))
    # a visit's group is the first whose run of visits ends past it
    before = jax.lax.convert_element_type(
        upto[None, :] <= at[:, None], jnp.int32)
    group = jax.lax.min(jax.lax.reduce(before, i32(0), jax.lax.add, (1,)),
                        i32(g - 1))
    of_group = group[:, None] == jax.lax.iota(jnp.int32, g)[None, :]
    tile = jax.lax.select(
        jnp.broadcast_to(visits > 0, (steps,)),
        pick(of_group, first) + at - pick(of_group, upto - touched),
        jnp.zeros((steps,), jnp.int32))
    # after the visits, the groups of no rows in turn: the k-th of them
    # is the group with no rows that has k such groups before it
    no_rows = jax.lax.convert_element_type(~has_rows, jnp.int32)
    rank = jax.lax.cumsum(no_rows, axis=0) - no_rows
    kth = (~has_rows)[None, :] & (rank[None, :] == (step - visits)[:, None])
    group = jax.lax.select(step < visits, group,
                           pick(kth, jax.lax.iota(jnp.int32, g)))
    return Schedule(tile, group, start, end, visits.reshape(1),
                    last(jax.lax.cumsum(no_rows, axis=0)), rows)


def rows_visited(plan: Schedule):
    """The rows a grouped product multiplies: the schedule's visits times
    a tile's rows."""
    return plan.visits[0] * plan.rows


def _pick_cols(n: int, need) -> int:
    """The widest block of `n` columns (all of them, or a divisor of `n`
    in whole lanes of 128) whose kernel `need(cols)` fits the VMEM a
    kernel's blocks may take."""
    for parts in range(1, n + 1):
        cols, rest = divmod(n, parts)
        if rest or (parts > 1 and cols % 128):
            continue
        if need(cols) <= _VMEM_BLOCKS:
            return cols
    raise ValueError(f"no block of {n} columns fits {_VMEM_BLOCKS} bytes")


def _params(need: int, grid: int):
    limit = None if need <= (12 << 20) else min(need + _VMEM_ROOM,
                                                _VMEM_MOST)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid - 1) + ("arbitrary",),
        vmem_limit_bytes=limit)


def _in_group(start_ref, end_ref, g, t, rows: int):
    """(lo, hi, interior): the group's rows counted from the tile's first
    row, and whether the whole tile lies in the group."""
    lo, hi = start_ref[g] - t * rows, end_ref[g] - t * rows
    return lo, hi, (lo <= 0) & (hi >= rows)


def _row_mask(lo, hi, shape):
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (r >= lo) & (r < hi)


def _dot_kernel(tile_ref, group_ref, start_ref, end_ref, visits_ref,
                lhs_ref, rhs_ref, out_ref, *, rows: int, dims):
    v = pl.program_id(1)
    lo, hi, interior = _in_group(start_ref, end_ref, group_ref[v],
                                 tile_ref[v], rows)
    out = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims, preferred_element_type=jnp.float32,
        precision=_prec(lhs_ref.dtype)).astype(out_ref.dtype)

    @pl.when(interior)
    def _():
        out_ref[...] = out

    @pl.when(jnp.logical_not(interior))
    def _():    # the rows of the tile's other groups stay theirs
        out_ref[...] = jnp.where(_row_mask(lo, hi, out.shape), out,
                                 out_ref[...])


def _drhs_kernel(tile_ref, group_ref, start_ref, end_ref, visits_ref,
                 lhs_ref, dout_ref, out_ref, acc_ref, *, rows: int):
    v, steps = pl.program_id(2), pl.num_programs(2)
    g = group_ref[v]

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(v < visits_ref[0])     # past them: a group of no rows
    def _():
        lo, hi, interior = _in_group(start_ref, end_ref, g, tile_ref[v],
                                     rows)

        def add(a, b):
            acc_ref[...] += jax.lax.dot_general(
                a, b, _TN, preferred_element_type=jnp.float32,
                precision=_prec(a.dtype))

        @pl.when(interior)
        def _():
            add(lhs_ref[...], dout_ref[...])

        @pl.when(jnp.logical_not(interior))
        def _():    # both sides: a row of no group may hold anything
            a, b = lhs_ref[...], dout_ref[...]
            add(jnp.where(_row_mask(lo, hi, a.shape), a, 0),
                jnp.where(_row_mask(lo, hi, b.shape), b, 0))

    @pl.when((v == steps - 1)
             | (group_ref[jnp.minimum(v + 1, steps - 1)] != g))
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _dot(lhs, rhs, plan: Schedule, *, transpose_rhs: bool, interpret: bool):
    """`lhs` [M, K] by `rhs` [G, K, N], or with `transpose_rhs` `lhs`
    [M, N] by `rhs`'s kernels transposed, -> [M, N] or [M, K]."""
    (m, k), rows = lhs.shape, plan.rows
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    size = lhs.dtype.itemsize
    need = lambda cols: (2 * (rows * k + k * cols + rows * cols) * size
                         + 2 * rows * cols * 4)
    cols = _pick_cols(n, need)
    return pl.pallas_call(
        functools.partial(_dot_kernel, rows=rows,
                          dims=_NT if transpose_rhs else _NN),
        name="grouped_dot_dlhs" if transpose_rhs else "grouped_dot",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan.prefetched),
            grid=(n // cols, plan.visits[0]),
            in_specs=[
                pl.BlockSpec((rows, k), lambda j, v, tile, *_: (tile[v], 0)),
                pl.BlockSpec((1, cols, k) if transpose_rhs else (1, k, cols),
                             (lambda j, v, tile, group, *_: (group[v], j, 0))
                             if transpose_rhs else
                             (lambda j, v, tile, group, *_: (group[v], 0, j))),
            ],
            out_specs=pl.BlockSpec((rows, cols),
                                   lambda j, v, tile, *_: (tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=_params(need(cols), 2),
        interpret=interpret,
    )(*plan.prefetched, lhs, rhs)


def _drhs_blocks(rows: int, k: int, n: int, size: int):
    """(kc, nc, bytes): the [kc, nc] block of a group's `d_rhs` that
    `grouped_dot_drhs` accumulates at a time, and the VMEM it needs: the
    narrower of the two operands in one block, the wider one in as few as
    fit."""
    need = lambda kc, nc: (2 * rows * (kc + nc) * size
                           + kc * nc * (4 + 4 + 2 * size))
    if k >= n:
        nc = _pick_cols(n, lambda c: need(128, c))
        kc = _pick_cols(k, lambda c: need(c, nc))
    else:
        kc = _pick_cols(k, lambda c: need(c, 128))
        nc = _pick_cols(n, lambda c: need(kc, c))
    return kc, nc, need(kc, nc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_dot_drhs(lhs, dout, plan: Schedule, *, interpret: bool = False):
    """`lhs` [M, K] and `dout` [M, N] -> [G, K, N]: each group's
    `lhs^T @ dout` over its own rows, summed in float32 and rounded once,
    zeros for a group of none. `grouped_dot`'s gradient by `rhs`, and on
    its own the sum of `dout`'s rows by whatever `lhs` marks
    (`ops/embedding.py`: a one-hot)."""
    (m, k), rows, groups = lhs.shape, plan.rows, plan.start.shape[0]
    n = dout.shape[1]
    kc, nc, need = _drhs_blocks(rows, k, n, lhs.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, rows=rows),
        name="grouped_dot_drhs",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan.prefetched),
            grid=(k // kc, n // nc, plan.visits[0] + plan.empty),
            in_specs=[
                pl.BlockSpec((rows, kc),
                             lambda i, j, v, tile, *_: (tile[v], i)),
                pl.BlockSpec((rows, nc),
                             lambda i, j, v, tile, *_: (tile[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, kc, nc),
                lambda i, j, v, tile, group, *_: (group[v], i, j)),
            scratch_shapes=[pltpu.VMEM((kc, nc), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=_params(need, 3),
        interpret=interpret,
    )(*plan.prefetched, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_dot(lhs, rhs, group_sizes, rows=None, interpret: bool = False):
    """`lhs` [M, K], `rhs` [G, K, N], `group_sizes` [G] -> [M, N]: row r
    by the kernel of the group it lies in, the rows sorted by group. Only
    the row tiles that hold a row of a group are visited (tiles of `rows`,
    `tile_rows(M)` where None); a row in no group is left undefined, and
    so is its gradient. In place of `group_sizes` the `schedule` made from
    them may be passed, which several products over the same groups then
    share."""
    return _fwd(lhs, rhs, group_sizes, rows, interpret)[0]


def _fwd(lhs, rhs, group_sizes, rows, interpret):
    plan = group_sizes if isinstance(group_sizes, Schedule) else schedule(
        group_sizes, lhs.shape[0], rows)
    out = _dot(lhs, rhs, plan, transpose_rhs=False, interpret=interpret)
    return out, (lhs, rhs, plan)


def _bwd(rows, interpret, res, dout):
    lhs, rhs, plan = res
    return (_dot(dout, rhs, plan, transpose_rhs=True, interpret=interpret),
            grouped_dot_drhs(lhs, dout, plan, interpret=interpret), None)


grouped_dot.defvjp(_fwd, _bwd)
