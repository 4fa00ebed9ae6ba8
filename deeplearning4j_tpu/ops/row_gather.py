"""Row gathers whose work follows the rows that are live, kernels.

    take_rows(x [N, d], index [C], live)  -> [C, d]   out[r] = x[index[r]]
    sum_rows(x [C, d], back [N, k], live) -> [N, d]   y[t] = sum_j x[back[t, j]]

for the rows `r < live` and the places `back[t, j] < live` alone: what an
expert layer's dispatch and combine are (`parallel/moe.held_experts`), each
the other's transpose, over a tier of `C` rows sized for the worst routing
of which a step fills a two-hundredth to a third. XLA's gather walks the tier;
here a row is ONE DMA, issued only where the row is live, several in
flight, and a grid whose bound the device computes from `live` stops at
the last tile that holds one.

Mosaic takes a one-row slice of an array only along a LEADING dimension
(a row of a 2-D array is a sixteenth of a bf16 tile, in HBM and in VMEM
alike), so between the two ends of a copy a row is a slab of its own: an
array `[rows, d]` in "row form" is `[rows, s, lanes]` words, a bf16 row two
values a uint32 word (column `c` in the low half, column `c + d/2` in the
high, so packing and unpacking move whole lane blocks and no lane), a
float32 row as it is. `pack_rows` writes that form from the tiled one over
the live row tiles, strided stores inside the kernel; `take_rows` and
`sum_rows` copy rows of it and unpack by strided loads. What an expert
layer does to the tier's rows round its gathers rides in these passes
over the live rows, so that no pass of XLA's walks the tier: the packing
adds a second cotangent and weighs a row by its pair's weight, and
`sum_rows`' transpose weighs the rows it takes and writes their dots with
the rows weighed, the weights' gradient. Each is under a `jax.jit` of its
own, so a program with sixty calls traces and lowers a body a shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.grouped_matmul import tile_rows

# row copies in flight. 20,000 rows of 8 KiB took 2.08, 1.23, 0.93 and
# 0.77 ms with 4, 8, 16 and 32 in flight and 0.60 with a whole tile's 256
# (XLA's gather: 0.80): the copies are bound by their issue, some 30 ns
# each, not by bytes (chip runs, PR 44)
RING = 32
# tokens a grid step of `sum_rows`, whose sums it holds in row form
TOKENS = 32


def _form(d: int, dtype):
    """(s, lanes, word dtype) of a row of `d` values in row form."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        if d % 2:
            raise ValueError(f"a bf16 row of {d} values has no word pairs")
        words, word = d // 2, jnp.uint32
    elif dtype == jnp.float32:
        words, word = d, jnp.float32
    else:
        raise ValueError(f"rows of {dtype} are not gathered here")
    lanes = 128 if words % 128 == 0 else words
    return words // lanes, lanes, word


def _pack(lo, hi):
    """Two bf16 lane blocks in one uint32 block."""
    bits = lambda v: jax.lax.bitcast_convert_type(
        v.astype(jnp.float32), jnp.uint32)
    return (bits(lo) >> 16) | (bits(hi) & jnp.uint32(0xFFFF0000))


def _in_float32(fn, *operands):
    """`fn` of the operands in float32, rounded once to their dtype: what
    XLA makes of a bf16 sum or product."""
    return fn(*(v.astype(jnp.float32) for v in operands)).astype(
        operands[0].dtype)


def _unpack(u):
    """The two float32 lane blocks a uint32 block holds as bf16."""
    f32 = lambda v: jax.lax.bitcast_convert_type(v, jnp.float32)
    return f32(u << 16), f32(u & jnp.uint32(0xFFFF0000))


def _ring(count, start, landed):
    """`start(i)` then `landed(i)` for every i < `count`, in order, RING
    copies in flight: RING out, then one out for each that lands (its slot
    takes the next), then the last RING in. Three loops with no branch in
    them: a `pl.when` a row cost `sum_rows` a fifth of its time (chip
    runs, PR 44)."""
    loop = lambda lo, hi, body: jax.lax.fori_loop(
        lo, hi, lambda i, carry: body(i) or carry, None)
    loop(jnp.int32(0), jax.lax.min(count, jnp.int32(RING)), start)
    loop(jnp.int32(RING), count,
         lambda i: (landed(i - RING), start(i)) and None)
    loop(jax.lax.max(count - RING, jnp.int32(0)), count, landed)


def _tiles(live, tile: int):
    return jax.lax.div(live + (tile - 1), jnp.int32(tile))


def _live(live):
    return jax.lax.convert_element_type(live, jnp.int32).reshape(1)


# ------------------------------------------------------------------ pack
def _pack_kernel(live_ref, *refs, s: int, lanes: int, other: bool,
                 weight: bool):
    x_ref, out_ref = refs[0], refs[-1]
    half = s * lanes

    def block(at):      # (x + other) * weight, each rounded as XLA rounds
        v = x_ref[:, at:at + lanes]
        if other:
            v = _in_float32(jax.lax.add, v, refs[1][:, at:at + lanes])
        if weight:
            v = _in_float32(jax.lax.mul, v, jnp.broadcast_to(
                refs[-2][...], v.shape).astype(v.dtype))
        return v

    for j in range(s):
        if out_ref.dtype == jnp.uint32:
            out_ref[:, j, :] = _pack(block(j * lanes),
                                     block(half + j * lanes))
        else:
            out_ref[:, j, :] = block(j * lanes)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_rows(x, live, other=None, weight=None, *, interpret: bool = False):
    """`x` [R, d] -> its row form [R, s, lanes], over the row tiles that
    hold a row under `live`; the tiles past them hold anything. With
    `other` [R, d] the rows of `x + other`, with `weight` [R] each row
    times its weight, rounded as XLA rounds them."""
    (r, d), (s, lanes, word) = x.shape, _form(x.shape[1], x.dtype)
    tile = tile_rows(r)
    rows = pl.BlockSpec((tile, d), lambda i, live: (i, 0))
    operands, specs = [x], [rows]
    if other is not None:
        operands, specs = operands + [other], specs + [rows]
    if weight is not None:
        operands.append(weight.reshape(r, 1))
        specs.append(pl.BlockSpec((tile, 1), lambda i, live: (i, 0)))
    return pl.pallas_call(
        functools.partial(_pack_kernel, s=s, lanes=lanes,
                          other=other is not None,
                          weight=weight is not None),
        name="pack_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_tiles(_live(live)[0], tile),),
            in_specs=specs,
            out_specs=pl.BlockSpec((tile, s, lanes),
                                   lambda i, live: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((r, s, lanes), word),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(_live(live), *operands)


# ------------------------------------------------------------------ take
def _take_kernel(live_ref, index_ref, x_ref, *refs, tile: int, s: int,
                 lanes: int, weighed: bool):
    if weighed:
        weight_ref, other_ref, out_ref, dots_ref, buf, sem = refs
    else:
        out_ref, buf, sem = refs
    i = pl.program_id(0)
    live = live_ref[0] - i * tile          # rows of this tile that are live
    copy = lambda r: pltpu.make_async_copy(
        x_ref.at[index_ref[0, r]], buf.at[r],
        sem.at[jax.lax.rem(r, jnp.int32(RING))])
    _ring(jax.lax.min(live, jnp.int32(tile)), lambda r: copy(r).start(),
          lambda r: copy(r).wait())
    half = s * lanes

    def store(keep):
        dots = jnp.zeros((tile, lanes), jnp.float32)
        for j in range(s):
            u = buf[:, j, :]
            if buf.dtype == jnp.uint32:
                parts = zip(_unpack(u), (j * lanes, half + j * lanes))
            else:
                parts = ((u, j * lanes),)
            for v, at in parts:
                v = keep(v)
                if weighed:     # the row dots first, then the row weighed
                    dots = dots + v * other_ref[:, at:at + lanes].astype(
                        jnp.float32)
                    v = v * jnp.broadcast_to(
                        weight_ref[...], v.shape).astype(jnp.float32)
                out_ref[:, at:at + lanes] = v.astype(out_ref.dtype)
        if weighed:
            dots_ref[...] = jnp.sum(dots, axis=1, keepdims=True).astype(
                dots_ref.dtype)

    @pl.when(live >= tile)
    def _():
        store(lambda v: v)

    @pl.when(live < tile)       # the last tile: zeros past the live rows
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (tile, lanes), 0)
        store(lambda v: jax.lax.select(row < live, v, jnp.zeros_like(v)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _take(x, index, live, weight=None, other=None, *, interpret: bool):
    """`x[index]` over the live rows; with `weight` [C] and `other`
    [C, d] (`x[index] * weight[:, None]`, the row dots `sum(x[index] *
    other, axis=1)` [C]), the dots summed in float32."""
    (n, d), c = x.shape, index.shape[0]
    s, lanes, word = _form(d, x.dtype)
    tile = tile_rows(c)
    tiles = -(-c // tile)
    live = _live(live)
    index = jax.lax.pad(jax.lax.convert_element_type(index, jnp.int32),
                        jnp.int32(0), ((0, tiles * tile - c, 0),))
    packed = pack_rows(x, jnp.int32(n), interpret=interpret)
    weighed = weight is not None
    rows = pl.BlockSpec((tile, d), lambda i, live: (i, 0))
    row = pl.BlockSpec((tile, 1), lambda i, live: (i, 0))
    taken = jax.ShapeDtypeStruct((c, d), x.dtype)
    out = pl.pallas_call(
        functools.partial(_take_kernel, tile=tile, s=s, lanes=lanes,
                          weighed=weighed),
        name="take_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(_tiles(live[0], tile),),
            in_specs=[
                pl.BlockSpec((None, 1, tile), lambda i, live: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
            + ([row, rows] if weighed else []),
            out_specs=[rows, row] if weighed else rows,
            scratch_shapes=[pltpu.VMEM((tile, s, lanes), word),
                            pltpu.SemaphoreType.DMA((RING,))]),
        out_shape=[taken, jax.ShapeDtypeStruct((c, 1), weight.dtype)]
        if weighed else taken,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(live, index.reshape(tiles, 1, tile), packed,
      *((weight.reshape(c, 1), other) if weighed else ()))
    return (out[0], out[1].reshape(c)) if weighed else out


# ------------------------------------------------------------------- sum
def _sum_kernel(live_ref, count_ref, token_ref, first_ref, rows_ref, x_ref,
                out_ref, land, acc, sem, *, tokens: int, k: int, s: int,
                lanes: int):
    count = count_ref[pl.program_id(0)]     # the tile's live places
    slot = lambda q: jax.lax.rem(q, jnp.int32(RING))
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def start(i):       # the i-th live place: its token's (i - first)-th
        t = token_ref[0, i]
        row = rows_ref[0, t * k + i - first_ref[0, t]]
        pltpu.make_async_copy(x_ref.at[row], land.at[slot(i)],
                              sem.at[slot(i)]).start()

    def landed(q):      # add the row to its token's sums, in the places' order
        pltpu.make_async_copy(x_ref.at[0], land.at[slot(q)],
                              sem.at[slot(q)]).wait()
        t = token_ref[0, q]
        u = land[slot(q)]
        parts = _unpack(u) if land.dtype == jnp.uint32 else (u,)
        for half, v in enumerate(parts):
            acc[half, t] = acc[half, t] + v

    _ring(count, start, landed)
    for half in range(acc.shape[0]):
        for j in range(s):
            at = half * s * lanes + j * lanes
            out_ref[:, at:at + lanes] = acc[half, :, j, :].astype(
                out_ref.dtype)


def _live_places(back, live, tokens: int):
    """The places that hold a live row, a tile of `tokens` tokens at a
    time and in the places' own order (token by token, a token's pairs in
    j's order): how many a tile has (`count` [tiles]), the token of the
    tile's q-th (`token` [tiles, 1, tokens x k]), the number of the first
    place of each token in that list (`first` [tiles, 1, tokens]) and each
    token's live rows, its pairs' order kept, at the front of its k
    (`rows` [tiles, 1, tokens x k])."""
    n, k = back.shape
    tiles, p = n // tokens, tokens * k
    held = back < live
    ones = jax.lax.convert_element_type(held, jnp.int32)
    total = lambda v, axis: jax.lax.reduce(v, jnp.int32(0), jax.lax.add,
                                           (axis,))
    zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
    # the running counts as sums under a triangle: no `reduce-window`.
    # (Either way this function reads 0.56 to 0.59 ms a call on the chip
    # where the compiler's own estimate of its seven fusions is 0.08,
    # half the time of the `sum_rows` kernel it feeds, and nobody has
    # looked into it yet; chip runs, PR 44.)
    at, of = (jax.lax.broadcasted_iota(jnp.int32, (n, k, k), axis)
              for axis in (1, 2))
    rank = total(jax.lax.select(   # a pair's, among its token's live ones
        of < at, jnp.broadcast_to(ones[:, None, :], (n, k, k)),
        zeros(n, k, k)), 2)
    rows = total(jax.lax.select(
        held[:, None, :] & (rank[:, None, :] == at),
        jnp.broadcast_to(back[:, None, :], (n, k, k)), zeros(n, k, k)), 2)
    each = total(ones, 1).reshape(tiles, tokens)
    at, of = (jax.lax.broadcasted_iota(jnp.int32, (tiles, tokens, tokens),
                                       axis) for axis in (1, 2))
    upto = total(jax.lax.select(
        of <= at, jnp.broadcast_to(each[:, None, :], (tiles, tokens, tokens)),
        zeros(tiles, tokens, tokens)), 2)
    # the q-th live place is its token's: of the first token whose places
    # reach past q, so as many tokens as end at q or before stand before it
    q = jax.lax.broadcasted_iota(jnp.int32, (tiles, p, tokens), 1)
    token = total(jax.lax.convert_element_type(upto[:, None, :] <= q,
                                               jnp.int32), 2)
    return (jax.lax.index_in_dim(upto, tokens - 1, axis=1, keepdims=False),
            jax.lax.min(token, jnp.int32(tokens - 1)).reshape(tiles, 1, p),
            (upto - each).reshape(tiles, 1, tokens),
            rows.reshape(tiles, 1, p))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sum(x, back, live, other=None, weight=None, *, interpret: bool):
    (c, d), (n, k) = x.shape, back.shape
    s, lanes, word = _form(d, x.dtype)
    tokens = min(TOKENS, n)
    if n % tokens:
        raise ValueError(f"{n} tokens are not whole tiles of {tokens}")
    live = _live(live)
    count, token, first, rows = _live_places(
        jax.lax.convert_element_type(back, jnp.int32), live[0], tokens)
    packed = pack_rows(x, live, other, weight, interpret=interpret)
    scalars = lambda width: pl.BlockSpec(
        (None, 1, width), lambda i, *_: (i, 0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_sum_kernel, tokens=tokens, k=k, s=s, lanes=lanes),
        name="sum_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tokens,),
            in_specs=[scalars(tokens * k), scalars(tokens),
                      scalars(tokens * k),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((RING, s, lanes), word),
                pltpu.VMEM((2 if word == jnp.uint32 else 1, tokens, s,
                            lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(live, count, token, first, rows, packed)


# ------------------------------------------------- each the other's transpose
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def take_rows(x, index, back, live, copies: int = 1,
              interpret: bool = False):
    """`x` [N, d], `index` [C], `live` -> [C, d]: `x[index[r]]` in the rows
    `r < live`, zeros in the rest of the last tile that holds one, and
    anything in the tiles past it. `back` [N, k] is what `sum_rows` over
    the same pairs takes, each pair's place among the `C` rows: its
    transpose gathers by it. With `copies` > 1 a tuple of that array so
    many times over: each copy's cotangent comes back on its own and they
    are summed row by row as the rows are packed, not in a pass over the
    tier before it."""
    return _copies(_take(x, index, live, interpret=interpret), copies)


def _copies(taken, copies: int):
    return taken if copies == 1 else (taken,) * copies


def _take_fwd(x, index, back, live, copies, interpret):
    return (_copies(_take(x, index, live, interpret=interpret), copies),
            (back, live))


def _take_bwd(copies, interpret, res, g):
    back, live = res
    if copies == 1:
        g = (g,)
    if copies > 2:
        g = (g[0], functools.reduce(jax.lax.add, g[1:]))
    return (_sum(g[0], back, live, *g[1:], interpret=interpret),
            None, None, None)


take_rows.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def sum_rows(x, weight, index, back, live, interpret: bool = False):
    """`x` [C, d], `back` [N, k], `live` -> [N, d]: `sum_j x[back[t, j]]`
    over the places `back[t, j] < live`, accumulated in float32 in j's
    order and rounded once; with `weight` [C] (or None) each row times
    its weight first, rounded to the rows' dtype as `x * weight[:, None]`
    is. No row at or past `live` is read: it may hold anything. `index`
    [C] is what `take_rows` over the same pairs takes."""
    return _sum(x, back, live, None, weight, interpret=interpret)


def _sum_fwd(x, weight, index, back, live, interpret):
    return (_sum(x, back, live, None, weight, interpret=interpret),
            (x, weight, index, live))


def _sum_bwd(interpret, res, g):
    x, weight, index, live = res
    if weight is None:
        return (_take(g, index, live, interpret=interpret), None, None,
                None, None)
    # the rows at or past `live` hold anything, in both: a pair that is
    # not held has no weight to give a gradient to
    return (*_take(g, index, live, weight, x, interpret=interpret),
            None, None, None)


sum_rows.defvjp(_sum_fwd, _sum_bwd)
