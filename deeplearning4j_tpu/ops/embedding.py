"""Token lookup whose gradient is a grouped product, not a scatter.

    lookup(table [V, D], ids [...]) -> [..., D]        table[ids]

The forward is `jnp.take`. Its transpose, as XLA writes it, is a
scatter-add of the cotangent's rows into a table of zeros, whose time on
the chip depends on the row's width by a threshold inside the compiler
(8,192 rows of 5,120 into 12,800: 23.3 ms for 0.2 GB of traffic, where
3,072 wide into 25,024 rows took 2.5; chip runs, PR 47 and 48) and, row
after row, on how the ids repeat. What the gradient IS,

    dW = onehot(ids)^T @ dY

is a grouped product over the tokens sorted by id, a group a TILE of the
vocabulary's rows: `ops/grouped_matmul.grouped_dot_drhs` with `lhs` the
one-hot of an id's place inside its tile `[T, tile]` (exact in bfloat16)
and `dout` the cotangent's rows in sorted order. Each tile of the table is
summed in float32 over the row tiles that hold one of its ids and written
once, zeros where no id fell in it; the visits are at most
`ceil(T / 256) + tiles - 1` however the ids are distributed. At the five
token cells' shapes the whole gradient, sort and gather included, read
0.57 to 2.63 ms where the scatter read 1.61 to 23.32, uniform ids or
Zipfian (chip runs, PR 48).

Where it engages: where the one-device kernels run
(`ops/kernel_defaults.kernels_run`) and the shapes suit them
(`embedding_backward_tile`); elsewhere `lookup` IS `jnp.take`, scatter and
all. Ids out of range keep `jnp.take`'s meaning: a negative id counts from
the table's end, and an id at or past `V` (or below `-V`) reads NaN and
gives its cotangent to no row (it sorts past every tile, into no group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.kernel_defaults import (
    embedding_backward_tile, kernels_run, record_dispatch,
)


def lookup(table, ids):
    """`table[ids]`, rows of `table` [V, D] by integer `ids` of any shape."""
    ids = ids.astype(jnp.int32)
    tile = embedding_backward_tile(table.shape[1]) if kernels_run() else None
    record_dispatch("embedding_backward", "grouped" if tile else "scatter")
    if tile is None:
        return jnp.take(table, ids, axis=0)
    return _lookup(table, ids, (table.shape[0], tile))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(table, ids, form):
    """`form` = (the table's rows, the rows of a vocabulary tile)."""
    return jnp.take(table, ids, axis=0)


def _lookup_fwd(table, ids, form):
    return _lookup(table, ids, form), ids


def _lookup_bwd(form, ids, g):
    return table_gradient(ids, g, *form), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def table_gradient(ids, g, vocab: int, tile: int):
    """`ids` [...] and the lookup's cotangent `g` [..., D] -> [vocab, D]:
    row `r` the float32 sum, rounded once, of `g`'s rows whose id is `r`;
    the tokens sorted by id, `tile` rows of the table a group."""
    from deeplearning4j_tpu.ops import grouped_matmul   # Pallas: on use

    d = g.shape[-1]
    ids, g = ids.reshape(-1), g.reshape(-1, d)
    t, tiles = ids.shape[0], -(-vocab // tile)
    rows = grouped_matmul.tile_rows(t)
    ids = jnp.where(ids < 0, ids + vocab, ids)
    key = jnp.where((ids >= 0) & (ids < vocab), ids, tiles * tile)
    # whole row tiles: the places past the tokens sort with the ids out of
    # range, past every group, and what stands in their rows reaches nothing
    key = jnp.pad(key, (0, -t % rows), constant_values=tiles * tile)
    key, order = jax.lax.sort_key_val(
        key, jax.lax.iota(jnp.int32, key.shape[0]))
    of_tile = key // tile
    sizes = jnp.sum(of_tile[:, None] == jax.lax.iota(jnp.int32, tiles),
                    axis=0, dtype=jnp.int32)
    onehot = ((key - of_tile * tile)[:, None]
              == jax.lax.iota(jnp.int32, tile)).astype(g.dtype)
    dw = grouped_matmul.grouped_dot_drhs(
        onehot, jnp.take(g, jnp.minimum(order, t - 1), axis=0),
        grouped_matmul.schedule(sizes, key.shape[0], rows))
    return dw.reshape(tiles * tile, d)[:vocab]
