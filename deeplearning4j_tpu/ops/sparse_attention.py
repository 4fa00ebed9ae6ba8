"""Block-sparse causal attention whose block list is data (InfLLM-V2, the
`minicpm4` mixer of MiniCPM-SALA), selection and kernels.

For a query token `t` and a KV group `g` (the query heads that share one
KV head), `select_blocks` decides which blocks of `block_size` keys the
token reads:

  (1) compressed keys `kc_j = mean(k[stride j : stride j + kernel])`, over
      the windows that end at or before `t`;
  (2) `p_{h,j} = softmax_j(q_h . kc_j * scale)`, summed over the group's
      query heads;
  (3) a block's score is the largest `p` of the compressed windows that
      overlap it;
  (4) chosen: the first `init_blocks` blocks, the blocks that cover the
      last `window_size` tokens `[t - window_size + 1, t]`, and of the
      rest the best-scored until `topk` blocks are held (all, where fewer
      exist; of equal scores the lower block first).

The choice is discrete and carries no gradient. What it returns is a
mask `[B, Hkv, T, T // block_size]`, not index lists: a token's row says
which blocks it reads, and a count of its true entries is the number of
block visits the selection kept.

  (5) `block_sparse_attention`: `softmax` over the chosen blocks' keys
      `s <= t` of `q . k_s * scale`, times `v`.

The kernels are the flash kernels of `ops/attention.py` with two things
added. A Q tile of consecutive tokens walks the K tiles that ANY of its
tokens lists (the union, flags and fetch indices scalar-prefetched: a
tile nobody lists is neither fetched nor computed) and masks per row:
the row's listed blocks come as a `[block_q, 128]` slice of the mask and
are spread over the tile's keys by one small product with a 0/1 matrix
made from iotas, which costs the matrix unit half as much again and no
relayout. Forward, dQ and dK/dV kernels recompute scores a tile at a time
from the saved log-sum-exp; nothing `[T, T]` and no gathered copy of K or
V exists. The forward names its output and log-sum-exp
(`ops/attention.name_residuals`), so a checkpointed layer keeps them.

The forward's online-softmax step is `ops/attention._softmax_update`, the
flash and banded forwards' too, and the backward's tile theirs
(`ops/attention._dq_step`, `_dkdv_step`; dK/dV key-major, the mask's
spread product with it, `_tile_mask`). The K tile is the caller's (the
unit the walk skips by) and each kernel's Q tile its own
(`ops/attention._pick_tile`, `_kernel_tiles`), with a walk of its own
where it differs: at the policy's 256 x 512, 32 query heads and 16,384
tokens all three take 1,024 tokens a tile and share one walk; on a v5e a
forward call takes 26.7 ms where 256 with `[rows, 1]` statistics took
50.0 (PR 36, PERF.md section 6), dQ and dK/dV 31.1 and 35.5 ms where 256
with the tile transposed twice a step took 41.4 and 55.1 (PR 39, host
clock). A taller Q tile walks the union of more tokens' lists; on the
benchmark's seeded weights that is every causal tile at either height.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention import (
    _LSE_LANES, _NEG_INF, _NT, _STAT_ROWS, _causal_mask, _causal_tiles,
    _dkdv_step, _dq_step, _fold3, _group, _on_tiles, _pick_tile, _prec,
    _publish_bwd_steps, _softmax_finish, _softmax_init, _softmax_scratch,
    _softmax_update, _stat_lanes, _stat_rows, _tile_params, _unfold3,
    name_residuals,
)

_LANES = 128


class BlockSelection(NamedTuple):
    """The sizes of the selection (MiniCPM4's `sparse_config`)."""

    block_size: int = 64
    topk: int = 64            # blocks a token reads, the forced ones counted
    init_blocks: int = 1
    window_size: int = 2048
    kernel_size: int = 32
    kernel_stride: int = 16
    dense_len: int = 8192     # at or under it the layer is dense causal


class _Dense(threading.local):
    runs = 0


_dense = _Dense()


def dense_runs() -> int:
    """How many layers with a block selection this thread has traced so
    far as plain causal attention, because `T <= dense_len`; a model reads
    it before and after its own forward trace."""
    return _dense.runs


def note_dense_run() -> None:
    _dense.runs += 1


# ---------------------------------------------------------------- selection
def block_scores(q, k, sel: BlockSelection, scale=None, rows: int = 2048):
    """Steps (1) to (3): `[B, Hkv, T, NB]` float32 scores of every block
    for every token and KV group, 0 where no compressed window of the
    block ends at or before the token. q [B, T, H, Dh], k [B, T, Hkv, Dh]."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = d ** -0.5 if scale is None else scale
    ks, kk, bs = sel.kernel_stride, sel.kernel_size, sel.block_size
    nb = -(-t // bs)
    nw = (t - kk) // ks + 1 if t >= kk else 0
    if nw <= 0:
        return jnp.zeros((b, hkv, t, nb), jnp.float32)
    starts = jnp.arange(nw) * ks
    # (1) mean of each window's keys, in float32
    csum = jnp.cumsum(k.astype(jnp.float32), axis=1)
    csum = jnp.pad(csum, ((0, 0), (1, 0), (0, 0), (0, 0)))
    kc = (csum[:, starts + kk] - csum[:, starts]) / kk       # [B, NW, Hkv, D]
    kc = kc.astype(k.dtype)
    ends = starts + kk - 1                                   # [NW]
    # the windows that overlap block n are n r + lo0 .. n r + hi0
    if bs % ks:
        raise ValueError(f"block_size {bs} is no multiple of "
                         f"kernel_stride {ks}")
    r, lo0, hi0 = bs // ks, -((kk - 1) // ks), (bs - 1) // ks
    right = max(nb * r + hi0 - nw, 0)

    rows = min(rows, t)
    n_chunks = -(-t // rows)
    tp = n_chunks * rows
    qg = jnp.pad(q, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    qg = qg.reshape(b, n_chunks, rows, hkv, g, d)
    ids = jnp.arange(tp).reshape(n_chunks, rows)

    def chunk(args):
        qc, tok = args                       # [B, R, Hkv, G, D], [R]
        s = jnp.einsum("brhgd,bwhd->bhgrw", qc, kc, precision=_prec(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        seen = ends[None, :] <= tok[:, None]                 # [R, NW]
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)),
                      0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        p = jnp.sum(p, axis=2)                               # (2) [B,Hkv,R,NW]
        p = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (-lo0, right)))
        return functools.reduce(jnp.maximum, [                # (3)
            p[..., o - lo0:o - lo0 + nb * r:r]
            for o in range(lo0, hi0 + 1)])

    out = jax.lax.map(chunk, (jnp.moveaxis(qg, 1, 0), ids))  # [C,B,Hkv,R,NB]
    return jnp.moveaxis(out, 0, 2).reshape(b, hkv, tp, nb)[:, :, :t]


def select_blocks(q, k, sel: BlockSelection, scale=None):
    """Steps (1) to (4): the mask `[B, Hkv, T, NB]` (bool) of the blocks
    each token reads for each KV group. No gradient flows through it."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    t = q.shape[1]
    bs = sel.block_size
    nb = -(-t // bs)
    score = block_scores(q, k, sel, scale)
    tok = jnp.arange(t)[:, None]
    blk = jnp.arange(nb)[None, :]
    own = tok // bs
    reachable = blk <= own
    forced = reachable & ((blk < sel.init_blocks)
                          | (blk >= jnp.maximum(tok - sel.window_size + 1, 0)
                             // bs))
    ranked = jnp.where(forced, jnp.inf, jnp.where(reachable, score, -jnp.inf))
    kth = min(sel.topk, nb)
    vals, idxs = jax.lax.top_k(ranked, kth)
    # the worst pair kept; of equal scores the lower block comes first
    last_v, last_i = vals[..., -1:], idxs[..., -1:]
    keep = (ranked > last_v) | ((ranked == last_v) & (blk <= last_i))
    return keep & reachable


def causal_visits(batch: int, kv_heads: int, t: int, block_size: int) -> int:
    """Block visits of a dense causal layer: every token and KV group
    reads the blocks up to its own."""
    full, rest = divmod(t, block_size)
    per_sequence = block_size * full * (full + 1) // 2 + rest * (full + 1)
    return batch * kv_heads * per_sequence


def selection_counts(allow, block_size: int):
    """(kept, causal): the block visits the mask keeps, and those a dense
    causal layer would make, over all tokens and KV groups (int32)."""
    b, hkv, t, _ = allow.shape
    return (jnp.sum(allow, dtype=jnp.int32),
            jnp.asarray(causal_visits(b, hkv, t, block_size), jnp.int32))


def masked_attention(q, k, v, allow, block_size: int, scale=None):
    """(5) by a dense masked softmax: the oracle of the kernels, and the
    path of shapes the kernels do not tile (it makes [T, T] scores)."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    keys = jnp.repeat(allow, block_size, axis=-1)[..., :t]   # [B,Hkv,T,T]
    keys = keys & (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])
    qg = q.reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=_prec(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keys[:, :, None], s, _NEG_INF)
    w = jnp.where(keys[:, :, None], jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v,
                   precision=_prec(q.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, h, d).astype(q.dtype)


# ------------------------------------------------------------------ kernels
def sparse_eligible(t: int, block_size: int, block_q: int,
                    block_k: int) -> bool:
    """The shapes the kernels tile: whole tiles, a K tile made of whole
    blocks (a power of two wide) that all lie in one 128-lane slice of the
    mask."""
    bpt = block_k // max(block_size, 1)
    return (t % block_q == 0 and t % block_k == 0 and block_q % 8 == 0
            and block_k % _LANES == 0 and block_size & (block_size - 1) == 0
            and block_k % block_size == 0 and _LANES % bpt == 0)


def _tile_mask(a, qb, kb, bq, bk, block_size, key_major: bool = False):
    """[bq, bk] bool ([bk, bq] key-major): key c of K tile `kb` is visible
    to row r of Q tile `qb` iff the row lists the key's block and the key
    is not ahead of it. `a` is the rows' [bq, 128] slice of the mask (0/1
    in the kernel's dtype), in which this tile's blocks start at lane
    `(kb * bpt) % 128`."""
    bpt = bk // block_size
    shift = block_size.bit_length() - 1
    off = (kb * bpt) % _LANES
    # spread[lane, c] (key-major: [c, lane]): lane holds key c's block
    spread_shape = (bk, _LANES) if key_major else (_LANES, bk)
    lane = jax.lax.broadcasted_iota(jnp.int32, spread_shape, int(key_major))
    col = jax.lax.broadcasted_iota(jnp.int32, spread_shape,
                                   int(not key_major))
    spread = (lane == off + jnp.right_shift(col, shift)).astype(a.dtype)
    if key_major:
        listed = jax.lax.dot_general(spread, a, _NT,
                                     preferred_element_type=jnp.float32)
    else:
        listed = jnp.dot(a, spread, preferred_element_type=jnp.float32)
    return (listed > 0.5) & _causal_mask(qb, kb, bq, bk, key_major)


def _fwd_kernel(visit_ref, fetch_ref, q_ref, k_ref, v_ref, a_ref, o_ref,
                lse_ref, acc_scr, m_scr, l_scr, *, scale, g, nq, nk,
                block_size):
    b, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    flat = ((b // g) * nq + qb) * nk + kb
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        _softmax_init(acc_scr, m_scr, l_scr)

    @pl.when(visit_ref[flat] > 0)
    def _():
        q = q_ref[0]
        prec = _prec(q.dtype)
        s = jnp.dot(q, k_ref[0].T, preferred_element_type=jnp.float32,
                    precision=prec) * scale
        # the mask and not only a bias: a row may list nothing in this
        # tile, or nothing yet (`ops/attention._softmax_update`)
        _softmax_update(s, _tile_mask(a_ref[0], qb, kb, bq, bk, block_size),
                        v_ref[0], acc_scr, m_scr, l_scr, prec)

    @pl.when(kb == nk - 1)
    def _():
        o, lse = _softmax_finish(acc_scr, m_scr, l_scr, bk)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0] = lse


def _bwd_dq_kernel(visit_ref, fetch_ref, q_ref, k_ref, v_ref, a_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr, *, scale, g, nq, nk,
                   block_size):
    b, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    flat = ((b // g) * nq + qb) * nk + kb
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    # no tile is interior: which blocks a row lists is data
    _on_tiles(lambda masked: _dq_step(
        dq_scr, q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
        delta_ref[0], _tile_mask(a_ref[0], qb, kb, bq, bk, block_size),
        scale), visit_ref[flat] > 0)

    @pl.when(kb == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkdv_kernel(visit_ref, fetch_ref, q_ref, k_ref, v_ref, a_ref,
                     do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                     dv_scr, *, scale, nq, nk, block_size):
    """Grid (batch x KV heads, K tiles, group x Q tiles): the K tile's
    gradient gathers in scratch over the Q tiles of each of the group's
    query heads that list it, the tile key-major
    (`ops/attention._dkdv_step`)."""
    b, kb, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qb = step % nq
    flat = (b * nq + qb) * nk + kb
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    _on_tiles(lambda masked: _dkdv_step(
        dk_scr, dv_scr, q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0, :1], delta_ref[0, :1],
        _tile_mask(a_ref[0], qb, kb, bq, bk, block_size, True), scale),
        visit_ref[flat] > 0)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _walk(allow, block_q: int, block_k: int, block_size: int):
    """What the kernels prefetch, flat int32 `[B Hkv, NQ, NK]` each:
    `visit` (does any token of Q tile i list a block of K tile j),
    `fetch_k` (j where visited, else the last visited j before it, so
    that an unvisited step fetches nothing new) and `fetch_q` (the same
    along i for the dK/dV kernel, whose leading dead steps wait on the
    first live tile)."""
    bh, t, _ = allow.shape
    nq, nk, bpt = t // block_q, t // block_k, block_k // block_size
    tiles = allow.reshape(bh, nq, block_q, nk, bpt)
    visit = jnp.any(tiles, axis=(2, 4))                      # [BH, NQ, NK]
    j = jnp.arange(nk, dtype=jnp.int32)[None, None, :]
    fetch_k = jnp.maximum(jax.lax.cummax(jnp.where(visit, j, -1), axis=2), 0)
    i = jnp.arange(nq, dtype=jnp.int32)[None, :, None]
    seen = jax.lax.cummax(jnp.where(visit, i, -1), axis=1)
    first = jnp.argmax(visit, axis=1).astype(jnp.int32)[:, None, :]
    fetch_q = jnp.where(seen < 0, first, seen)
    flat = lambda a: a.astype(jnp.int32).reshape(-1)
    return flat(visit), flat(fetch_k), flat(fetch_q)


def _mask_operand(allow, dtype):
    """The mask as the kernels read it: `[B Hkv, T, NB]` 0/1 in the
    kernel's dtype, its last axis padded to whole 128-lane slices."""
    b, hkv, t, nb = allow.shape
    a = allow.reshape(b * hkv, t, nb).astype(dtype)
    return jnp.pad(a, ((0, 0), (0, 0), (0, -nb % _LANES)))


def _specs(d, g, nq, nk, block_q, block_k, block_size):
    """Block specs of the forward and dQ kernels' grid (batch x heads, Q
    tiles, K tiles), whose index maps read the prefetched fetch indices:
    Q tile, K/V tile, the rows' 128-lane slice of the mask, row
    statistics."""
    bpt = block_k // block_size

    def at(b, i, j, fetch):
        return fetch[((b // g) * nq + i) * nk + j]

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j, v, f: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda b, i, j, v, f: (b // g, at(b, i, j, f), 0))
    a_spec = pl.BlockSpec(
        (1, block_q, _LANES),
        lambda b, i, j, v, f: (b // g, i, at(b, i, j, f) * bpt // _LANES))
    row_spec = pl.BlockSpec((1, block_q, _LSE_LANES),
                            lambda b, i, j, v, f: (b, i, 0))
    return q_spec, kv_spec, a_spec, row_spec


def _run_fwd(q3, k3, v3, a3, walk, *, scale, block_size, block_q, block_k,
             interpret):
    bh, t, d = q3.shape
    g = _group(q3, k3)
    nq, nk = t // block_q, t // block_k
    visit, fetch_k, _ = walk
    q_spec, kv_spec, a_spec, row_spec = _specs(d, g, nq, nk, block_q,
                                               block_k, block_size)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, g=g, nq=nq, nk=nk,
                          block_size=block_size),
        name="sparse_attention_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, a_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=_softmax_scratch(block_q, d)),
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, t, _LSE_LANES), jnp.float32)],
        compiler_params=_tile_params(block_q, block_k, d, q3.dtype.itemsize),
        interpret=interpret,
    )(visit, fetch_k, q3, k3, v3, a3)
    return o, lse[..., 0]


def _run_bwd(q3, k3, v3, a3, walks, o3, lse, do3, *, scale, block_size,
             tiles, interpret):
    """dq, dk, dv from the residuals. `tiles` is `_kernel_tiles`'s, each
    kernel's own tile, and `walks` the dQ and the dK/dV kernel's walk,
    each made for its tile."""
    bh, t, d = q3.shape
    g = _group(q3, k3)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    params = functools.partial(_tile_params, d=d,
                               itemsize=q3.dtype.itemsize, backward=True)

    (block_q, block_k), (visit, fetch_k, _) = tiles["dq"], walks[0]
    nq, nk = t // block_q, t // block_k
    q_spec, kv_spec, a_spec, row_spec = _specs(d, g, nq, nk, block_q,
                                               block_k, block_size)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, g=g, nq=nq, nk=nk,
                          block_size=block_size),
        name="sparse_attention_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, a_spec, q_spec, row_spec,
                      row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
        compiler_params=params(block_q, block_k), interpret=interpret,
    )(visit, fetch_k, q3, k3, v3, a3, do3, _stat_lanes(lse),
      _stat_lanes(delta))

    # dK/dV: b runs over KV heads; step s is Q tile s % nq of the group's
    # query head s // nq
    (block_q, block_k), (visit, _, fetch_q) = tiles["dkdv"], walks[1]
    nq, nk, bpt = t // block_q, t // block_k, block_k // block_size

    def qat(b, j, s, f):
        return f[(b * nq + s % nq) * nk + j]

    q_spec_t = pl.BlockSpec(
        (1, block_q, d),
        lambda b, j, s, v, f: (b * g + s // nq, qat(b, j, s, f), 0))
    row_spec_t = pl.BlockSpec(
        (1, _STAT_ROWS, block_q),
        lambda b, j, s, v, f: (b * g + s // nq, 0, qat(b, j, s, f)))
    kv_spec_t = pl.BlockSpec((1, block_k, d), lambda b, j, s, v, f: (b, j, 0))
    a_spec_t = pl.BlockSpec(
        (1, block_q, _LANES),
        lambda b, j, s, v, f: (b, qat(b, j, s, f), j * bpt // _LANES))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, nq=nq, nk=nk,
                          block_size=block_size),
        name="sparse_attention_bwd_dkdv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh // g, nk, g * nq),
            in_specs=[q_spec_t, kv_spec_t, kv_spec_t, a_spec_t, q_spec_t,
                      row_spec_t, row_spec_t],
            out_specs=[kv_spec_t, kv_spec_t],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        compiler_params=params(block_q, block_k), interpret=interpret,
    )(visit, fetch_q, q3, k3, v3, a3, do3, _stat_rows(lse),
      _stat_rows(delta))
    return dq, dk, dv


def _kernel_tiles(t: int, heads: int, block_size: int, block_q: int,
                  block_k: int, interpret: bool) -> dict:
    """{"fwd" | "dq" | "dkdv": (block_q, block_k)}: each kernel's own
    tile (`ops/attention._pick_tile`). The K tile stays the caller's: it
    is the unit the walk skips by, and what a selection lists is data.
    The Q tile may grow, priced over the causal triangle, the most a walk
    can visit; its walk is then the union of more tokens' lists, so a
    backward kernel's grows no taller than the forward's (which its
    score tile's size stops first) and the three share one walk. The
    backward's gauge `attention_bwd_steps` counts the causal triangle as
    its edge steps: no tile is interior, and how many a walk skips is
    data."""
    out = {}
    for kernel in ("fwd", "dq", "dkdv"):
        tallest = out["fwd"][0] if out else t
        out[kernel] = tile = _pick_tile(
            "sparse_attention", kernel, block_q, block_k,
            interpret=interpret,
            legal=lambda bq, bk: (bk == block_k and bq <= tallest
                                  and sparse_eligible(t, block_size, bq, bk)),
            tiles=lambda bq, bk: _causal_tiles(t, bq, bk),
            steps=None if kernel == "fwd" else (
                lambda bq, bk: (t // bq) * (t // bk)))
        if kernel != "fwd":
            _publish_bwd_steps(
                "sparse_attention", kernel, heads,
                (t // tile[0]) * (t // tile[1]), _causal_tiles(t, *tile), 0)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def block_sparse_attention(q, k, v, allow, block_size: int,
                           scale=None, block_q: int = 256,
                           block_k: int = 512, interpret: bool = False):
    """(5): q [B, T, H, Dh], k and v [B, T, Hkv, Dh], `allow`
    [B, Hkv, T, T // block_size] bool (what `select_blocks` returns; it
    gets no cotangent). Returns o [B, T, H, Dh]. The shapes have to be
    `sparse_eligible`."""
    return _sparse_fwd(q, k, v, allow, block_size, scale, block_q, block_k,
                       interpret)[0]


def _sparse_fwd(q, k, v, allow, block_size, scale, block_q, block_k,
                interpret):
    t = q.shape[1]
    block_q, block_k = min(block_q, t), min(block_k, t)
    if not sparse_eligible(t, block_size, block_q, block_k):
        raise ValueError(
            f"block-sparse attention cannot tile T={t} with blocks of "
            f"{block_size} keys in tiles of {block_q} x {block_k}")
    s = scale if scale is not None else q.shape[-1] ** -0.5
    q3, shape_q = _fold3(q)
    k3, shape_k = _fold3(k)
    v3, _ = _fold3(v)
    a3 = _mask_operand(allow, q.dtype)
    allow3 = allow.reshape(a3.shape[0], t, -1)
    # one walk a distinct tile; the backward kernels' ride as residuals
    tiles = _kernel_tiles(t, q3.shape[0], block_size, block_q, block_k,
                          interpret)
    made = {tile: _walk(allow3, *tile, block_size)
            for tile in set(tiles.values())}
    o3, lse = _run_fwd(q3, k3, v3, a3, made[tiles["fwd"]], scale=s,
                       block_size=block_size, block_q=tiles["fwd"][0],
                       block_k=tiles["fwd"][1], interpret=interpret)
    o3, lse = name_residuals(o3, lse)
    walks = made[tiles["dq"]], made[tiles["dkdv"]]
    return (_unfold3(o3, shape_q),
            (q3, k3, v3, a3, walks, o3, lse, shape_q, shape_k))


def _sparse_bwd(block_size, scale, block_q, block_k, interpret, res, do):
    q3, k3, v3, a3, walks, o3, lse, shape_q, shape_k = res
    bh, t, _ = q3.shape
    s = scale if scale is not None else q3.shape[-1] ** -0.5
    do3, _ = _fold3(do)
    tiles = _kernel_tiles(t, bh, block_size, min(block_q, t),
                          min(block_k, t), interpret)
    dq, dk, dv = _run_bwd(q3, k3, v3, a3, walks, o3, lse, do3, scale=s,
                          block_size=block_size, tiles=tiles,
                          interpret=interpret)
    return (_unfold3(dq, shape_q), _unfold3(dk, shape_k),
            _unfold3(dv, shape_k), None)


block_sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)
