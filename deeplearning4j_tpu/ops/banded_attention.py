"""Banded flash attention (Pallas TPU): sliding-window + GQA + ring decode.

The flash kernel in `ops/attention.py` is a full-context kernel: its grid
sweeps every K block for every Q block, so a sliding-window layer gains
nothing from it and `nn/layers/attention.py` historically forced window/
GQA/ring shapes onto the O(T²) dense band-masked path — the exact shapes
the decode serving stack runs on. This module closes that gap with one
kernel family:

* `banded_attention` — full-sequence forward whose GRID is banded: for
  each Q block only the `nkb` K blocks that can intersect the band are
  visited (`nkb` is a constant in T, derived from window/block sizes), so
  compile-time FLOPs scale with T·w, not T². GQA is native: the K/V tiles
  stay Hkv-wide while the query tile carries the whole `G = H/Hkv` group
  (`[1, G, Bq, Dh]` folded to `(G·Bq, Dh)` rows against one `[Bk, Dh]`
  KV tile), so KV HBM traffic really is Hkv/H of MHA — the cache is never
  broadcast to H heads the way the layer's dense GQA path must.
* `banded_decode_attention` — the single-query serving variant. It reads
  the `KVSlotPool` carry layout `[S, L, Hkv, Dh]` directly and evaluates
  the rolling-ring held-index arithmetic (`held = end - ((end - j) % L)`,
  see `nn/layers/attention.py` scalar-ring branch) inside the kernel from
  scalar-prefetched per-slot positions, so one compiled program serves
  every session position — the zero-recompile decode contract holds.

The forward's online-softmax step is `ops/attention._softmax_update`, the
one the flash and block-sparse forwards make (statistics a lane wide, the
normalizer summed across lanes once a Q tile), and its tile its own
(`ops/attention._pick_tile`): at 48 query heads over 8 KV heads of 128,
8,192 tokens and a window of 4,096 the six-wide group's 256 tokens
(1,536 rows) against 512 keys, 5.3 ms a call on a v5e where Q 256 x K 256
with `[rows, 1]` statistics took 14.7 (PR 36, PERF.md section 6); its
grid's K extent is the most blocks a Q block really meets. The decode
kernels keep their own single-query loops.

Both kernels run under `interpret=True` on CPU (the parity suite in
tests/test_banded_attention.py pins them against the layer's dense
band-masked oracle). Backward: blockwise over the band's tiles only, as
`ops/attention.py`'s is over all of them. The forward rule saves the
per-row log-sum-exp; a dQ kernel sweeps each Q block's K blocks and a
dK/dV kernel each K block's Q blocks, scores recomputed a tile at a time
by the one backward tile function of the three families
(`ops/attention._dq_step`, `_dkdv_step`: dK/dV key-major, so that nothing
is transposed a tile), the whole GQA group's rows folded against one
Hkv-wide KV tile so dK/dV sum over the group in the matmul itself.
Nothing of size [T, T] exists in either direction. Each backward kernel
picks its own tile from the policy's blocks (`_pick_tile`), its grid's
inner extent is the most tiles any pinned block really meets, and a
tile wholly inside the band builds no mask (`_tile_interior`): at the
shapes above both take 1,536 rows x 512 keys, where seven of a Q
block's nine or ten tiles are interior, dQ 7.7 and dK/dV 8.9 ms a call
on a v5e where 768 x 256 with the tile transposed twice a step took 9.9
and 16.5 (PR 39, PERF.md section 6; host clock). `banded_reference`
stays as the oracle. The forward rule names the kernel's output and that
log-sum-exp `attention_out` and `attention_lse`
(`ops/attention.name_residuals`), so that a checkpointed layer keeps
them and its recomputed forward does not run the kernel a second time.

Dispatch is NOT decided here: `kernel_defaults.banded_policy` owns the
banded-vs-dense verdict (banded where the dense scores are a memory
hazard; env hatch `DL4J_TPU_ATTN=banded` forces it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention import (
    _LSE_LANES, _NEG_INF, _STAT_ROWS, _dkdv_step, _dq_step,
    _on_tiles, _pick_tile, _prec, _publish_bwd_steps, _softmax_finish,
    _softmax_init, _softmax_scratch, _softmax_update, _stat_lanes,
    _stat_rows, _tile_params, name_residuals,
)


# --------------------------------------------------------------- reference
def banded_reference(q, k, v, window: int, causal: bool, scale: float):
    """Dense band-masked oracle over native GQA layouts: q [B, T, H, Dh],
    k/v [B, T, Hkv, Dh]. Numerically the layer's `_masked_attention` band
    path (score-level -1e30 bias, f32 softmax); also the recompute
    backward for `banded_attention`."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, t, hkv, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * scale
    qi = jnp.arange(t)[:, None]
    ki = jnp.arange(t)[None, :]
    if causal:
        vis = (ki <= qi) & (ki > qi - window)
    else:
        vis = jnp.abs(qi - ki) < window
    s = jnp.where(vis[None, None, None], s, _NEG_INF)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, h, dh)


def _fit_block(block: int, t: int, *, interpret: bool) -> int:
    """Largest block <= requested that divides t. On TPU blocks walk down
    in 128-lane steps (Mosaic tiling); in interpret mode any divisor is
    legal, which is what lets the parity suite cover odd T/w shapes."""
    block = max(1, min(block, t))
    if interpret:
        while t % block:
            block -= 1
        return block
    while block > 128 and t % block:
        block -= 128
    if t % block:
        raise ValueError(f"seq len {t} not divisible by any block <= "
                         f"{block} (need a multiple of 128)")
    return block


def _kb_first(i, *, nk: int, nkb: int, block_q: int, block_k: int,
              window: int, causal: bool):
    """First K block visited for Q block `i` (shared by the BlockSpec
    index_map and the in-kernel mask arithmetic, so they can never
    disagree). The last needed block is `ub` = the block holding the
    band's rightmost visible key for the block's last row; the window of
    `nkb` blocks ending there always covers the leftmost too (`nkb` is
    the most K blocks any Q block meets, `_live_blocks`: a function of
    the window and the block sizes, not of T, which is the T·w
    contract)."""
    hi = (i + 1) * block_q - 1 + (0 if causal else window - 1)
    ub = jnp.minimum(hi // block_k, nk - 1)
    return jnp.clip(ub - (nkb - 1), 0, nk - nkb)


def _banded_kernel(q_ref, k_ref, v_ref, o_ref, *rest, nk: int, window: int,
                   causal: bool, scale: float, with_lse: bool):
    """Grid = (batch·Hkv, Q blocks, band K blocks). Per Q block only the
    `nkb` K blocks the band can touch are visited; the online-softmax
    state rides VMEM scratch across that innermost sweep and is updated
    once a K block by `ops/attention._softmax_update`, as in
    `_flash_kernel`. The query tile is the whole GQA group ([G, Bq, Dh]
    folded to G·Bq rows) against one Hkv-wide KV tile. With `with_lse`
    the per-row log-sum-exp is emitted too, the residual the blockwise
    backward recomputes score tiles from."""
    if with_lse:
        lse_ref, acc_scr, m_scr, l_scr = rest
    else:
        acc_scr, m_scr, l_scr = rest
    i = pl.program_id(1)
    j = pl.program_id(2)
    nkb = pl.num_programs(2)
    q = q_ref[0]                                   # [G, Bq, Dh]
    g, bq, d = q.shape
    block_k = k_ref.shape[1]
    kb = _kb_first(i, nk=nk, nkb=nkb, block_q=bq, block_k=block_k,
                   window=window, causal=causal) + j

    @pl.when(j == 0)
    def _():
        _softmax_init(acc_scr, m_scr, l_scr)

    # A clamped band (first/last rows of the sequence) can hand this step
    # a K block fully outside the visible interval — skip its FLOPs.
    @pl.when(_tile_live(i, kb, bq, block_k, window, causal))
    def _():
        prec = _prec(q.dtype)
        s = jnp.dot(q.reshape(g * bq, d), k_ref[0].T,
                    preferred_element_type=jnp.float32,
                    precision=prec) * scale        # [G·Bq, Bk]
        # the mask and not only a bias: a row whose band has not started
        # in this block is all masked (`_softmax_update`)
        _softmax_update(s, _visible(i, kb, g, bq, block_k, window, causal),
                        v_ref[0], acc_scr, m_scr, l_scr, prec)

    @pl.when(j == nkb - 1)
    def _():
        o, lse = _softmax_finish(acc_scr, m_scr, l_scr, block_k)
        o_ref[0] = o.reshape(g, bq, d).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = lse.reshape(g, bq, _LSE_LANES)


def _visible(qb, kb, g: int, bq: int, block_k: int, window: int,
             causal: bool, key_major: bool = False):
    """[G·Bq, Bk] mask of the band inside the tile (Q block `qb`, the
    group's rows folded, against K block `kb`), or [Bk, G·Bq] key-major:
    one arithmetic for the forward and both backward kernels."""
    shape = (block_k, g * bq) if key_major else (g * bq, block_k)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, int(key_major))
    q_ids = qb * bq + rows % bq                    # row r of group g -> q
    k_ids = (kb * block_k
             + jax.lax.broadcasted_iota(jnp.int32, shape, int(not key_major)))
    if causal:
        return (k_ids <= q_ids) & (k_ids > q_ids - window)
    return (k_ids < q_ids + window) & (k_ids > q_ids - window)


def _fold_heads(q, k, v):
    """[B, T, H, Dh] -> [B·Hkv, G, T, Dh] and [B, T, Hkv, Dh] -> [B·Hkv,
    T, Dh]; heads group as h = hkv·G + g, matching the layer's
    `q.reshape(B, T, Hkv, G, Dh)` GQA grouping."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hkv, t, dh)
    return (q.transpose(0, 2, 1, 3).reshape(b * hkv, h // hkv, t, dh),
            fold(k), fold(v))


def _unfold_q(x, b: int):
    """[B·Hkv, G, T, Dh] -> [B, T, H, Dh]."""
    bh, g, t, dh = x.shape
    return x.reshape(b, bh // b, g, t, dh).transpose(0, 3, 1, 2, 4) \
        .reshape(b, t, bh // b * g, dh)


def _unfold_kv(x, b: int):
    """[B·Hkv, T, Dh] -> [B, T, Hkv, Dh]."""
    bh, t, dh = x.shape
    return x.reshape(b, bh // b, t, dh).transpose(0, 2, 1, 3)


def _live_blocks(i: int, block_q: int, block_k: int, t: int, window: int,
                 causal: bool) -> int:
    """How many K blocks hold a key that a row of Q block `i` sees."""
    lo = max(i * block_q - window + 1, 0)
    hi = min((i + 1) * block_q - 1 + (0 if causal else window - 1), t - 1)
    return hi // block_k - lo // block_k + 1


def _run_banded(q5, k3, v3, *, window: int, causal: bool, scale: float,
                block_q: int, block_k: int, interpret: bool,
                with_lse: bool = False):
    """The forward over folded heads (`_fold_heads`): o [B·Hkv, G, T, Dh]
    and, with `with_lse`, the rows' log-sum-exp [B·Hkv, G, T]."""
    bh, g, t, dh = q5.shape

    def live(bq, bk):
        return [_live_blocks(i, bq, bk, t, window, causal)
                for i in range(t // bq)]

    block_q, block_k = _pick_tile(
        "banded_attention", "fwd",
        _fit_block(block_q, t, interpret=interpret),
        _fit_block(block_k, t, interpret=interpret), fold=g,
        interpret=interpret,
        legal=lambda bq, bk: t % bq == 0 and t % bk == 0,
        tiles=lambda bq, bk: sum(live(bq, bk)))
    # the grid's K extent is the most blocks any Q block really meets
    nk, nkb = t // block_k, max(live(block_q, block_k))
    kmap = functools.partial(_kb_first, nk=nk, nkb=nkb, block_q=block_q,
                             block_k=block_k, window=window, causal=causal)
    q_spec = pl.BlockSpec((1, g, block_q, dh), lambda bb, i, j: (bb, 0, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, dh),
                           lambda bb, i, j: (bb, kmap(i) + j, 0))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct(q5.shape, q5.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, g, block_q, _LSE_LANES),
                                      lambda bb, i, j: (bb, 0, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, g, t, _LSE_LANES),
                                              jnp.float32))
    out = pl.pallas_call(
        functools.partial(_banded_kernel, nk=nk, window=window,
                          causal=causal, scale=scale, with_lse=with_lse),
        name="banded_attention_fwd",
        grid=(bh, t // block_q, nkb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=_softmax_scratch(g * block_q, dh),
        compiler_params=_tile_params(g * block_q, block_k, dh,
                                     q5.dtype.itemsize),
        interpret=interpret,
    )(q5, k3, v3)
    if with_lse:
        return out[0], out[1][..., 0]      # one lane: O(T) between passes
    return out, None


# ----------------------------------------------------- blockwise backward
def _live_q_blocks(j: int, block_q: int, block_k: int, t: int, window: int,
                   causal: bool) -> int:
    """`_live_blocks` seen from K block `j`: how many Q blocks hold a row
    that sees one of its keys."""
    lo = max(j * block_k - (0 if causal else window - 1), 0)
    hi = min((j + 1) * block_k - 1 + window - 1, t - 1)
    return hi // block_q - lo // block_q + 1


def _qb_first(j, *, nq: int, nqb: int, block_q: int, block_k: int,
              window: int, causal: bool):
    """First Q block visited for K block `j`: the block of the first row
    that can see the K block's first key; the `nqb` blocks from there
    reach the last row that can see its last key."""
    lo = j * block_k - (0 if causal else window - 1)
    return jnp.clip(jnp.maximum(lo, 0) // block_q, 0, nq - nqb)


def _tile_live(qb, kb, bq: int, block_k: int, window: int, causal: bool):
    """Whether Q block `qb` and K block `kb` share any pair of the band."""
    lo = qb * bq - window + 1
    hi = (qb + 1) * bq - 1 + (0 if causal else window - 1)
    return (kb * block_k <= hi) & (kb * block_k + block_k - 1 >= lo)


def _tile_interior(qb, kb, bq: int, block_k: int, window: int, causal: bool):
    """Whether every pair of Q block `qb` and K block `kb` is in the band:
    its last key is visible to its first row, its first key to its last
    row. Such a tile builds no mask (`ops/attention._on_tiles`)."""
    first_row, last_row = qb * bq, (qb + 1) * bq - 1
    first_key, last_key = kb * block_k, (kb + 1) * block_k - 1
    return ((last_key <= first_row + (0 if causal else window - 1))
            & (first_key > last_row - window))


def _banded_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, dq_scr, *, nk: int, window: int,
                          causal: bool, scale: float):
    """Grid = (batch·Hkv, Q blocks, band K blocks), as the forward: the
    group's dQ tile accumulates in VMEM scratch across the band."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nkb = pl.num_programs(2)
    g, bq, d = q_ref.shape[1:]
    block_k = k_ref.shape[1]
    kb = _kb_first(i, nk=nk, nkb=nkb, block_q=bq, block_k=block_k,
                   window=window, causal=causal) + j
    at = (i, kb, bq, block_k, window, causal)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(masked):
        mask = (_visible(i, kb, g, bq, block_k, window, causal)
                if masked else None)
        _dq_step(dq_scr, q_ref[0].reshape(g * bq, d), k_ref[0], v_ref[0],
                 do_ref[0].reshape(g * bq, d),
                 lse_ref[0].reshape(g * bq, _LSE_LANES),
                 delta_ref[0].reshape(g * bq, _LSE_LANES), mask, scale)

    _on_tiles(tile, _tile_live(*at), _tile_interior(*at))

    @pl.when(j == nkb - 1)
    def _():
        dq_ref[0] = dq_scr[:].reshape(g, bq, d).astype(dq_ref.dtype)


def _banded_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, dk_scr, dv_scr, *, nq: int,
                            window: int, causal: bool, scale: float):
    """Grid = (batch·Hkv, K blocks, band Q blocks): the K/V tile's
    gradient accumulates in VMEM scratch across the Q blocks that can see
    it, summed over the GQA group by the folded rows' contraction. The
    tile is key-major (`ops/attention._dkdv_step`): the group's row
    statistics come as [G, 8, Bq] and are laid side by side, [1, G·Bq]."""
    j = pl.program_id(1)
    step = pl.program_id(2)
    nqb = pl.num_programs(2)
    g, bq, d = q_ref.shape[1:]
    block_k = k_ref.shape[1]
    qb = _qb_first(j, nq=nq, nqb=nqb, block_q=bq, block_k=block_k,
                   window=window, causal=causal) + step
    at = (qb, j, bq, block_k, window, causal)

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def stat(ref):
        return jnp.concatenate([ref[0, head, :1] for head in range(g)],
                               axis=1)

    def tile(masked):
        mask = (_visible(qb, j, g, bq, block_k, window, causal, True)
                if masked else None)
        _dkdv_step(dk_scr, dv_scr, q_ref[0].reshape(g * bq, d), k_ref[0],
                   v_ref[0], do_ref[0].reshape(g * bq, d), stat(lse_ref),
                   stat(delta_ref), mask, scale)

    _on_tiles(tile, _tile_live(*at), _tile_interior(*at))

    @pl.when(step == nqb - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _run_banded_bwd(q5, k3, v3, o5, lse, do5, *, window: int, causal: bool,
                    scale: float, block_q: int, block_k: int,
                    interpret: bool):
    """dq [B·Hkv, G, T, Dh], dk and dv [B·Hkv, T, Dh] from the O(T)
    residuals (q, k, v, o, L), over the band's tiles only. Each kernel
    takes its own tile from the passed blocks (`ops/attention._pick_tile`)
    and its grid's inner extent is the most tiles any block really meets."""
    bh, g, t, dh = q5.shape
    band = (t, window, causal)

    def live_k(bq, bk):
        return [_live_blocks(i, bq, bk, *band) for i in range(t // bq)]

    def live_q(bq, bk):
        return [_live_q_blocks(j, bq, bk, *band) for j in range(t // bk)]

    def pick(kernel, outer, live):
        """The kernel's tile, the extents of its grid's two last
        dimensions (`outer(bq, bk)` pinned blocks by the most live blocks
        one of them sweeps) and the geometry its index maps read."""
        bq, bk = _pick_tile(
            "banded_attention", kernel,
            _fit_block(block_q, t, interpret=interpret),
            _fit_block(block_k, t, interpret=interpret), fold=g,
            interpret=interpret,
            legal=lambda bq, bk: t % bq == 0 and t % bk == 0,
            tiles=lambda bq, bk: sum(live(bq, bk)),
            steps=lambda bq, bk: outer(bq, bk) * max(live(bq, bk)))
        swept = live(bq, bk)
        interior = sum(
            bool(_tile_interior(i, kb, bq, bk, window, causal))
            for i in range(t // bq) for kb in range(t // bk)
            if _tile_live(i, kb, bq, bk, window, causal))
        _publish_bwd_steps("banded_attention", kernel, bh,
                           outer(bq, bk) * max(swept), sum(swept), interior)
        return bq, bk, max(swept), dict(block_q=bq, block_k=bk,
                                        window=window, causal=causal)

    # Δ = rowsum(do · o): one fused elementwise and reduce in XLA
    delta = jnp.sum(do5.astype(jnp.float32) * o5.astype(jnp.float32),
                    axis=-1)
    kernel_args = dict(window=window, causal=causal, scale=scale)
    params = functools.partial(_tile_params, d=dh,
                               itemsize=q5.dtype.itemsize, backward=True)

    bq, bk, nkb, geometry = pick("dq", lambda bq, bk: t // bq, live_k)
    nk = t // bk
    kmap = functools.partial(_kb_first, nk=nk, nkb=nkb, **geometry)
    q_spec = pl.BlockSpec((1, g, bq, dh), lambda bb, i, j: (bb, 0, i, 0))
    row_spec = pl.BlockSpec((1, g, bq, _LSE_LANES),
                            lambda bb, i, j: (bb, 0, i, 0))
    kv_spec = pl.BlockSpec((1, bk, dh), lambda bb, i, j: (bb, kmap(i) + j, 0))
    dq = pl.pallas_call(
        functools.partial(_banded_bwd_dq_kernel, nk=nk, **kernel_args),
        name="banded_attention_bwd_dq",
        grid=(bh, t // bq, nkb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q5.shape, q5.dtype),
        scratch_shapes=[pltpu.VMEM((g * bq, dh), jnp.float32)],
        compiler_params=params(g * bq, bk),
        interpret=interpret,
    )(q5, k3, v3, do5, _stat_lanes(lse), _stat_lanes(delta))

    bq, bk, nqb, geometry = pick("dkdv", lambda bq, bk: t // bk, live_q)
    nq = t // bq
    qmap = functools.partial(_qb_first, nq=nq, nqb=nqb, **geometry)
    q_spec = pl.BlockSpec((1, g, bq, dh),
                          lambda bb, j, i: (bb, 0, qmap(j) + i, 0))
    row_spec = pl.BlockSpec((1, g, _STAT_ROWS, bq),
                            lambda bb, j, i: (bb, 0, 0, qmap(j) + i))
    kv_spec = pl.BlockSpec((1, bk, dh), lambda bb, j, i: (bb, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_banded_bwd_dkdv_kernel, nq=nq, **kernel_args),
        name="banded_attention_bwd_dkdv",
        grid=(bh, t // bk, nqb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=params(g * bq, bk),
        interpret=interpret,
    )(q5, k3, v3, do5, _stat_rows(lse), _stat_rows(delta))
    return dq, dk, dv


def banded_eligible(t: int, h: int, hkv: int, *, min_t: int = 256,
                    any_backend: bool = False) -> bool:
    """SHAPE eligibility for the full-sequence banded kernel: TPU backend,
    128-lane-tileable T, and a clean GQA grouping. `min_t` is the perf
    floor (below it the band is most of the matrix and dense wins on
    launch overhead); the verdict lives in
    `kernel_defaults.banded_policy`. `any_backend=True` waives the TPU
    requirement (env-forced routing runs interpret-mode off-TPU — a
    production force must not silently un-force itself)."""
    return ((any_backend or jax.default_backend() == "tpu")
            and t % 128 == 0
            and t >= min_t and hkv >= 1 and h % hkv == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def banded_attention(q, k, v, window: int, causal: bool = True,
                     scale: Optional[float] = None, block_q: int = 256,
                     block_k: int = 256, interpret: bool = False):
    """Banded (sliding-window) self-attention, GQA-native.

    q: [B, T, H, Dh]; k/v: [B, T, Hkv, Dh] with Hkv dividing H (Hkv == H
    is plain MHA). Causal visibility is `q - window < k <= q`;
    bidirectional is `|q - k| < window` — exactly the layer's dense band
    semantics. Forward and backward are O(T·w) in compute, HBM traffic
    and memory by grid construction: the backward recomputes the band's
    score tiles from the rows' saved log-sum-exp."""
    s = scale if scale is not None else q.shape[-1] ** -0.5
    o, _ = _run_banded(*_fold_heads(q, k, v), window=window, causal=causal,
                       scale=s, block_q=block_q, block_k=block_k,
                       interpret=interpret)
    return _unfold_q(o, q.shape[0])


def _banded_fwd(q, k, v, window, causal, scale, block_q, block_k,
                interpret):
    s = scale if scale is not None else q.shape[-1] ** -0.5
    q5, k3, v3 = _fold_heads(q, k, v)
    o5, lse = _run_banded(q5, k3, v3, window=window, causal=causal, scale=s,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, with_lse=True)
    o5, lse = name_residuals(o5, lse)
    return _unfold_q(o5, q.shape[0]), (q5, k3, v3, o5, lse)


def _banded_bwd(window, causal, scale, block_q, block_k, interpret, res,
                do):
    q5, k3, v3, o5, lse = res
    b = do.shape[0]
    s = scale if scale is not None else q5.shape[-1] ** -0.5
    do5 = do.transpose(0, 2, 1, 3).reshape(q5.shape)
    dq, dk, dv = _run_banded_bwd(
        q5, k3, v3, o5, lse, do5, window=window, causal=causal, scale=s,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return _unfold_q(dq, b), _unfold_kv(dk, b), _unfold_kv(dv, b)


banded_attention.defvjp(_banded_fwd, _banded_bwd)


# ------------------------------------------------------- decode (serving)
def decode_reference(q, cache_k, cache_v, qpos, end, window: Optional[int],
                     rolling: bool, scale: float, scale_k=None,
                     scale_v=None):
    """Dense oracle for the single-query decode kernel, mirroring the
    layer's per-slot `_decode` visibility arithmetic over the pool layout
    (q [S, H, Dh], caches [S, L, Hkv, Dh], qpos/end [S] int32). Rows with
    an empty visible set are garbage-by-contract on BOTH paths (softmax
    of a constant here, zeros in the kernel) — inactive lanes, never
    read back. Quantized caches pass their [S, L, Hkv] scale rows and are
    dequantized up front (the kernel fuses the same product into its
    block loads)."""
    if scale_k is not None:
        cache_k = cache_k.astype(q.dtype) * scale_k.astype(q.dtype)[..., None]
        cache_v = cache_v.astype(q.dtype) * scale_v.astype(q.dtype)[..., None]
    s_, h, dh = q.shape
    l = cache_k.shape[1]
    hkv = cache_k.shape[2]
    g = h // hkv
    j = jnp.arange(l)[None, :]                               # [1, L]
    qp = qpos[:, None]
    if rolling:
        held = end[:, None] - ((end[:, None] - j) % l)       # [S, L]
        vis = (held >= 0) & (held <= qp) & (held > qp - window)
    else:
        vis = j <= qp
        if window is not None:
            vis = vis & (j > qp - window)
    qg = q.reshape(s_, hkv, g, dh)
    sc = jnp.einsum("shgd,slhd->shgl", qg, cache_k) * scale
    sc = jnp.where(vis[:, None, None], sc, _NEG_INF)
    o = jnp.einsum("shgl,slhd->shgd", jax.nn.softmax(sc, axis=-1), cache_v)
    return o.reshape(s_, h, dh)


def _decode_kernel(qpos_ref, end_ref, *refs, cache_len: int,
                   window: Optional[int], rolling: bool, hkv: int,
                   scale: float, quant: bool = False):
    """Grid = (slots, L blocks): one slot's [L, Hkv, Dh] cache rows sweep
    through VMEM while the single-token query group stays resident. The
    per-slot positions arrive scalar-prefetched (SMEM) so visibility is
    computed from traced scalars — one compiled program for every session
    position, which is what keeps the decode zero-recompile contract.

    `quant=True` adds two [1, Bl, Hkv] scale-row refs after the caches:
    the per-(token, kv-head) dequantization product happens on the VMEM
    block right after the load, so quantized KV pays the narrow HBM sweep
    and never materializes a full-width cache."""
    if quant:
        (q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
         acc_scr, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, l_scr = refs
        sk_ref = sv_ref = None
    si = pl.program_id(0)
    lb = pl.program_id(1)
    nlb = pl.num_programs(1)
    q = q_ref[0]                                   # [H, Dh]
    h, d = q.shape
    block_l = k_ref.shape[1]
    g = h // hkv
    pos = qpos_ref[si]
    end = end_ref[si]

    @pl.when(lb == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    if rolling:
        # Every ring slot can hold a live position (the ring IS the band
        # wrapped onto L slots) — no block is statically or dynamically
        # dead, so there is nothing to skip.
        relevant = lb >= 0
    else:
        # Linear cache: only blocks intersecting [pos-w+1, pos] live.
        relevant = lb * block_l <= pos
        if window is not None:
            relevant &= lb * block_l + block_l - 1 > pos - window

    @pl.when(relevant)
    def _():
        kc = k_ref[0]                              # [Bl, Hkv, Dh]
        vc = v_ref[0]
        if quant:
            # fused dequantize-on-load: widen the narrow block in VMEM
            kc = kc.astype(jnp.float32) * sk_ref[0][:, :, None]
            vc = vc.astype(jnp.float32) * sv_ref[0][:, :, None]
        prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        # GQA: each Hkv tile scores its G-row query group; Hkv is a
        # static python loop (tiny: 1-16), so the kernel stays one fused
        # program with no H-wide KV broadcast.
        s = jnp.concatenate([
            jnp.dot(q[hk * g:(hk + 1) * g], kc[:, hk, :].T,
                    preferred_element_type=jnp.float32,
                    precision=prec)
            for hk in range(hkv)], axis=0) * scale  # [H, Bl]
        j = (lb * block_l
             + jax.lax.broadcasted_iota(jnp.int32, (h, block_l), 1))
        if rolling:
            held = end - ((end - j) % cache_len)
            vis = (held >= 0) & (held <= pos) & (held > pos - window)
        else:
            vis = j <= pos
            if window is not None:
                vis = vis & (j > pos - window)
        s = jnp.where(vis, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(vis, jnp.exp(s - m_new), 0.0)   # dead-block guard
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[hk * g:(hk + 1) * g].astype(vc.dtype), vc[:, hk, :],
                    preferred_element_type=jnp.float32, precision=prec)
            for hk in range(hkv)], axis=0)            # [H, Dh]
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(lb == nlb - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def banded_decode_attention(q, cache_k, cache_v, qpos, end,
                            window: Optional[int] = None,
                            rolling: bool = False,
                            scale: Optional[float] = None,
                            block_l: int = 512,
                            interpret: bool = False,
                            scale_k=None, scale_v=None):
    """Single-query attention over the KVSlotPool layout.

    q: [S, H, Dh] (this step's query token per slot, post-RoPE);
    cache_k/cache_v: [S, L, Hkv, Dh] (post-write: this step's K/V already
    scattered in); qpos: [S] int32 global position of each slot's query;
    end: [S] int32 newest written global position per slot (rolling ring
    only; ignored otherwise — pass qpos). Returns [S, H, Dh].

    Visibility matches the layer's per-slot `_decode`: rolling recovers
    each ring slot's current occupant arithmetically
    (`held = end - ((end - j) % L)`, visible iff `0 <= held <= qpos` and
    `held > qpos - window`); linear caches see `j <= qpos` minus anything
    beyond the window. Inference-only (no vjp): the decode path never
    differentiates."""
    s_, h, dh = q.shape
    cache_len = cache_k.shape[1]
    hkv = cache_k.shape[2]
    if h % hkv:
        raise ValueError(f"H {h} not divisible by Hkv {hkv}")
    if rolling and window is None:
        raise ValueError("rolling decode requires a window")
    sc = scale if scale is not None else dh ** -0.5
    quant = scale_k is not None
    block_l = _fit_block(block_l, cache_len, interpret=interpret)
    qpos = qpos.astype(jnp.int32)
    end = end.astype(jnp.int32)
    in_specs = [
        pl.BlockSpec((1, h, dh), lambda si, lb, *refs: (si, 0, 0)),
        pl.BlockSpec((1, block_l, hkv, dh),
                     lambda si, lb, *refs: (si, lb, 0, 0)),
        pl.BlockSpec((1, block_l, hkv, dh),
                     lambda si, lb, *refs: (si, lb, 0, 0)),
    ]
    inputs = [q, cache_k, cache_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, block_l, hkv),
                         lambda si, lb, *refs: (si, lb, 0)),
            pl.BlockSpec((1, block_l, hkv),
                         lambda si, lb, *refs: (si, lb, 0)),
        ]
        inputs += [scale_k.astype(jnp.float32),
                   scale_v.astype(jnp.float32)]
    out_dtype = q.dtype
    return pl.pallas_call(
        functools.partial(_decode_kernel, cache_len=cache_len,
                          window=window, rolling=rolling, hkv=hkv,
                          scale=sc, quant=quant),
        name="banded_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_, cache_len // block_l),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, dh),
                                   lambda si, lb, *refs: (si, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, dh), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, h, dh), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qpos, end, *inputs)


def _paged_decode_kernel(qpos_ref, pt_ref, *refs, page_len: int,
                         window: Optional[int], hkv: int, scale: float,
                         quant: bool = False):
    """Paged twin of `_decode_kernel`: grid = (slots, NP logical pages),
    and the lb-th cache block is whatever PHYSICAL page the slot's
    scalar-prefetched page table maps logical page lb to — the BlockSpec
    index_map reads `pt_ref[si, lb]`, so block-scattered storage costs
    the kernel nothing (vLLM-style TPU paged attention). Visibility
    stays the linear `j <= pos` arithmetic over LOGICAL positions
    j = lb * page_len + offset. Unmapped tail entries of the table must
    still hold a valid physical index (the pool keeps them 0): their
    blocks DMA in, but the relevant-guard skips their math."""
    if quant:
        (q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref,
         acc_scr, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_scr, m_scr, l_scr = refs
        sk_ref = sv_ref = None
    si = pl.program_id(0)
    lb = pl.program_id(1)
    nlb = pl.num_programs(1)
    q = q_ref[0]                                   # [H, Dh]
    h, d = q.shape
    g = h // hkv
    pos = qpos_ref[si]

    @pl.when(lb == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # only logical pages intersecting [pos-w+1, pos] hold live keys
    relevant = lb * page_len <= pos
    if window is not None:
        relevant &= lb * page_len + page_len - 1 > pos - window

    @pl.when(relevant)
    def _():
        kc = k_ref[0]                              # [Lp, Hkv, Dh]
        vc = v_ref[0]
        if quant:
            # fused dequantize-on-load: widen the narrow block in VMEM
            kc = kc.astype(jnp.float32) * sk_ref[0][:, :, None]
            vc = vc.astype(jnp.float32) * sv_ref[0][:, :, None]
        prec = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        s = jnp.concatenate([
            jnp.dot(q[hk * g:(hk + 1) * g], kc[:, hk, :].T,
                    preferred_element_type=jnp.float32,
                    precision=prec)
            for hk in range(hkv)], axis=0) * scale  # [H, Lp]
        j = (lb * page_len
             + jax.lax.broadcasted_iota(jnp.int32, (h, page_len), 1))
        vis = j <= pos
        if window is not None:
            vis = vis & (j > pos - window)
        s = jnp.where(vis, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(vis, jnp.exp(s - m_new), 0.0)   # dead-block guard
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jnp.dot(p[hk * g:(hk + 1) * g].astype(vc.dtype), vc[:, hk, :],
                    preferred_element_type=jnp.float32, precision=prec)
            for hk in range(hkv)], axis=0)            # [H, Dh]
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(lb == nlb - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q, cache_k, cache_v, page_table, qpos,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False,
                           scale_k=None, scale_v=None):
    """Single-query attention over the PAGED KVSlotPool layout.

    q: [S, H, Dh]; cache_k/cache_v: [P, Lp, Hkv, Dh] — the shared
    physical page pool (post-write); page_table: [S, NP] int32 mapping
    each slot's logical pages to physical rows; qpos: [S] int32 logical
    position of each slot's query. Returns [S, H, Dh].

    The page table rides the scalar-prefetch lane next to the
    positions: Mosaic resolves each grid step's cache block address from
    `page_table[si, lb]` BEFORE the DMA, so sessions sharing a prompt
    prefix stream the SAME physical blocks and nothing is gathered into
    a per-slot logical copy. One compiled program serves every
    page-table content — page indices are data, not shape, the same
    zero-recompile discipline as slot ids. The block length IS the page
    length (pages are the unit of sharing and of tiling); quantized
    pools pass their [P, Lp, Hkv] scale rows for fused
    dequantize-on-load. Inference-only, non-rolling (the prefix cache
    never pages a rolling ring)."""
    s_, h, dh = q.shape
    page_len = cache_k.shape[1]
    hkv = cache_k.shape[2]
    npg = page_table.shape[1]
    if h % hkv:
        raise ValueError(f"H {h} not divisible by Hkv {hkv}")
    if not interpret and page_len % 128:
        raise ValueError(
            f"page_len {page_len} must be 128-lane tileable on TPU")
    sc = scale if scale is not None else dh ** -0.5
    quant = scale_k is not None
    qpos = qpos.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    in_specs = [
        pl.BlockSpec((1, h, dh), lambda si, lb, *refs: (si, 0, 0)),
        # the paged indirection: block lb of slot si is physical page
        # pt[si, lb] (refs = the scalar-prefetch operands, qpos then pt)
        pl.BlockSpec((1, page_len, hkv, dh),
                     lambda si, lb, qpos_ref, pt_ref: (pt_ref[si, lb],
                                                       0, 0, 0)),
        pl.BlockSpec((1, page_len, hkv, dh),
                     lambda si, lb, qpos_ref, pt_ref: (pt_ref[si, lb],
                                                       0, 0, 0)),
    ]
    inputs = [q, cache_k, cache_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, page_len, hkv),
                         lambda si, lb, qpos_ref, pt_ref: (pt_ref[si, lb],
                                                           0, 0)),
            pl.BlockSpec((1, page_len, hkv),
                         lambda si, lb, qpos_ref, pt_ref: (pt_ref[si, lb],
                                                           0, 0)),
        ]
        inputs += [scale_k.astype(jnp.float32),
                   scale_v.astype(jnp.float32)]
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_len=page_len,
                          window=window, hkv=hkv, scale=sc, quant=quant),
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_, npg),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, dh),
                                   lambda si, lb, *refs: (si, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, dh), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qpos, page_table, *inputs)
