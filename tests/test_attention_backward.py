"""The one backward tile (`ops/attention._bwd_scores`, `_dq_step`,
`_dkdv_step`) through each of the six kernels that call it, in interpret
mode against `jax.vjp` of the family's float32 oracle: dq, dk and dv,
float32 and bf16, at shapes that reach each branch (`_on_tiles`: an
interior tile that builds no mask, an edge tile, a dead step) and each
layout (a folded group, a two-sided band, Tq != Tk, a log-sum-exp with a
cotangent of its own). In interpret mode the passed blocks are the tile;
the tile each backward kernel picks for itself on the chip (`_pick_tile`)
is arithmetic, checked at the cells' shapes at the end.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.observe import get_registry
from test_attention_forward import _listing   # who lists which block

flash = importlib.import_module("deeplearning4j_tpu.ops.attention")
banded = importlib.import_module("deeplearning4j_tpu.ops.banded_attention")
sparse = importlib.import_module("deeplearning4j_tpu.ops.sparse_attention")

HI = jax.lax.Precision.HIGHEST
# against the largest entry of the oracle's gradient: float32 products at
# HIGHEST; bf16 rounds p, ds and the result (2**-8 each)
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
DTYPES = [jnp.float32, jnp.bfloat16]
KERNELS = ("dq", "dkdv")


def _inputs(seed, b, tq, tk, h, hkv, d, dtype):
    """q, k, v and the output's cotangent, rounded to `dtype`."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = [(b, tq, h, d), (b, tk, hkv, d), (b, tk, hkv, d), (b, tq, h, d)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(ks, shapes)]


def _oracle(q, k, v, vis):
    """(o [B, Tq, H, D], lse [B, H, Tq]) of softmax over the pairs `vis`
    [B or 1, Hkv or 1, Tq, Tk] has, in float32."""
    b, tq, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, tq, hkv, h // hkv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HI) * d ** -0.5
    s = jnp.where(vis[:, :, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)                       # [B,Hkv,G,Tq]
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jnp.exp(s - lse[..., None]), v,
                   precision=HI)
    return o.reshape(b, tq, h, d), lse.reshape(b, h, tq)


def _oracle_grads(q, k, v, do, vis, dlse=None):
    """dq, dk, dv of the oracle from the inputs as they are rounded, for
    the cotangent `do` of o (and `dlse` of the log-sum-exp)."""
    f32 = lambda x: x.astype(jnp.float32)
    (o, lse), vjp = jax.vjp(lambda q, k, v: _oracle(q, k, v, vis),
                            f32(q), f32(k), f32(v))
    return vjp((f32(do), jnp.zeros_like(lse) if dlse is None
                else f32(dlse)))


def _check(got, want, dtype):
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), w, rtol=TOL[dtype],
            atol=TOL[dtype] * float(np.abs(w).max()), err_msg=name)


def _tile(op, kernel):
    gauge = lambda field: int(get_registry().gauge(
        "attention_bwd_tile", op=op, kernel=kernel, field=field).value)
    return gauge("rows"), gauge("keys")


def _steps(op, kernel):
    return {kind: int(get_registry().gauge(
        "attention_bwd_steps", op=op, kernel=kernel, kind=kind).value)
        for kind in ("interior", "edge", "dead")}


def _reaches(op, kinds):
    """Both kernels' grids hold a step of every kind in `kinds` and none
    of another."""
    for kernel in KERNELS:
        steps = _steps(op, kernel)
        assert {k for k, n in steps.items() if n} == set(kinds), (kernel,
                                                                  steps)


# name -> (T, H, Hkv, D, window, causal, block_q, block_k, the kinds of
# step both grids have)
BANDED = {
    # 64 x 64 tiles in a band of 200: two K blocks wholly inside it a Q
    # block, the diagonal's and the far edge's masked, and the first Q
    # blocks' clamped sweep dead
    "interior_edge_dead_g6": (512, 6, 1, 128, 200, True, 64, 64,
                              ("interior", "edge", "dead")),
    # rows 64 to 127 of an even Q block see nothing in the first K block
    # the block visits: their p is 0 across a whole visited tile
    "row_with_no_key_in_a_tile_g6": (512, 6, 1, 128, 64, True, 128, 256,
                                     ("edge", "dead")),
    "two_sided_g1": (512, 2, 2, 64, 160, False, 64, 64,
                     ("interior", "edge", "dead")),
    # a band narrower than a tile: every live tile is on an edge
    "two_sided_narrow_g2": (256, 4, 2, 64, 40, False, 64, 64,
                            ("edge", "dead")),
    "odd_blocks": (192, 4, 2, 32, 50, True, 64, 48, ("edge", "dead")),
    "g16_folded_to_1024_rows": (256, 16, 1, 64, 100, True, 64, 128,
                                ("edge", "dead")),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(BANDED))
def test_banded_backward_is_its_oracles(case, dtype):
    t, h, hkv, d, window, causal, bq, bk, kinds = BANDED[case]
    q, k, v, do = _inputs(1, 2, t, t, h, hkv, d, dtype)
    _, vjp = jax.vjp(
        lambda q, k, v: banded.banded_attention(q, k, v, window, causal,
                                                None, bq, bk, True), q, k, v)
    got = vjp(do)
    for kernel in KERNELS:
        assert _tile("banded_attention", kernel) == (h // hkv * bq, bk)
    _reaches("banded_attention", kinds)
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    vis = ((ki <= qi) & (ki > qi - window) if causal
           else jnp.abs(qi - ki) < window)
    _check(got, _oracle_grads(q, k, v, do, vis[None, None]), dtype)


# name -> (Tq, Tk, H, Hkv, D, causal, block_q, block_k, kinds)
FLASH = {
    "causal_g6": (512, 512, 6, 1, 128, True, 128, 256,
                  ("interior", "edge", "dead")),
    "causal_tall_tile_g1": (256, 256, 2, 2, 64, True, 128, 64,
                            ("interior", "edge", "dead")),
    "causal_wide_tile_g2": (256, 256, 4, 2, 64, True, 64, 128,
                            ("interior", "edge", "dead")),
    # no mask exists without `causal`: every tile is interior
    "cross_tq_not_tk_g1": (128, 512, 2, 2, 128, False, 128, 256,
                           ("interior",)),
    "cross_g4": (256, 128, 4, 1, 64, False, 64, 128, ("interior",)),
    "odd_blocks": (96, 96, 4, 2, 32, True, 48, 32,
                   ("interior", "edge", "dead")),
}


def _fold(x):
    """[1, T, H, D] -> [H, T, D]."""
    return x[0].transpose(1, 0, 2)


def _causal_or_all(tq, tk, causal):
    return (jnp.tril(jnp.ones((tq, tk), bool)) if causal
            else jnp.ones((tq, tk), bool))[None, None]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(FLASH))
def test_flash_backward_is_its_oracles(case, dtype):
    tq, tk, h, hkv, d, causal, bq, bk, kinds = FLASH[case]
    q, k, v, do = _inputs(2, 1, tq, tk, h, hkv, d, dtype)
    _, vjp = jax.vjp(
        lambda q, k, v: flash.flash_attention(q, k, v, causal, None, bq, bk,
                                              True, "pallas"), q, k, v)
    got = vjp(do)
    for kernel in KERNELS:
        assert _tile("flash_attention", kernel) == (bq, bk)
    _reaches("flash_attention", kinds)
    _check(got, _oracle_grads(q, k, v, do, _causal_or_all(tq, tk, causal)),
           dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["causal_g6", "cross_tq_not_tk_g1"])
def test_flash_backward_takes_the_log_sum_exps_cotangent(case, dtype):
    """`flash_attention_with_lse` (the ring's merge): a cotangent of the
    emitted log-sum-exp shifts delta in both kernels."""
    tq, tk, h, hkv, d, causal, bq, bk, _ = FLASH[case]
    q, k, v, do = _inputs(3, 1, tq, tk, h, hkv, d, dtype)
    dlse = jax.random.normal(jax.random.PRNGKey(4), (1, h, tq), jnp.float32)
    _, vjp = jax.vjp(
        lambda q, k, v: flash.flash_attention_with_lse(
            _fold(q), _fold(k), _fold(v), causal, None, bq, bk, True),
        q, k, v)
    got = vjp((_fold(do), dlse[0]))
    want = _oracle_grads(q, k, v, do, _causal_or_all(tq, tk, causal), dlse)
    _check(got, want, dtype)
    shifted = np.abs(np.asarray(want[0]) - np.asarray(
        _oracle_grads(q, k, v, do, _causal_or_all(tq, tk, causal))[0]))
    assert float(shifted.max()) > 0.1     # the cotangent is not a rounding


# name -> (T, H, Hkv, D, block size, block_q, block_k, far)
SPARSE = {
    "rows_that_list_nothing_g6": (512, 6, 1, 128, 16, 64, 256, True),
    "g16": (256, 16, 1, 64, 8, 64, 128, False),
    "g1_two_kv_heads": (512, 2, 2, 128, 16, 128, 256, True),
    "tall_tile_g2": (512, 2, 1, 64, 16, 256, 128, True),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(SPARSE))
def test_sparse_backward_is_its_oracles(case, dtype):
    t, h, hkv, d, bs, bq, bk, far = SPARSE[case]
    q, k, v, do = _inputs(5, 1, t, t, h, hkv, d, dtype)
    allow = jnp.broadcast_to(_listing(t, bs, far), (1, hkv, t, t // bs))
    _, vjp = jax.vjp(
        lambda q, k, v: sparse.block_sparse_attention(q, k, v, allow, bs,
                                                      None, bq, bk, True),
        q, k, v)
    got = vjp(do)
    for kernel in KERNELS:
        assert _tile("sparse_attention", kernel) == (bq, bk)
    # which tiles a walk skips is data: the gauge counts the causal
    # triangle as edge steps, nothing as interior
    _reaches("sparse_attention", ("edge", "dead"))
    vis = (jnp.repeat(allow, bs, axis=-1)
           & jnp.tril(jnp.ones((t, t), bool))[None, None])
    _check(got, _oracle_grads(q, k, v, do, vis), dtype)


# (T, block_q, block_k, window or None for the flash family, causal)
GRIDS = [(512, 64, 64, 200, True), (512, 128, 64, 200, True),
         (512, 64, 128, 160, False), (256, 64, 32, 40, False),
         (384, 128, 128, 384, True), (512, 64, 128, None, True),
         (512, 128, 64, None, True), (256, 64, 64, None, False)]


@pytest.mark.parametrize("t,bq,bk,window,causal", GRIDS)
def test_the_grid_a_backward_builds(t, bq, bk, window, causal):
    """`attention_bwd_steps` against the pairs themselves: a tile is live
    where any of its pairs is visible and interior where all are; each
    kernel's grid is its pinned blocks by the most live tiles one of them
    sweeps (the flash family's: by all of them), two heads' worth."""
    q, k, v, do = _inputs(6, 1, t, t, 2, 2, 32, jnp.float32)
    if window is None:
        op, fn = "flash_attention", lambda q, k, v: flash.flash_attention(
            q, k, v, causal, None, bq, bk, True, "pallas")
    else:
        op, fn = "banded_attention", lambda q, k, v: banded.banded_attention(
            q, k, v, window, causal, None, bq, bk, True)
    jax.eval_shape(lambda q, k, v, do: jax.vjp(fn, q, k, v)[1](do),
                   q, k, v, do)
    qi, ki = np.arange(t)[:, None], np.arange(t)[None, :]
    vis = np.ones((t, t), bool)
    if causal:
        vis &= ki <= qi
    if window is not None:
        vis &= (ki > qi - window) & (ki < qi + window)
    pairs = vis.reshape(t // bq, bq, t // bk, bk)
    live, interior = pairs.any((1, 3)), pairs.all((1, 3))
    swept = {"dq": (t // bq) * (t // bk if window is None
                                else live.sum(1).max()),
             "dkdv": (t // bk) * (t // bq if window is None
                                  else live.sum(0).max())}
    for kernel in KERNELS:
        assert _steps(op, kernel) == {
            "interior": 2 * interior.sum(),
            "edge": 2 * (live.sum() - interior.sum()),
            "dead": 2 * (swept[kernel] - live.sum())}, kernel


# --- the tile a backward kernel picks on the chip: name -> (family's call
# of `_pick_tile`, the dQ kernel's tile, the dK/dV kernel's). The first
# three are the benchmark's cells (`trinity_large_fit`'s window and full
# layers, `minicpm_sala_fit`'s selecting layer), where the kernels alone
# were timed over the candidates (PERF.md section 6, PR 39).
def _picked(family, t, g, window=None, blocks=None, block_size=64,
            dtype=jnp.bfloat16, interpret=False):
    x = lambda *shape: jax.ShapeDtypeStruct(shape, dtype)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    if family == "banded":
        bq, bk = blocks or (256, 256)
        jax.eval_shape(
            lambda q, k, v, o, lse, do: banded._run_banded_bwd(
                q, k, v, o, lse, do, window=window, causal=True, scale=1.0,
                block_q=bq, block_k=bk, interpret=interpret),
            x(1, g, t, 128), x(1, t, 128), x(1, t, 128), x(1, g, t, 128),
            f32(1, g, t), x(1, g, t, 128))
        op = "banded_attention"
    elif family == "flash":
        bq, bk = blocks or (512, 512)
        jax.eval_shape(
            lambda q, k, v, o, lse, do: flash._run_flash_bwd(
                q, k, v, o, lse, do, causal=True, scale=1.0, block_q=bq,
                block_k=bk, interpret=interpret),
            x(g, t, 128), x(1, t, 128), x(1, t, 128), x(g, t, 128),
            f32(g, t), x(g, t, 128))
        op = "flash_attention"
    else:
        bq, bk = blocks or (256, 512)
        sparse._kernel_tiles(t, g, block_size, bq, bk, interpret)
        op = "sparse_attention"
    return tuple(_tile(op, kernel) for kernel in KERNELS)


PICKED = {
    "trinity_window_layer": (("banded", 8192, 6, 4096),
                             (1536, 512), (1536, 512)),
    "trinity_full_layer": (("flash", 8192, 6), (1024, 1024), (1024, 1024)),
    "minicpm_selecting_layer": (("sparse", 16384, 16),
                                (1024, 512), (1024, 512)),
    # a group of 16 keeps the policy's 256 tokens: its 4,096 x 256 tile
    # is inside the backward's 4 MiB, where the forward's 3 halve it
    "banded_g16": (("banded", 8192, 16, 4096), (4096, 256), (4096, 256)),
    # a band of 512 keys at 2,048 tokens keeps narrow tiles
    "narrow_band": (("banded", 2048, 4, 512), (1024, 256), (1024, 256)),
    # the sparse K tile stays the caller's, whatever it is
    "sparse_k_tile_kept": (("sparse", 4096, 2, None, (64, 128)),
                           (2048, 128), (2048, 128)),
    "short_flash": (("flash", 512, 2), (512, 512), (512, 512)),
    # float32 operands: the same tile, `_tile_params` raises the limit
    "trinity_window_layer_f32": (
        ("banded", 8192, 6, 4096, None, 64, jnp.float32),
        (1536, 512), (1536, 512)),
}


@pytest.mark.parametrize("case", sorted(PICKED))
def test_the_tile_the_backward_picks(case):
    call, dq, dkdv = PICKED[case]
    assert _picked(*call) == (dq, dkdv)


@pytest.mark.parametrize("family,t,g,window,blocks", [
    ("banded", 512, 6, 200, (64, 32)), ("flash", 512, 6, None, (128, 64)),
    ("sparse", 512, 6, None, (64, 128))])
def test_interpret_mode_keeps_the_passed_blocks(family, t, g, window,
                                                blocks):
    fold = g if family == "banded" else 1
    tile = (fold * blocks[0], blocks[1])
    assert _picked(family, t, g, window, blocks, 16,
                   interpret=True) == (tile, tile)
