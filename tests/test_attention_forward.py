"""The one online-softmax update (`ops/attention._softmax_update`) through
each of the three forward kernels that call it, in interpret mode against
the family's oracle: output and log-sum-exp (the backward kernels read
the second), float32 and bf16, at shapes that reach each branch of the
update. In interpret mode the passed blocks are the tile; the tile the
forward picks for itself on the chip (`_pick_tile`) is arithmetic, checked
at the cells' shapes at the end.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.observe import get_registry

flash = importlib.import_module("deeplearning4j_tpu.ops.attention")
banded = importlib.import_module("deeplearning4j_tpu.ops.banded_attention")
sparse = importlib.import_module("deeplearning4j_tpu.ops.sparse_attention")

HI = jax.lax.Precision.HIGHEST
TOL = {jnp.float32: (2e-5, 2e-5), jnp.bfloat16: (2e-2, 2e-4)}   # o, lse


def _inputs(seed, b, t, h, hkv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = [(b, t, h, d), (b, t, hkv, d), (b, t, hkv, d)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(ks, shapes)]


def _oracle(q, k, v, vis):
    """(o [B, T, H, D], lse [B, H, T]) of softmax over the pairs `vis`
    [B, Hkv, T, T] has, in float32 from the inputs as they are rounded."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    qg = q.reshape(b, t, hkv, h // hkv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, precision=HI) * d ** -0.5
    s = jnp.where(vis[:, :, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)                       # [B,Hkv,G,T]
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jnp.exp(s - lse[..., None]), v,
                   precision=HI)
    return o.reshape(b, t, h, d), lse.reshape(b, h, t)


def _tile(op):
    gauge = lambda field: int(get_registry().gauge(
        "attention_fwd_tile", op=op, field=field).value)
    return gauge("rows"), gauge("keys_per_update")


def _check(got_o, got_lse, want_o, want_lse, dtype):
    tol_o, tol_lse = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got_o, np.float32),
                               np.asarray(want_o), rtol=tol_o, atol=tol_o)
    np.testing.assert_allclose(np.asarray(got_lse), np.asarray(want_lse),
                               rtol=tol_lse, atol=tol_lse)


DTYPES = [jnp.float32, jnp.bfloat16]

# name -> (T, H, Hkv, D, window, causal, block_q, block_k, what the chosen
# sweep of keys has to be: "lanes" whole 128-lane groups, more than one;
# "odd" no multiple of 128, the plain cross-lane sum)
BANDED = {
    # rows 64 to 127 of an even Q block see nothing in the first K block
    # the block visits: m is still -1e30 there when the block ends
    "band_not_started_g6": (512, 6, 1, 128, 64, True, 128, 256, "lanes"),
    "g16_folded_to_1024_rows": (512, 16, 1, 128, 200, True, 64, 256,
                                "lanes"),
    "g1_two_sided": (512, 2, 2, 64, 100, False, 128, 256, "lanes"),
    "odd_key_block": (192, 4, 2, 32, 50, True, 64, 48, "odd"),
    "head_dim_256": (256, 2, 1, 256, 96, True, 64, 128, "one_group"),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(BANDED))
def test_banded_forward_is_its_oracle(case, dtype):
    t, h, hkv, d, window, causal, bq, bk, sweep = BANDED[case]
    q, k, v = _inputs(1, 2, t, h, hkv, d, dtype)
    o5, lse = banded._run_banded(
        *banded._fold_heads(q, k, v), window=window, causal=causal,
        scale=d ** -0.5, block_q=bq, block_k=bk, interpret=True,
        with_lse=True)
    assert _tile("banded_attention") == (h // hkv * bq, bk)
    assert {"lanes": bk % 128 == 0 and bk >= 256, "odd": bk % 128 > 0,
            "one_group": bk == 128}[sweep]
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    vis = ((ki <= qi) & (ki > qi - window) if causal
           else jnp.abs(qi - ki) < window)
    want_o, want_lse = _oracle(q, k, v, vis[None, None])
    _check(banded._unfold_q(o5, 2), lse.reshape(2, h, t), want_o, want_lse,
           dtype)
    np.testing.assert_allclose(
        np.asarray(want_o),
        np.asarray(banded.banded_reference(q, k, v, window, causal,
                                           d ** -0.5), np.float32),
        rtol=TOL[dtype][0], atol=TOL[dtype][0])


# name -> (Tq, Tk, H, Hkv, D, causal, block_q, block_k, sweep)
FLASH = {
    "causal_g6": (512, 512, 6, 1, 128, True, 128, 256, "lanes"),
    "causal_g16": (256, 256, 16, 1, 64, True, 64, 256, "lanes"),
    "cross_g1": (128, 512, 2, 2, 128, False, 128, 512, "lanes"),
    "odd_key_block": (96, 96, 4, 2, 32, True, 96, 96, "odd"),
    "head_dim_256": (256, 256, 2, 2, 256, True, 128, 128, "one_group"),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(FLASH))
def test_flash_forward_is_its_oracle(case, dtype):
    tq, tk, h, hkv, d, causal, bq, bk, sweep = FLASH[case]
    q, _, _ = _inputs(2, 1, tq, h, hkv, d, dtype)
    _, k, v = _inputs(3, 1, tk, h, hkv, d, dtype)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, *x.shape[1:2],
                                                      x.shape[3])
    o3, lse = flash.flash_attention_with_lse(fold(q), fold(k), fold(v),
                                             causal, None, bq, bk, True)
    assert _tile("flash_attention") == (min(bq, tq), min(bk, tk))
    assert {"lanes": bk % 128 == 0 and bk >= 256, "odd": bk % 128 > 0,
            "one_group": bk == 128}[sweep]
    vis = (jnp.tril(jnp.ones((tq, tk), bool)) if causal
           else jnp.ones((tq, tk), bool))
    want_o, want_lse = _oracle(q, k, v, vis[None, None])
    _check(o3.reshape(1, h, tq, d).transpose(0, 2, 1, 3),
           lse.reshape(1, h, tq), want_o, want_lse, dtype)
    if causal:
        dense = flash._dense_attention(
            fold(q).astype(jnp.float32),
            jnp.repeat(fold(k), h // hkv, axis=0).astype(jnp.float32),
            jnp.repeat(fold(v), h // hkv, axis=0).astype(jnp.float32),
            True, d ** -0.5)
        np.testing.assert_allclose(np.asarray(o3, np.float32),
                                   np.asarray(dense), rtol=TOL[dtype][0],
                                   atol=TOL[dtype][0])


def _listing(t, bs, far):
    """[1, 1, T, T // bs]: every token lists its own block and the one
    before it; token 8 of every 64 also lists block 0 and, with `far`,
    the block half the sequence back. So a K tile far behind a Q tile is
    visited for one row's sake and every other row lists nothing in it,
    and the rows ahead of that token have nothing yet when it comes."""
    tok = np.arange(t)[:, None]
    blk = np.arange(t // bs)[None, :]
    own = tok // bs
    allow = (blk <= own) & (blk >= own - 1)
    scout = (tok % 64 == 8)
    allow |= scout & (blk == 0)
    if far:
        allow |= scout & (blk == np.maximum(own - t // bs // 2, 0))
    return jnp.asarray(allow[None, None])


# name -> (T, H, Hkv, D, block size, block_q, block_k, far)
SPARSE = {
    "rows_that_list_nothing_g6": (512, 6, 1, 128, 16, 64, 256, True),
    "g16": (256, 16, 1, 64, 8, 64, 128, False),
    "g1_two_kv_heads": (512, 2, 2, 128, 16, 128, 256, True),
    "head_dim_256": (256, 2, 1, 256, 8, 64, 128, False),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(SPARSE))
def test_sparse_forward_is_its_oracle(case, dtype):
    t, h, hkv, d, bs, bq, bk, far = SPARSE[case]
    q, k, v = _inputs(4, 1, t, h, hkv, d, dtype)
    allow = jnp.broadcast_to(_listing(t, bs, far), (1, hkv, t, t // bs))
    o, res = sparse._sparse_fwd(q, k, v, allow, bs, None, bq, bk, True)
    assert _tile("sparse_attention") == (bq, bk)
    vis = (jnp.repeat(allow, bs, axis=-1)
           & jnp.tril(jnp.ones((t, t), bool))[None, None])
    want_o, want_lse = _oracle(q, k, v, vis)
    _check(o, res[6].reshape(1, h, t), want_o, want_lse, dtype)
    masked = sparse.masked_attention(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32), allow, bs)
    np.testing.assert_allclose(np.asarray(want_o), np.asarray(masked),
                               rtol=2e-5, atol=2e-5)


def test_a_row_with_no_key_yet_adds_nothing():
    """The update alone: rows 0 and 1 have every pair of the first sweep
    masked (m stays -1e30, where exp(s - m) would be 1), row 1 of the
    second too; the live rows come out as a plain softmax and the dead
    row as zeros with a log-sum-exp of -1e30 + log(1e-30)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, keys, d = 8, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    s = jax.random.normal(ks[0], (2, rows, keys), jnp.float32)
    v = jax.random.normal(ks[1], (2, keys, d), jnp.float32)
    mask = np.ones((2, rows, keys), bool)
    mask[0, :2] = False
    mask[1, 1] = False
    mask[1, 2, 100:] = False
    mask = jnp.asarray(mask)

    def kernel(s_ref, mask_ref, v_ref, o_ref, lse_ref, acc, m, l):
        flash._softmax_init(acc, m, l)
        for step in range(2):
            flash._softmax_update(s_ref[step], mask_ref[step] > 0,
                                  v_ref[step], acc, m, l, HI)
        o_ref[:], lse_ref[:] = flash._softmax_finish(acc, m, l, keys)

    o, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 128), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32),
                        pltpu.VMEM((rows, 128), jnp.float32)],
        interpret=True,
    )(s, mask.astype(jnp.int32), v)
    sc = jnp.where(mask, s, -jnp.inf).transpose(1, 0, 2).reshape(rows, -1)
    want_lse = jax.nn.logsumexp(sc[jnp.arange(rows) != 1], axis=-1)
    want = jnp.einsum("rk,kd->rd", jax.nn.softmax(sc, axis=-1),
                      v.reshape(-1, d), precision=HI)
    live = np.arange(rows) != 1
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse)[live, 0],
                               np.asarray(want_lse), rtol=2e-5, atol=2e-5)
    assert float(np.abs(np.asarray(o)[1]).max()) == 0.0
    assert float(np.asarray(lse)[1, 0]) < -1e29
    assert (np.asarray(lse) == np.asarray(lse)[:, :1]).all()


# --- the tile the forward picks on the chip: name -> (family's call of
# `_pick_tile`, the tile). The first three are the benchmark's cells
# (`trinity_large_fit`'s window and full layers, `minicpm_sala_fit`'s
# selecting layer), where the kernels alone were timed over the candidates
# (PERF.md section 6, PR 36).
def _picked(family, t, g, window=None, blocks=None, block_size=64):
    x = jax.ShapeDtypeStruct
    if family == "banded":
        bq, bk = blocks or (256, 256)
        jax.eval_shape(
            lambda q, k, v: banded._run_banded(
                q, k, v, window=window, causal=True, scale=1.0, block_q=bq,
                block_k=bk, interpret=False),
            x((1, g, t, 128), jnp.bfloat16), x((1, t, 128), jnp.bfloat16),
            x((1, t, 128), jnp.bfloat16))
        return _tile("banded_attention")
    if family == "flash":
        bq, bk = blocks or (512, 512)
        jax.eval_shape(
            lambda q, k, v: flash._run_flash(
                q, k, v, causal=True, scale=1.0, block_q=bq, block_k=bk,
                interpret=False),
            x((g, t, 128), jnp.bfloat16), x((1, t, 128), jnp.bfloat16),
            x((1, t, 128), jnp.bfloat16))
        return _tile("flash_attention")
    bq, bk = blocks or (256, 512)
    jax.eval_shape(
        lambda q, k, v, a: sparse._sparse_fwd(q, k, v, a, block_size, None,
                                              bq, bk, False)[0],
        x((1, t, g, 128), jnp.bfloat16), x((1, t, 1, 128), jnp.bfloat16),
        x((1, t, 1, 128), jnp.bfloat16),
        x((1, 1, t, t // block_size), jnp.bool_))
    return _tile("sparse_attention")


PICKED = {
    "trinity_window_layer": (("banded", 8192, 6, 4096), (1536, 512)),
    "trinity_full_layer": (("flash", 8192, 6), (1024, 512)),
    "minicpm_selecting_layer": (("sparse", 16384, 16), (1024, 512)),
    # a group of 16 folds to 2,048 rows at the Q block's floor of 128
    "banded_g16": (("banded", 8192, 16, 4096), (2048, 256)),
    # a band of 512 keys: a wider K block would compute mostly masked pairs
    "narrow_band": (("banded", 2048, 4, 512), (1024, 256)),
    # the sparse K tile stays the caller's, whatever it is
    "sparse_k_tile_kept": (("sparse", 4096, 2, None, (64, 128)),
                           (2048, 128)),
    "short_flash": (("flash", 512, 2), (512, 512)),
}


@pytest.mark.parametrize("case", sorted(PICKED))
def test_the_tile_the_forward_picks(case):
    call, tile = PICKED[case]
    assert _picked(*call) == tile
