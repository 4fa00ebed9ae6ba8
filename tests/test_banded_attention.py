"""Interpret-mode parity suite for the banded attention subsystem
(ops/banded_attention.py) and its layer routing.

The contract under test: the one-pass O(T·w) Pallas kernel — sliding
window + GQA head grouping + rolling-ring held-index arithmetic fused
into the grid — is numerically the dense band-masked path it replaces,
across causal and bidirectional windows, GQA group ratios, ring
wraparound under slot reuse, and odd T/w edge shapes. Plus the
acceptance probe: the banded program's compiled flops must scale T·w,
not T² (the dense contender's quadrupling is the control).

Everything runs in interpret mode on CPU — the kernel arithmetic is
identical on TPU; only the lowering differs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.banded_attention import (
    banded_attention,
    banded_decode_attention,
    banded_reference,
    decode_reference,
)

TOL = dict(rtol=2e-5, atol=2e-5)


def _qkv(b, t, h, hkv, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, dh), jnp.float32),
            jax.random.normal(ks[1], (b, t, hkv, dh), jnp.float32),
            jax.random.normal(ks[2], (b, t, hkv, dh), jnp.float32))


class TestFullSeqParity:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 2), (4, 1)])
    def test_gqa_ratios(self, causal, h, hkv):
        t, w, dh = 64, 16, 8
        q, k, v = _qkv(2, t, h, hkv, dh)
        got = banded_attention(q, k, v, w, causal, None, 16, 16,
                               interpret=True)
        want = banded_reference(q, k, v, w, causal, dh ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)

    @pytest.mark.parametrize("t,w", [(7, 3), (33, 16), (48, 5),
                                     (64, 1), (64, 64), (64, 100)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_odd_shapes(self, t, w, causal):
        # T not a tile multiple, w=1 (self-only), w>=T (full context):
        # interpret mode fits blocks down to any divisor, so the grid
        # math — not a padded special case — must cover these.
        dh = 8
        q, k, v = _qkv(1, t, 4, 2, dh, seed=t * 131 + w)
        got = banded_attention(q, k, v, w, causal, None, 16, 16,
                               interpret=True)
        want = banded_reference(q, k, v, w, causal, dh ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)

    def test_gradients_match_reference(self):
        # custom_vjp routes the backward through the blockwise dQ and
        # dK/dV kernels over the band's tiles; the dense band-masked
        # reference under plain autodiff is the oracle.
        t, w, dh = 32, 8, 8
        q, k, v = _qkv(1, t, 4, 2, dh, seed=5)

        def f(attn):
            def loss(q, k, v):
                return (attn(q, k, v) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        got = f(lambda q, k, v: banded_attention(
            q, k, v, w, True, None, 8, 8, interpret=True))
        want = f(lambda q, k, v: banded_reference(
            q, k, v, w, True, dh ** -0.5))
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t,w,bq,bk", [
        (64, 24, 16, 16),     # window under T: most tiles lie off the band
        (64, 24, 8, 32),      # Q and K blocks of different sizes
        (64, 100, 16, 16),    # window over T: every tile is on the band
        (48, 1, 16, 8),       # self only
        (40, 17, 8, 8),       # T and w off every tile's edge
    ])
    def test_blockwise_backward_for_a_six_wide_group(self, causal, t, w,
                                                     bq, bk):
        # 48 query heads over 8 KV heads in small: 12 over 2, so the
        # backward's tiles hold a group of six and dK/dV sum over it
        dh = 8
        q, k, v = _qkv(2, t, 12, 2, dh, seed=t + w)
        do = jax.random.normal(jax.random.PRNGKey(99), q.shape)

        def grads(attn):
            return jax.grad(lambda q, k, v: (attn(q, k, v) * do).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        got = grads(lambda q, k, v: banded_attention(
            q, k, v, w, causal, None, bq, bk, interpret=True))
        want = grads(lambda q, k, v: banded_reference(
            q, k, v, w, causal, dh ** -0.5))
        for g, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), **TOL)

    def test_backward_keeps_no_score_matrix(self):
        # the residuals between the passes are q, k, v, o and one number a
        # row; nothing [T, T] is traced in either direction
        t, w = 256, 32
        q, k, v = _qkv(1, t, 4, 2, 8)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: banded_attention(
            q, k, v, w, True, None, 32, 32, interpret=False).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        shapes = [tuple(var.aval.shape) for eqn in jaxpr.eqns
                  for var in eqn.outvars]
        assert not any(s[-2:] == (t, t) for s in shapes if len(s) >= 2)

    def test_multi_block_sweep(self):
        # the same answer regardless of tiling: block geometry must not
        # leak into the math (first-block init, relevant-skip, kb_first)
        t, w, dh = 64, 12, 8
        q, k, v = _qkv(2, t, 4, 2, dh, seed=9)
        want = banded_reference(q, k, v, w, True, dh ** -0.5)
        for bq, bk in ((8, 8), (16, 8), (8, 32), (32, 32), (64, 64)):
            got = banded_attention(q, k, v, w, True, None, bq, bk,
                                   interpret=True)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), **TOL,
                                       err_msg=f"bq={bq} bk={bk}")


class TestFlopsScaling:
    def test_banded_flops_scale_subquadratic(self):
        """The acceptance probe: doubling T quadruples the DENSE
        program's flops (T² control) but must not quadruple the banded
        program's (O(T·w) contract; the interpret lowering is a loop,
        so its cost is flat-to-linear in T)."""
        w, dh, bq = 16, 8, 8

        def flops(fn, t):
            q, k, v = _qkv(1, t, 4, 2, dh)
            c = jax.jit(fn).lower(q, k, v).cost_analysis()
            if isinstance(c, (list, tuple)):
                c = c[0]
            return float(c["flops"])

        dense = lambda q, k, v: banded_reference(q, k, v, w, True,
                                                 dh ** -0.5)
        banded = lambda q, k, v: banded_attention(
            q, k, v, w, True, None, bq, bq, True)
        d1, d2 = flops(dense, 64), flops(dense, 128)
        b1, b2 = flops(banded, 64), flops(banded, 128)
        assert d2 / d1 > 3.5, f"dense control broke: {d1} -> {d2}"
        assert b2 / b1 <= 2.5, (
            f"banded flops grew {b2 / b1:.2f}x for 2x T — the O(T*w) "
            f"contract is broken ({b1} -> {b2})")


class TestDecodeParity:
    def _cache(self, s, l, h, hkv, dh, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(ks[0], (s, h, dh), jnp.float32),
                jax.random.normal(ks[1], (s, l, hkv, dh), jnp.float32),
                jax.random.normal(ks[2], (s, l, hkv, dh), jnp.float32))

    @pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 2), (4, 1)])
    def test_linear_cache(self, h, hkv):
        s, l, dh = 4, 8, 8
        q, ck, cv = self._cache(s, l, h, hkv, dh)
        qpos = jnp.asarray([0, 3, 5, 7], jnp.int32)
        for window in (None, 4):
            got = banded_decode_attention(q, ck, cv, qpos, qpos,
                                          window=window, rolling=False,
                                          block_l=4, interpret=True)
            want = decode_reference(q, ck, cv, qpos, qpos, window,
                                    False, dh ** -0.5)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), **TOL)

    def test_ring_wraparound_under_reuse(self):
        # positions far past L: every slot has been overwritten at least
        # once, and the held-index arithmetic — not stored metadata —
        # must reconstruct which global position each slot now holds
        s, l, h, hkv, dh, w = 6, 8, 4, 2, 8, 4
        q, ck, cv = self._cache(s, l, h, hkv, dh, seed=3)
        qpos = jnp.asarray([0, 3, 7, 9, 15, 23], jnp.int32)
        got = banded_decode_attention(q, ck, cv, qpos, qpos, window=w,
                                      rolling=True, block_l=4,
                                      interpret=True)
        want = decode_reference(q, ck, cv, qpos, qpos, w, True,
                                dh ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)

    def test_block_sweep(self):
        s, l, h, hkv, dh = 4, 8, 4, 2, 8
        q, ck, cv = self._cache(s, l, h, hkv, dh, seed=11)
        qpos = jnp.asarray([1, 2, 6, 7], jnp.int32)
        want = decode_reference(q, ck, cv, qpos, qpos, 4, True,
                                dh ** -0.5)
        for bl in (2, 4, 8):
            got = banded_decode_attention(q, ck, cv, qpos, qpos,
                                          window=4, rolling=True,
                                          block_l=bl, interpret=True)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), **TOL,
                                       err_msg=f"block_l={bl}")


class TestLayerRouting:
    """The integration seam: DL4J_TPU_ATTN / DL4J_TPU_DECODE_ATTN route
    the REAL layer through the kernel (interpret mode on CPU), and the
    forced-banded output matches the forced-dense output."""

    def _layer(self, **kw):
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadAttention,
        )
        lay = MultiHeadAttention(n_in=32, n_out=32, num_heads=4,
                                 activation="identity", **kw)
        p, _ = lay.init_params(jax.random.PRNGKey(0), None, jnp.float32)
        return lay, p

    def _full(self, env, monkeypatch, causal):
        monkeypatch.setenv("DL4J_TPU_ATTN", env)
        lay, p = self._layer(num_kv_heads=2, window=24, causal=causal)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 32))
        y, _ = lay.apply(p, x)
        return np.asarray(y)

    @pytest.mark.parametrize("causal", [True, False])
    def test_full_seq_forced_banded_matches_dense(self, monkeypatch,
                                                  causal):
        dense = self._full("dense", monkeypatch, causal)
        band = self._full("banded", monkeypatch, causal)
        np.testing.assert_allclose(band, dense, **TOL)

    def _decode_run(self, env, monkeypatch, *, rolling, per_slot):
        monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", env)
        lay, p = self._layer(num_kv_heads=2, window=8, causal=True,
                             max_cache=8 if rolling else 16,
                             rolling_cache=rolling)
        st = lay.decode_carry(2, per_slot=per_slot)
        ys = []
        for i in range(12):   # 12 steps over an 8-slot ring = reuse
            x = jax.random.normal(jax.random.PRNGKey(40 + i), (2, 1, 32))
            y, st = lay.apply(p, x, state=st)
            ys.append(np.asarray(y))
        return np.stack(ys)

    @pytest.mark.parametrize("rolling,per_slot", [(False, False),
                                                  (True, False),
                                                  (True, True)])
    def test_decode_forced_banded_matches_dense(self, monkeypatch,
                                                rolling, per_slot):
        dense = self._decode_run("dense", monkeypatch, rolling=rolling,
                                 per_slot=per_slot)
        band = self._decode_run("banded", monkeypatch, rolling=rolling,
                                per_slot=per_slot)
        np.testing.assert_allclose(band, dense, **TOL)

    def test_default_cpu_path_is_dense(self, monkeypatch):
        # No env, CPU backend: policy must stay on the dense path (no
        # measured rows, not a TPU) — existing behavior unchanged.
        monkeypatch.delenv("DL4J_TPU_ATTN", raising=False)
        from deeplearning4j_tpu.ops.kernel_defaults import banded_policy
        assert banded_policy(256, 4, 2).kind == "dense"

    def test_dispatch_counter_records_policy_calls(self):
        from deeplearning4j_tpu.observe import get_registry
        from deeplearning4j_tpu.ops.kernel_defaults import banded_policy
        c = get_registry().counter("kernel_dispatch_total",
                                   op="banded_attention", impl="dense")
        v0 = c.value
        banded_policy(256, 4, 2)          # CPU default: dense
        assert c.value == v0 + 1
