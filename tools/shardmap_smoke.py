"""Pallas-kernels-under-shard_map smoke (VERDICT r4 #4).

Interpret mode on the CPU mesh cannot catch Mosaic lowering errors, so
every Pallas path must also compile AND run inside a sharded jit on the
real chip — the composition production actually uses (kernels under DP,
the ring's per-shard flash, KV-cache decode). This tool runs each
composition with numerics checked against its XLA oracle and records
the verdicts; run it on the chip through the builder's chip tool:

    python tools/shardmap_smoke.py            # real chip (non-interpret)
    SMOKE_INTERPRET=1 JAX_PLATFORMS=cpu ...   # harness self-check on CPU

Results: one JSON line per check; aggregate in
tools/shardmap_smoke_results.json (TPU evidence never overwritten by
CPU runs).
"""
import functools
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    make_mesh, shard_map_compat as _sm,
)
from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache

INTERPRET = bool(os.environ.get("SMOKE_INTERPRET"))


def _mesh(axis="data"):
    # the package's own mesh construction (device ordering included)
    return make_mesh({axis: -1})


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


# ------------------------------------------------------------ checks
def check_flash_fwd_shardmap():
    """flash_attention (512^2 tiles, Pallas backward residuals) sharded
    over batch*heads — the composition MultiHeadAttention uses under DP."""
    from deeplearning4j_tpu.ops.attention import (_dense_attention,
                                                  flash_attention)
    mesh = _mesh()
    n = len(jax.devices())
    bh, t, d = 4 * n, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.bfloat16) for kk in ks)
    spec = P("data", None, None)

    fn = jax.jit(_sm(
        lambda q, k, v: flash_attention(q, k, v, True, None, 512, 512,
                                        INTERPRET, "pallas"),
        mesh, (spec, spec, spec), spec))
    o = fn(q, k, v)
    ref = _dense_attention(q, k, v, True, d ** -0.5)
    return {"max_err": _maxerr(o, ref), "tol": 0.04}


def check_flash_bwd_shardmap():
    """grad through the blockwise Pallas backward inside shard_map."""
    from deeplearning4j_tpu.ops.attention import (_dense_attention,
                                                  flash_attention)
    mesh = _mesh()
    n = len(jax.devices())
    bh, t, d = 2 * n, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (bh, t, d), jnp.float32) * 0.5
               for kk in ks)
    spec = P("data", None, None)

    def local_loss(q, k, v):
        o = flash_attention(q, k, v, True, None, 512, 512, INTERPRET,
                            "pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2, keepdims=True)[None]

    def loss(q, k, v):
        per_shard = _sm(local_loss, mesh, (spec, spec, spec),
                        P("data"))(q, k, v)
        return jnp.sum(per_shard)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def ref_loss(q, k, v):
        o = _dense_attention(q, k, v, True, d ** -0.5)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    err = max(_maxerr(a, b) for a, b in zip(g, gr))
    scale = max(float(jnp.max(jnp.abs(x))) for x in gr)
    return {"max_err": err / max(scale, 1e-6), "tol": 0.05,
            "note": "relative to max |grad|"}


def check_fused_lstm_shardmap():
    """Pallas fused LSTM (fwd+bwd) sharded over batch."""
    from deeplearning4j_tpu.ops.lstm import _cell, fused_lstm
    mesh = _mesh()
    n = len(jax.devices())
    T, B, H = 32, 4 * n, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    xw = jax.random.normal(ks[0], (T, B, 4 * H), jnp.float32) * 0.1
    rw = jax.random.normal(ks[1], (H, 4 * H), jnp.float32) * 0.05
    p = jnp.zeros((3, H), jnp.float32)
    h0 = jnp.zeros((B, H), jnp.float32)
    c0 = jnp.zeros((B, H), jnp.float32)
    mask = jnp.ones((T, B), jnp.float32)
    bspec = P(None, "data")          # [T, B, ...] and [B, H]

    def local(xw, rw, h0, c0, mask):
        return fused_lstm(xw, rw, p, h0, c0, mask, INTERPRET)[0]

    fn = jax.jit(_sm(local, mesh,
                     (P(None, "data", None), P(None, None),
                      P("data", None), P("data", None), bspec),
                     P(None, "data", None)))
    hs = fn(xw, rw, h0, c0, mask)

    def step(carry, xw_t):
        h, c = carry
        h2, c2, *_ = _cell(xw_t, h, c, rw, p)
        return (h2, c2), h2

    _, ref = jax.lax.scan(step, (h0, c0), xw)
    fwd_err = _maxerr(hs, ref)

    def loss_fused(xw, rw):
        def body(xw, rw, h0, c0, mask):
            return jnp.sum(fused_lstm(xw, rw, p, h0, c0, mask,
                                      INTERPRET)[0] ** 2,
                           keepdims=True)[None]
        per = _sm(body, mesh,
                  (P(None, "data", None), P(None, None), P("data", None),
                   P("data", None), P(None, "data")),
                  P("data"))(xw, rw, h0, c0, mask)
        return jnp.sum(per)

    g = jax.jit(jax.grad(loss_fused, argnums=(0, 1)))(xw, rw)

    def loss_ref(xw, rw):
        def step(carry, xw_t):
            h, c = carry
            h2, c2, *_ = _cell(xw_t, h, c, rw, p)
            return (h2, c2), h2
        _, hs = jax.lax.scan(step, (h0, c0), xw)
        return jnp.sum(hs ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1))(xw, rw)
    bwd_err = max(_maxerr(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-6)
                  for a, b in zip(g, gr))
    return {"max_err": max(fwd_err, bwd_err), "tol": 0.02,
            "note": "fwd abs + bwd rel"}


def check_conv_fused_shardmap():
    """Frozen-but-supported opt-in: conv1x1+BN-stats kernel under DP
    sharding (per-shard batch statistics, the local-BN convention)."""
    from deeplearning4j_tpu.ops.conv_fused import conv1x1_bn_act
    mesh = _mesh()
    n = len(jax.devices())
    B, Hh, W, C, N = 2 * n, 8, 8, 32, 64
    r = np.random.default_rng(3)
    x = jnp.asarray(r.standard_normal((B, Hh, W, C)), jnp.float32)
    w = jnp.asarray(r.standard_normal((C, N)) * 0.1, jnp.float32)
    gamma = jnp.asarray(r.random(N) + 0.5, jnp.float32)
    beta = jnp.asarray(r.standard_normal(N) * 0.1, jnp.float32)

    def local(x, w, gamma, beta):
        o, _, _ = conv1x1_bn_act(x, w, gamma, beta, train=True, relu=True,
                                 interpret=INTERPRET)
        return o

    fn = jax.jit(_sm(local, mesh,
                     (P("data", None, None, None), P(None, None),
                      P(None), P(None)),
                     P("data", None, None, None)))
    o = fn(x, w, gamma, beta)

    # per-shard oracle (local batch stats)
    outs = []
    for i in range(n):
        xs = x[i * (B // n):(i + 1) * (B // n)]
        y = jnp.einsum("bhwc,cn->bhwn", xs, w)
        m = y.mean(axis=(0, 1, 2))
        v = y.var(axis=(0, 1, 2))
        outs.append(jnp.maximum(gamma * (y - m) / jnp.sqrt(v + 1e-5)
                                + beta, 0))
    ref = jnp.concatenate(outs, axis=0)
    return {"max_err": _maxerr(o, ref), "tol": 2e-3}


def check_ring_flash():
    """ring attention with the per-shard flash path over a real seq mesh
    (1-chip: a 1-ring — still lowers the with_lse kernel + cond cases)."""
    from deeplearning4j_tpu.parallel.ring_attention import (attention,
                                                            ring_self_attention)
    mesh = _mesh("seq")
    n = len(jax.devices())
    B, T, H, D = 2, 512 * n, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32) * 0.5
               for kk in ks)
    o = ring_self_attention(q, k, v, mesh, axis="seq", causal=True,
                            use_flash=True, interpret=INTERPRET)
    ref = attention(q, k, v, causal=True)
    return {"max_err": _maxerr(o, ref), "tol": 5e-3}


def check_kv_decode():
    """Jitted KV-cache decode stepping compiles and reproduces the full
    forward on this device."""
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer
    V, T = 13, 16
    net = TextGenerationTransformer(num_classes=V, input_shape=(T, 1),
                                    d_model=32, num_heads=2,
                                    num_blocks=2).init()
    rng = np.random.default_rng(5)
    x = rng.integers(0, V, (2, T, 1)).astype(np.float32)
    full = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    outs = [np.asarray(net.rnn_time_step(x[:, :4, :]))]
    for t in range(4, T):
        outs.append(np.asarray(net.rnn_time_step(x[:, t:t + 1, :])))
    stepped = np.concatenate(outs, axis=1)
    return {"max_err": _maxerr(stepped, full), "tol": 2e-3}


def check_kv_decode_gqa_rolling():
    """The modern decode compositions — GQA (grouped einsum against the
    narrow cache) + sliding window + the mod-L ring-buffer scatter —
    compile and run on this device. Teacher-forced: BOTH models step the
    SAME 29-token sequence and the per-step probability outputs are
    compared, so an ulp-level near-tie cannot cascade into rollout
    divergence (greedy-rollout exactness is pinned by the CPU suite)."""
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer
    V, T, w = 13, 8, 4
    mk = dict(num_classes=V, input_shape=(T, 1), d_model=32, num_heads=4,
              num_kv_heads=2, num_blocks=2, pos_encoding="rope",
              norm="rms", ffn_activation="swiglu", window=w)
    roll = TextGenerationTransformer(rolling_cache=True, **mk).init()
    big = TextGenerationTransformer(max_decode=64, **mk).init()
    rng = np.random.default_rng(6)
    seq = rng.integers(0, V, (2, 29, 1)).astype(np.float32)

    def stepped(net):
        net.rnn_clear_previous_state()
        outs = [np.asarray(net.rnn_time_step(seq[:, :5]))]
        for t in range(5, seq.shape[1]):
            outs.append(np.asarray(net.rnn_time_step(seq[:, t:t + 1])))
        return np.concatenate(outs, axis=1)

    return {"max_err": _maxerr(stepped(roll), stepped(big)), "tol": 2e-3,
            "note": "teacher-forced probs, ring vs linear cache"}


CHECKS = [check_flash_fwd_shardmap, check_flash_bwd_shardmap,
          check_fused_lstm_shardmap, check_conv_fused_shardmap,
          check_ring_flash, check_kv_decode, check_kv_decode_gqa_rolling]


def main():
    enable_compile_cache()
    device = jax.devices()[0]
    only = [s for s in os.environ.get("SMOKE_ONLY", "").split(",") if s]
    names = [c.__name__.replace("check_", "") for c in CHECKS]
    unknown = [s for s in only if s not in names]
    if unknown:
        # a typo must not burn chip time on a silent no-op green
        print(json.dumps({"error": f"unknown SMOKE_ONLY entries {unknown}",
                          "known": names}))
        return 1
    results = {}
    n_fail = 0
    for check in CHECKS:
        name = check.__name__.replace("check_", "")
        if only and name not in only:
            continue
        try:
            r = check()
            r["ok"] = bool(r["max_err"] <= r["tol"])
        except Exception as e:  # noqa: BLE001 - record and continue
            r = {"ok": False,
                 "error": f"{type(e).__name__}: {e}",
                 "trace": traceback.format_exc(limit=3)}
        r["name"] = name
        r["device"] = str(device)
        r["interpret"] = INTERPRET
        n_fail += 0 if r["ok"] else 1
        print(json.dumps(r), flush=True)
        results[name] = r
    out = os.path.join(os.path.dirname(__file__),
                       "shardmap_smoke_results.json")
    prior = {}
    if os.path.exists(out):
        with open(out) as fh:
            prior = json.load(fh)
    wrote = device.platform == "tpu" or not prior
    if wrote:
        prior.update(results)
        with open(out, "w") as fh:
            json.dump(prior, fh, indent=1)
    print(json.dumps({"written": out if wrote else None,
                      "skipped_write": not wrote, "n": len(results),
                      "failures": n_fail}))
    return n_fail


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
