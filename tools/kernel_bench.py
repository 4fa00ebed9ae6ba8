"""Kernel-level TPU microbenchmarks: Pallas kernels vs their XLA baselines.

Measures, on the real chip, the head-to-head numbers for the two places
this framework hand-writes kernels instead of trusting the compiler
(SURVEY §7: "fused LSTM needs Pallas"; flash attention for long context):

  - ops/attention.flash_attention  vs  dense XLA attention
      forward (inference) and forward+backward (training), causal,
      T in {1024, 2048, 4096}
  - ops/banded_attention.banded_attention  vs  the dense band-masked
      reference: windowed GQA, T in {1024, 2048, 4096}, w = T/8
  - ops/banded_attention.banded_decode_attention  vs  the dense masked
      einsum: single-query decode over [S, L, Hkv, Dh], L in {1024, 4096}
  - ops/lstm.fused_lstm            vs  the lax.scan fallback
      forward and forward+backward

Timing uses the same two-point differential as bench.py: two chained
leg counts, scalar-only fetches, min-of-two legs, escalate step counts
until the differential dominates fetch-latency jitter.

Results: one JSON line per measurement; aggregate written to
tools/kernel_bench_results.json keyed by measurement name, carrying the
device so CPU smoke runs never overwrite TPU evidence.
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

_T0 = time.monotonic()
_TOTAL_BUDGET = float(os.environ.get("KBENCH_TIMEOUT", "1800"))
_JOB_BUDGET = float(os.environ.get("KBENCH_JOB_TIMEOUT", "240"))


def _timed_per_iter(run, n_start=8):
    """(t(n2)-t(n1))/(n2-n1) with jitter-dominance escalation."""
    job_t0 = time.monotonic()
    float(run(2))  # compile + warmup
    n1, n2 = n_start, 4 * n_start
    samples = {}

    def leg(n):
        if n not in samples:
            def one():
                t0 = time.perf_counter()
                float(run(n))
                return time.perf_counter() - t0
            samples[n] = min(one(), one())
        return samples[n]

    for _ in range(8):
        t1, t2 = leg(n1), leg(n2)
        diff = t2 - t1
        if diff >= 2.0 and diff >= 0.5 * t1:
            return diff / (n2 - n1)
        if time.monotonic() - job_t0 + 8 * t2 > _JOB_BUDGET:
            raise RuntimeError(
                f"degenerate timing: diff={diff:.4f}s over {n2 - n1} iters, "
                "no budget left to escalate")
        n1, n2 = n2, 4 * n2
    raise RuntimeError("degenerate timing after max escalation")


def _loop(body, x0):
    """Jitted run(n): n dynamic-trip-count iterations chained through the
    carry. The scalar reduces over ALL carry leaves so no leaf (and hence
    no part of the body) is dead code."""
    @jax.jit
    def run(n, x0=x0):
        out = lax.fori_loop(0, n, body, x0)
        return sum(x.astype(jnp.float32).mean()
                   for x in jax.tree_util.tree_leaves(out))
    return run


# ------------------------------------------------------------- attention
def bench_attention(t, train, flash, causal=True, block_q=512, block_k=512,
                    backward="pallas"):
    from deeplearning4j_tpu.ops.attention import (_dense_attention,
                                                  flash_attention)
    bh, d = 32, 64  # [BH, T, D] layout: no head transposes in either path
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (bh, t, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh, t, d), jnp.bfloat16)
    v = jax.random.normal(kv, (bh, t, d), jnp.bfloat16)

    if flash:
        attn = lambda q, k, v: flash_attention(q, k, v, causal, None,
                                               block_q, block_k, False,
                                               backward)
    else:
        attn = lambda q, k, v: _dense_attention(q, k, v, causal, d ** -0.5)

    if train:
        def loss(q, k, v):
            o = attn(q, k, v)
            return (o.astype(jnp.float32) ** 2).mean()
        g = jax.grad(loss, argnums=(0, 1, 2))

        def body(i, c):
            q, k, v = c
            dq, dk, dv = g(q, k, v)
            s = 1e-3
            return (q - s * dq, k - s * dk, v - s * dv)
        run = _loop(body, (q, k, v))
    else:
        def body(i, c):
            q, k, v = c
            return (attn(q, k, v), k, v)
        run = _loop(body, (q, k, v))

    per_iter = _timed_per_iter(run)
    # Useful FLOPs: 2 matmuls over the causal half; backward ~2.5x forward
    # (dense recompute pays full fwd again + bwd matmuls).
    factor = 0.5 if causal else 1.0
    fwd_flops = 4 * bh * t * t * d * factor
    flops = fwd_flops * (3.5 if train else 1.0)
    # Flash rows carry their full config both in the name (rows never
    # collide across configs) and as explicit fields (the defaults
    # updater reads fields, not name parsing, for new rows).
    blk = f"_bq{block_q}_bk{block_k}" if flash else ""
    bwd = "_bwddense" if (flash and train and backward == "dense") else ""
    r = {
        "name": f"attn_t{t}_{'train' if train else 'fwd'}_"
                f"{'flash' if flash else 'dense'}{blk}{bwd}",
        "per_iter_ms": round(per_iter * 1e3, 3),
        "tflops_per_s": round(flops / per_iter / 1e12, 2),
        "shape": f"bh{bh} t{t} d{d} causal={causal} bf16",
    }
    if flash:
        r.update(block_q=block_q, block_k=block_k)
        if train:
            r["backward"] = backward
    return r


# ------------------------------------------------------- banded attention
def bench_banded(t, window, train, banded, block_q=256, block_k=256):
    """Windowed/GQA attention: the banded Pallas kernel vs the dense
    band-masked reference (the layer's fallback path)."""
    from deeplearning4j_tpu.ops.banded_attention import (
        banded_attention, banded_reference,
    )
    b, h, hkv, d = 4, 8, 2, 64
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.bfloat16)

    if banded:
        attn = lambda q, k, v: banded_attention(q, k, v, window, True,
                                                None, block_q, block_k)
    else:
        attn = lambda q, k, v: banded_reference(q, k, v, window, True,
                                                d ** -0.5)

    if train:
        def loss(q, k, v):
            o = attn(q, k, v)
            return (o.astype(jnp.float32) ** 2).mean()
        g = jax.grad(loss, argnums=(0, 1, 2))

        def body(i, c):
            q, k, v = c
            dq, dk, dv = g(q, k, v)
            s = 1e-3
            return (q - s * dq, k - s * dk, v - s * dv)
        run = _loop(body, (q, k, v))
    else:
        def body(i, c):
            q, k, v = c
            return (attn(q, k, v), k, v)
        run = _loop(body, (q, k, v))

    per_iter = _timed_per_iter(run)
    # Useful FLOPs: the O(T*w) band only — both contenders get the same
    # numerator, so the dense side's T^2 wasted lanes show as low TFLOP/s.
    fwd_flops = 4 * b * h * t * window * d
    flops = fwd_flops * (3.5 if train else 1.0)
    blk = f"_bq{block_q}_bk{block_k}" if banded else ""
    r = {
        "name": f"battn_t{t}_w{window}_{'train' if train else 'fwd'}_"
                f"{'banded' if banded else 'dense'}{blk}",
        "per_iter_ms": round(per_iter * 1e3, 3),
        "tflops_per_s": round(flops / per_iter / 1e12, 2),
        "shape": f"b{b} t{t} w{window} h{h} hkv{hkv} d{d} causal bf16",
        "window": window,
    }
    if banded:
        r.update(block_q=block_q, block_k=block_k)
    return r


# --------------------------------------------------- single-query decode
def bench_decode(cache_len, banded, block_l=512):
    """One decode step over the KV-pool layout [S, L, Hkv, Dh]: the
    scalar-prefetch Pallas kernel vs the dense masked einsum."""
    from deeplearning4j_tpu.ops.banded_attention import (
        banded_decode_attention, decode_reference,
    )
    s, h, hkv, d = 32, 8, 2, 64
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (s, h, d), jnp.bfloat16)
    ck = jax.random.normal(kk, (s, cache_len, hkv, d), jnp.bfloat16)
    cv = jax.random.normal(kv, (s, cache_len, hkv, d), jnp.bfloat16)
    qpos = jnp.full((s,), cache_len - 1, jnp.int32)

    if banded:
        f = lambda q, ck, cv: banded_decode_attention(
            q, ck, cv, qpos, qpos, window=None, rolling=False,
            block_l=block_l)
    else:
        f = lambda q, ck, cv: decode_reference(q, ck, cv, qpos, qpos,
                                               None, False, d ** -0.5)

    def body(i, c):
        q, ck, cv = c
        o = f(q, ck, cv)
        return (q + 1e-9 * o.astype(q.dtype), ck, cv)
    run = _loop(body, (q, ck, cv))

    per_iter = _timed_per_iter(run)
    # decode is bandwidth-bound: report GB/s of cache traffic instead of
    # TFLOP/s (the per-token HBM sweep is the resource being bought)
    cache_bytes = 2 * s * cache_len * hkv * d * 2   # k+v, bf16
    blk = f"_bl{block_l}" if banded else ""
    r = {
        "name": f"dattn_l{cache_len}_{'banded' if banded else 'dense'}"
                f"{blk}",
        "per_iter_ms": round(per_iter * 1e3, 3),
        "cache_gb_per_s": round(cache_bytes / per_iter / 1e9, 2),
        "shape": f"s{s} l{cache_len} h{h} hkv{hkv} d{d} bf16",
    }
    if banded:
        r["block_l"] = block_l
    return r


# ------------------------------------------------------------------ lstm
def bench_lstm(train, fused):
    from deeplearning4j_tpu.ops.lstm import _cell, fused_lstm
    T, B, H = 256, 64, 512
    key = jax.random.PRNGKey(1)
    kx, kr = jax.random.split(key)
    xw = jax.random.normal(kx, (T, B, 4 * H), jnp.float32)
    rw = jax.random.normal(kr, (H, 4 * H), jnp.float32) * 0.01
    p = jnp.zeros((3, H), jnp.float32)
    h0 = jnp.zeros((B, H), jnp.float32)
    c0 = jnp.zeros((B, H), jnp.float32)
    mask = jnp.ones((T, B), jnp.float32)

    if fused:
        f = lambda xw, rw: fused_lstm(xw, rw, p, h0, c0, mask)[0]
    else:
        def f(xw, rw):
            def step(carry, xw_t):
                h, c = carry
                h2, c2, *_ = _cell(xw_t, h, c, rw, p)
                return (h2, c2), h2
            _, hs = lax.scan(step, (h0, c0), xw)
            return hs

    if train:
        def loss(xw, rw):
            return (f(xw, rw) ** 2).mean()
        g = jax.grad(loss, argnums=(0, 1))

        def body(i, c):
            xw, rw = c
            dxw, drw = g(xw, rw)
            return (xw - 1e-3 * dxw, rw - 1e-3 * drw)
        run = _loop(body, (xw, rw))
    else:
        def body(i, c):
            xw, rw = c
            hs = f(xw, rw)
            return (xw, rw + 1e-9 * hs.mean())
        run = _loop(body, (xw, rw))

    per_iter = _timed_per_iter(run)
    flops = T * 2 * B * H * 4 * H * (3.0 if train else 1.0)
    return {
        "name": f"lstm_{'train' if train else 'fwd'}_"
                f"{'fused' if fused else 'scan'}",
        "per_iter_ms": round(per_iter * 1e3, 3),
        "tflops_per_s": round(flops / per_iter / 1e12, 2),
        "shape": f"T{T} B{B} H{H} f32",
    }


def main():
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    results = {}
    jobs = []
    only = [s for s in os.environ.get("KBENCH_ONLY", "").split(",") if s]
    for t in (1024, 2048, 4096):
        for train in (False, True):
            for flash in (False, True):
                jobs.append(("attn", functools.partial(bench_attention, t,
                                                       train, flash)))
            if train:
                # backward ablation at the 512^2 production tiles: the
                # Pallas blockwise bwd vs the dense XLA recompute bwd
                jobs.append(("attn", functools.partial(
                    bench_attention, t, True, True, True, 512, 512,
                    "dense")))
    for bq, bk in ((128, 128), (256, 256), (512, 256), (256, 512),
                   (128, 512)):
        jobs.append(("sweep", functools.partial(
            bench_attention, 2048, False, True, True, bq, bk)))
        jobs.append(("sweeptrain", functools.partial(
            bench_attention, 2048, True, True, True, bq, bk)))
    # does the win keep growing past 512-wide tiles at longer T?
    for bq, bk in ((1024, 1024), (512, 1024), (1024, 512)):
        jobs.append(("sweep", functools.partial(
            bench_attention, 4096, False, True, True, bq, bk)))
        jobs.append(("sweeptrain", functools.partial(
            bench_attention, 4096, True, True, True, bq, bk)))
    for t in (1024, 2048, 4096):
        w = max(128, t // 8)
        for train in (False, True):
            for banded in (False, True):
                jobs.append(("banded", functools.partial(
                    bench_banded, t, w, train, banded)))
    for cache_len in (1024, 4096):
        for banded in (False, True):
            jobs.append(("decode", functools.partial(
                bench_decode, cache_len, banded)))
    for train in (False, True):
        for fused in (False, True):
            jobs.append(("lstm", functools.partial(bench_lstm, train,
                                                   fused)))
    jobs = [j for tag, j in jobs if not only or tag in only]
    for job in jobs:
        if time.monotonic() - _T0 > _TOTAL_BUDGET:
            print(json.dumps({"skipped": "budget exhausted"}))
            break
        try:
            r = job()
        except Exception as e:  # noqa: BLE001 - record and continue
            r = {"name": getattr(job, "func", job).__name__,
                 "args": str(getattr(job, "args", ())),
                 "error": f"{type(e).__name__}: {e}"}
        r["device"] = str(device)
        print(json.dumps(r), flush=True)
        if "name" in r and "error" not in r:
            results[r["name"]] = r
    out = os.path.join(os.path.dirname(__file__),
                       "kernel_bench_results.json")
    prior = {}
    if os.path.exists(out):
        with open(out) as fh:
            prior = json.load(fh)
    # TPU evidence is never overwritten by CPU smoke runs
    if device.platform == "tpu" or not prior:
        prior.update(results)
        with open(out, "w") as fh:
            json.dump(prior, fh, indent=1)
    print(json.dumps({"written": out, "n": len(results)}))


if __name__ == "__main__":
    main()
