"""Benchmark: ResNet-50 training throughput on the real TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} with
per-step latency and MFU alongside. The measurement runs in a CHILD
process (a failed TPU backend init is cached for the life of a jax
process, so retry must mean a fresh interpreter); the parent retries with
backoff and then at half the batch. The throughput child refuses to run
off the chip, and when every attempt fails the parent prints a structured
line with an "error" field and no value, and exits non-zero: a missing
chip is never answered with a CPU number.

Baseline: the reference repo publishes no numbers (BASELINE.md); the
north-star target is >=70% of reference A100 images/sec/chip for dl4j-zoo
ResNet-50 data-parallel training. We anchor on a public A100 ResNet-50
training throughput of ~2500 img/s/chip (MLPerf-era mixed precision), so
vs_baseline = value / (0.7 * 2500) — i.e. vs_baseline >= 1.0 meets the
target on a per-chip basis.

Env knobs: BENCH_MODEL=resnet50|vgg16|lstm|sentiment|inception|lenet|transformer
(BENCH_SEQ_LEN sets the transformer rung's sequence length, default 2048),
(comma-separate several to sweep the BASELINE configs, one JSON line
each), BENCH_BATCH, BENCH_STEPS, BENCH_DTYPE, BENCH_ATTEMPT_TIMEOUT (s),
BENCH_S2D=1 (space-to-depth ResNet stem, own
metric), BENCH_FUSED=1 (Pallas conv-epilogue fusion, own
metric), BENCH_PROFILE=<dir> (jax.profiler trace of post-warmup steps),
BENCH_STEPS_PER_DISPATCH (recorded in the JSON; sets K for
`--host-overhead`). `python bench.py --host-overhead` (or
BENCH_HOST_OVERHEAD=1) skips the ladder and measures per-step host
overhead of the fit hot path with forced per-step sync vs deferred loss
sync vs K-step fused dispatch (see _host_overhead_main).
`python bench.py --serving` (or BENCH_SERVING=1) drives the REAL
model-serving HTTP server with a closed-loop client pool, comparing the
continuous-batching scheduler against the legacy collect-then-run loop
(throughput + p50/p95/p99 + batch occupancy, reconciled against
/metrics); writes BENCH_serving.json (see _serving_main; knobs:
BENCH_SERVING_CLIENTS/SECS/ROWS/MAX_BATCH/OUT). The serving modes run
on the platform JAX resolved and print it.
`python bench.py --serving-decode` (or BENCH_SERVING_DECODE=1) runs the
closed-loop prompt→stream decode workload against POST /generate, one
leg per fused-decode K (default K∈{1,4,8}): tokens/sec + round
trips/token + p99 TTFT/ITL reconciled against the /metrics decode
section, zero-recompiles-after-warmup and cross-K greedy parity
asserted; writes BENCH_serving_decode.json (see _serving_decode_main;
knobs: BENCH_DECODE_CLIENTS/ROUNDS/MAX_TOKENS/PROMPT/PREFILL_CHUNK/
KS/OUT).
`python bench.py --serving-fleet` (or BENCH_SERVING_FLEET=1) drives the
FleetRouter over N replica PROCESSES: closed-loop 1→N replica scaling
with router-vs-replica /metrics reconciled exactly, a disaggregated
prefill→handoff→decode greedy-parity probe, and a forced SLO breach →
drain + reroute with zero failed in-flight streams; writes
BENCH_serving_fleet.json (see _serving_fleet_main; knobs:
BENCH_FLEET_REPLICAS/CLIENTS/ROUNDS/MAX_TOKENS/PROMPT/OUT).
`python bench.py --sharding` (or BENCH_SHARDING=1) profiles the GSPMD
sharding spine on a forced-8-device CPU mesh: per-device param +
optimizer-moment bytes replicated vs sharded, syncs/step, post-warmup
recompiles; writes BENCH_sharding.json (see _sharding_main; knobs:
BENCH_SHARDING_OUT/HIDDEN).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

A100_REF_IMG_S = 2500.0
TARGET_FRACTION = 0.70

# process birth, so the adaptive-timing deadline accounts for however
# long compile+warmup already took before timing started
_PROC_T0 = time.monotonic()

# child exit code for "timing differential never dominated latency noise"
# — deterministic for a given noise level, so the ladder must NOT treat
# it like a flaky backend init (no backoff-retry spiral, no batch-halving
# which only shortens steps and makes the condition harder)
_RC_DEGENERATE_TIMING = 17
# child exit code for "JAX resolved no TPU": deterministic, so the ladder
# stops at once instead of retrying through its backoffs
_RC_NO_CHIP = 18

# Peak dense bf16 matmul throughput per chip, FLOP/s (public spec sheets).
_PEAK_FLOPS = (
    ("v6", 918e12),       # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e device_kind is "TPU v5 lite"
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _peak_flops(device_kind: str):
    kind = device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    return None


def _compile(fn, donate, *args):
    """AOT-compile a jitted step once; return (callable, flops_per_step).

    Using the AOT executable for BOTH cost analysis and execution avoids a
    second trace/compile, and cost_analysis gives the exact HLO flop count
    for the MFU figure (PerformanceListener.java:24-60 is the reference's
    measurement seam; MFU is the TPU-native extension of it).
    """
    import jax

    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    flops = None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0)) or None
    except Exception:
        pass
    return compiled, flops


# Host-sync accounting for the emitted JSON: _timed_ips fetches ONE scalar
# loss per timing leg (that is the sync), so host_sync_per_step = legs/steps
# — the dispatch-depth evidence mirrored by tests/test_perf_guard.py.
_SYNC_STATS = {"syncs": 0, "steps": 0}


def _timed_ips(run, batch: int, steps: int):
    """Two-point timing that differences out a constant per-fetch
    latency: run N1 and N2 chained steps, force completion by fetching
    only the SCALAR loss each time, and take
    per_step = (t2 - t1) / (N2 - N1).

    BENCH_PROFILE=<dir>: capture a jax.profiler trace of a few post-warmup
    steps into <dir> (the utils/profiling.py seam, for MFU analysis)."""
    loss = run(3)           # compile + warmup
    _ = float(loss)
    _SYNC_STATS["syncs"] += 1
    _SYNC_STATS["steps"] += 3
    prof_dir = os.environ.get("BENCH_PROFILE")
    if prof_dir:
        from deeplearning4j_tpu.utils.profiling import trace

        with trace(prof_dir):
            _ = float(run(3))
    n1 = max(2, steps // 4)
    # n2 = 4*n1 keeps the dominance condition below structurally
    # reachable (diff scales with n2-n1 = 3*n1 while the latency
    # constant does not) AND lets each escalation round reuse the
    # previous round's n2 samples as its n1 samples
    n2 = max(steps, 4 * n1)
    last_loss = [0.0]

    def _leg(n):
        t0 = time.perf_counter()
        last_loss[0] = float(run(n))
        _SYNC_STATS["syncs"] += 1
        _SYNC_STATS["steps"] += n
        return time.perf_counter() - t0

    samples = {}

    def _timed(n):
        if n not in samples:
            samples[n] = min(_leg(n), _leg(n))
        return samples[n]

    # Adaptive: with sub-ms steps the differential t(n2)-t(n1) can be
    # smaller than the jitter of a slow host fetch (hundreds of ms),
    # which once produced a nonsense 32e9-seq/s record. Each leg count
    # is timed twice and min-filtered (jitter only ever ADDS time), and
    # the step counts are scaled until the differential dominates the
    # constant latency term. The deadline keeps the escalation's own
    # cost inside the child's attempt timeout, so persistent jitter
    # surfaces as this diagnostic, not as a killed child that the
    # ladder would misread as a hung backend. Anchored at PROCESS start
    # (_PROC_T0): compile+warmup already spent part of the attempt
    # budget before timing began.
    deadline = _PROC_T0 + 0.85 * float(
        os.environ.get("BENCH_ATTEMPT_TIMEOUT", "600"))
    for _ in range(6):
        t1 = _timed(n1)
        t2 = _timed(n2)
        diff, denom = t2 - t1, n2 - n1
        # absolute floor AND relative dominance: a slow host fetch's
        # latency varies by ~0.1-1s between legs even after the
        # min-of-two filter, so a differential under ~2s can still be
        # mostly that variance (observed: a 0.9ms/step acceptance for a
        # true 3.1ms/step model); requiring diff >= 2s bounds the
        # latency-variance error at roughly half, and >= 0.5*t1 keeps
        # the constant term from dominating
        if diff >= 2.0 and diff >= 0.5 * t1:
            break
        # next round costs ~two legs of 4*n2 (n2's samples are reused)
        if time.monotonic() + 8 * t2 > deadline:
            raise RuntimeError(
                f"degenerate timing: diff={diff:.4f}s over {denom} "
                "steps and no time budget left to escalate further "
                "(latency noise exceeded compute signal)")
        n1, n2 = n2, 4 * n2
    else:
        # never reached dominance — a positive diff here is still mostly
        # jitter; refuse to record it as a measurement
        raise RuntimeError(
            f"degenerate timing: diff={diff:.4f}s over {denom} steps "
            "(latency noise exceeded compute signal after 1024x scaling)")
    l2 = last_loss[0]
    per_step = diff / denom
    return batch / per_step, per_step, l2


def _bench_resnet50(batch: int, steps: int, dtype: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.optim.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    extra = {"stem": "s2d"} if os.environ.get("BENCH_S2D") else {}
    if os.environ.get("BENCH_FUSED"):  # Pallas conv-epilogue fusion
        extra["fused"] = True          # (ops/conv_fused.py)
    model = ResNet50(num_classes=1000, input_shape=(224, 224, 3),
                     updater=Nesterovs(0.1, 0.9), **extra)
    conf = dataclasses.replace(model.conf(), dtype=dtype)
    from deeplearning4j_tpu.models import ComputationGraph

    net = ComputationGraph(conf).init()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)), net.dtype)
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        {"input": x}, {"output": y}, None, None, key)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                {"input": x}, {"output": y}, None, None, key)[:4]
        return loss

    return _timed_ips(run, batch, steps) + (flops,)


def _bench_lenet(batch: int, steps: int, dtype: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.models import MultiLayerNetwork

    conf = dataclasses.replace(LeNet().conf(), dtype=dtype)
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 784)), net.dtype)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        x, y, None, None, key, None)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                x, y, None, None, key, None)[:4]
        return loss

    return _timed_ips(run, batch, steps) + (flops,)


def _bench_lstm(batch: int, steps: int, dtype: str):
    """GravesLSTM language-model-style step with the fused Pallas kernel
    (BASELINE config #3's RNN path; reference precedent: LSTMHelpers)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.recurrent import (
        GravesLSTM, RnnOutputLayer,
    )
    from deeplearning4j_tpu.optim.updaters import Adam

    T, F, H, C = 128, 128, 512, 64
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(0).updater(Adam(1e-3)).activation("tanh")
         .list(GravesLSTM(n_out=H), GravesLSTM(n_out=H),
               RnnOutputLayer(n_out=C, activation="softmax"))
         .set_input_type(InputType.recurrent(F))
         .build())).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, T, F)), jnp.float32)
    y = jnp.asarray(np.eye(C, dtype=np.float32)[
        rng.integers(0, C, (batch, T))])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        x, y, None, None, key, None)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                x, y, None, None, key, None)[:4]
        return loss

    return _timed_ips(run, batch, steps) + (flops,)


def _bench_vgg16(batch: int, steps: int, dtype: str):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import VGG16

    model = VGG16(num_classes=1000, input_shape=(224, 224, 3),
                  updater=Nesterovs(0.01, 0.9))
    conf = dataclasses.replace(model.conf(), dtype=dtype)
    net = (ComputationGraph(conf).init() if hasattr(conf, "vertices")
           else MultiLayerNetwork(conf).init())
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)), net.dtype)
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    graph = hasattr(conf, "vertices")
    feats = {"input": x} if graph else x
    labs = {"output": y} if graph else y
    extra = () if graph else (None,)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        feats, labs, None, None, key, *extra)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                feats, labs, None, None, key, *extra)[:4]
        return loss

    return _timed_ips(run, batch, steps) + (flops,)


def _bench_sentiment(batch: int, steps: int, dtype: str):
    """BASELINE config #3: Word2Vec-embedded sequences -> LSTM -> global
    max-pool -> binary sentiment head, with per-timestep feature masks
    (the reference's Word2VecSentimentRNN example shape: 300-d vectors,
    ~256-step reviews)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (
        GlobalPoolingLayer, OutputLayer,
    )
    from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM
    from deeplearning4j_tpu.optim.updaters import Adam

    T, F, H, C = 256, 300, 256, 2
    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(0).updater(Adam(2e-3)).activation("tanh")
         .list(GravesLSTM(n_out=H),
               GlobalPoolingLayer(pooling="max"),
               OutputLayer(n_out=C, activation="softmax"))
         .set_input_type(InputType.recurrent(F))
         .build())).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, T, F)), jnp.float32)
    lens = rng.integers(T // 4, T, batch)
    fmask = jnp.asarray(
        (np.arange(T)[None, :] < lens[:, None]).astype(np.float32))
    y = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, batch)])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        x, y, fmask, None, key, None)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                x, y, fmask, None, key, None)[:4]
        return loss

    return _timed_ips(run, batch, steps) + (flops,)


def _inception_h5_path() -> str:
    """Generate (once, cached) a full-channel-width InceptionV3 .h5 via
    the genuine-topology builder (tests/keras_fixtures.py — 94 Conv2D +
    94 BN, asymmetric 7x1/1x7 branches, nested concats)."""
    from deeplearning4j_tpu.data.datasets import data_dir

    dest = os.path.join(data_dir(), "bench", "inception_v3_s2.h5")
    if not os.path.exists(dest):
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tests"))
        try:
            from keras_fixtures import make_inception_v3_h5
        finally:
            sys.path.pop(0)
        # scale=2 halves channel widths: full 299x299 topology, ~6M
        # params — keeps one-time h5 generation under a minute.
        # Write-then-rename so a killed generation can't poison the cache.
        tmp = dest + ".tmp"
        make_inception_v3_h5(tmp, scale=2, classes=1000, input_size=299)
        os.replace(tmp, dest)
    return dest


def _bench_inception(batch: int, steps: int, dtype: str):
    """BASELINE config #4: Keras modelimport InceptionV3 .h5 -> graph ->
    inference throughput on TPU (the import-path capability: the
    reference zoo serves imported Keras models for inference)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.keras_import import (
        import_keras_model_and_weights,
    )

    net = import_keras_model_and_weights(_inception_h5_path())
    rng = np.random.default_rng(0)
    # imported weights keep their own dtype (f32 import fidelity)
    x = jnp.asarray(rng.standard_normal((batch, 299, 299, 3)), net.dtype)
    in_name = net.conf.network_inputs[0]

    def fwd(params, states, feats):
        values, _, _ = net._forward(params, states, feats,
                                    train=False, rng=None)
        return values[net.conf.network_outputs[0]]

    fwd_c, flops = _compile(fwd, (), net.params_tree, net.state_tree,
                            {in_name: x})

    def run(n):
        out = None
        for _ in range(n):
            out = fwd_c(net.params_tree, net.state_tree, {in_name: x})
        return jnp.max(out)

    return _timed_ips(run, batch, steps) + (flops,)


def _bench_transformer(batch: int, steps: int, dtype: str):
    """GPT-style causal transformer LM train step at long T — the
    long-context rung (charter extension; no reference counterpart).
    The attention core follows the measured-winner policy
    (`ops/kernel_defaults.attention_policy`): XLA dense or the Pallas
    flash kernel with the blockwise FlashAttention-2 backward, whichever
    the recorded rows say wins at this T (env hatches DL4J_TPU_ATTN* run
    the ablation — each forced configuration gets its own metric name).
    Rate is tokens/sec (= sequences/sec * T). MFU caveat: HLO
    cost_analysis cannot see inside pallas_call, so when flash engages
    the attention share of FLOPs is missing from the mfu field (same
    caveat as the fused-conv rungs, PERF_NOTES)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer

    T = int(os.environ.get("BENCH_SEQ_LEN", "2048"))
    conf = _dc.replace(
        TextGenerationTransformer(input_shape=(T, 1), d_model=512,
                                  num_heads=8, num_blocks=6).conf(),
        dtype=dtype)
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (batch, T, 1)), jnp.float32)
    y = jnp.asarray(np.eye(256, dtype=np.float32)[
        rng.integers(0, 256, (batch, T))])
    state = [net.params_tree, net.updater_state, net.state_tree]
    key = jax.random.PRNGKey(0)
    step_fn, flops = _compile(
        net.make_step_fn(), (0, 1, 2),
        state[0], state[1], state[2], jnp.asarray(0, jnp.int32),
        x, y, None, None, key, None)

    def run(n):
        loss = None
        for i in range(n):
            state[0], state[1], state[2], loss = step_fn(
                state[0], state[1], state[2], jnp.asarray(i, jnp.int32),
                x, y, None, None, key, None)[:4]
        return loss

    # tokens/sec: hand _timed_ips the token count per step as the rate unit
    return _timed_ips(run, batch * T, steps) + (flops,)


def _metric_name(model: str) -> str:
    """Metric key for a model, shared by the child AND the ladder's
    degraded/failure paths so every record of one experiment carries one
    name. The s2d stem experiment gets its own metric so it can't mask
    the standard-stem record in bench_last_tpu.json."""
    metric = _BENCHES.get(model, _BENCHES["resnet50"])[1]
    if model == "resnet50":
        tag = ""
        if os.environ.get("BENCH_S2D"):
            tag += "_s2d"
        if os.environ.get("BENCH_FUSED"):
            tag += "_fused"
        if tag:
            return f"resnet50{tag}_train_images_per_sec_per_chip"
    if model == "transformer":
        forced = os.environ.get("DL4J_TPU_ATTN", "").strip().lower()
        if forced in ("flash", "dense"):
            # ablation runs must not overwrite the production-config
            # record in bench_last_tpu.json (keyed by metric)
            return f"transformer_train_tokens_per_sec_attn{forced}"
    return metric


# per-model batch ceilings (memory/compile-time bounds), shared by the
# child and the fallback-ladder planner so degrade rungs actually degrade
_BATCH_CAPS = {"lstm": 64, "vgg16": 128, "sentiment": 32, "inception": 32,
               "transformer": 8}
_FIXED_DTYPE = {"lstm": "float32", "sentiment": "float32",
                "inception": "float32"}

_BENCHES = {
    "resnet50": (_bench_resnet50, "resnet50_train_images_per_sec_per_chip",
                 "images/sec", TARGET_FRACTION * A100_REF_IMG_S),
    "vgg16": (_bench_vgg16, "vgg16_train_images_per_sec_per_chip",
              "images/sec", TARGET_FRACTION * 1100.0),  # A100 VGG16 ~1100
    "lstm": (_bench_lstm, "lstm_train_sequences_per_sec",
             "sequences/sec", 100.0),   # no published reference; nominal
    "sentiment": (_bench_sentiment,
                  "w2v_lstm_sentiment_train_sequences_per_sec",
                  "sequences/sec", 100.0),  # nominal (config #3)
    "inception": (_bench_inception,
                  "keras_inception_v3_inference_images_per_sec",
                  "images/sec", 1000.0),    # nominal (config #4)
    "lenet": (_bench_lenet, "lenet_mnist_train_images_per_sec",
              "images/sec", 10000.0),   # no published reference; nominal
    "transformer": (_bench_transformer, "transformer_train_tokens_per_sec",
                    "tokens/sec", 100000.0),  # nominal (charter extension)
}


def _child_main():
    """One measurement in THIS process; prints detailed JSON on success."""
    import jax

    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: the throughput metrics are device metrics and JAX "
              f"resolved platform {dev.platform!r}; nothing was measured",
              file=sys.stderr)
        sys.exit(_RC_NO_CHIP)
    enable_compile_cache()

    model = os.environ.get("BENCH_MODEL", "resnet50")
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "40"))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")

    bench_fn, _, unit, anchor = _BENCHES[model]
    metric = _metric_name(model)
    if model in _BATCH_CAPS:
        batch = min(batch, _BATCH_CAPS[model])

    try:
        ips, per_step, loss, flops = bench_fn(batch, steps, dtype)
    except RuntimeError as e:
        if "degenerate timing" in str(e):
            print(str(e), file=sys.stderr)
            sys.exit(_RC_DEGENERATE_TIMING)
        raise
    # models that fix their own precision regardless of BENCH_DTYPE:
    # lstm/sentiment build float32 nets, inception keeps imported weights
    dtype = _FIXED_DTYPE.get(model, dtype)
    peak = _peak_flops(getattr(dev, "device_kind", ""))
    mfu = (flops / per_step / peak) if (flops and peak) else None
    print(json.dumps({
        "metric": metric,
        "value": round(ips, 2),
        "unit": unit,
        "vs_baseline": round(ips / anchor, 4),
        "per_step_ms": round(per_step * 1e3, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_step": flops,
        "batch": batch,
        "dtype": dtype,
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "final_loss": round(loss, 4),
        # async-dispatch evidence: scalar fetches per executed step in the
        # measured loop, and the dispatch fusion factor in effect
        "host_sync_per_step": (
            round(_SYNC_STATS["syncs"] / _SYNC_STATS["steps"], 6)
            if _SYNC_STATS["steps"] else None),
        "steps_per_dispatch": int(
            os.environ.get("BENCH_STEPS_PER_DISPATCH", "1")),
        "registry": _registry_snapshot(),
        # device-truth telemetry: one DeviceMonitor sample (HBM
        # in-use/peak/limit on TPU; live-array counts everywhere)
        "devices": _devices_summary(),
    }))


def _devices_summary():
    try:
        from deeplearning4j_tpu.observe.devicemon import (
            device_memory_summary,
        )
        return device_memory_summary()
    except Exception:
        return None


def _registry_snapshot():
    """The process-wide MetricsRegistry snapshot embedded in the BENCH
    blob (compile counts, ETL/prefetch series, listener gauges) —
    `python -m deeplearning4j_tpu.observe.dump BENCH_*.json` renders it."""
    try:
        from deeplearning4j_tpu.observe import get_registry
        return get_registry().snapshot()
    except Exception:
        return None


def _attempt_plans():
    """Ordered (env-overrides, label) attempts: a flaky backend init gets
    one fresh-process retry, then half the batch. Every attempt needs the
    chip."""
    model = os.environ.get("BENCH_MODEL", "resnet50")
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    batch = min(batch, _BATCH_CAPS.get(model, batch))  # label = real batch
    plans = [
        ({}, f"{model} b{batch}"),
        ({}, f"{model} b{batch} retry"),
    ]
    half = max(8, batch // 2)
    if half < batch:        # a capped model at its floor has no half rung
        plans.append(({"BENCH_BATCH": str(half)}, f"{model} b{half}"))
    return plans


_LAST_TPU_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "bench_last_tpu.json")


def _record_last_tpu(result):
    """Persist the last REAL-TPU measurement PER METRIC (tracked in git on
    purpose, carried across checkouts; keying by metric keeps one model's
    run from masquerading as another's baseline — variants like the s2d
    stem carry their own metric name for the same reason). Atomic replace so
    a crash can't truncate the file."""
    try:
        blob = {k: result[k] for k in
                ("metric", "value", "unit", "vs_baseline",
                 "per_step_ms", "mfu", "batch", "device")
                if k in result}
        blob["recorded_at_unix"] = time.time()
        records = _load_tpu_records()
        prev = records.get(blob["metric"])
        # in-tree perf regression guard (reference precedent:
        # BenchmarkDataSetIterator throughput fixtures): a new TPU
        # measurement >5% below the carried record is flagged loudly on
        # stderr AND in the record itself — the carried value keeps the
        # best measurement so a flaky slow run can't lower the bar
        if prev and "value" in prev and prev["value"] > 0:
            # compare against the best value ever carried, not just the
            # last record — otherwise repeated sub-5% drops could ratchet
            # the bar down without ever flagging
            best = max(prev["value"], prev.get("best_value", 0.0))
            ratio = blob["value"] / best
            if ratio < 0.95:
                blob["regression_vs_best"] = round(ratio, 4)
                print(f"[bench] PERF REGRESSION: {blob['metric']} "
                      f"{blob['value']:.1f} is {100 * (1 - ratio):.1f}% "
                      f"below the carried TPU record {best:.1f}",
                      file=sys.stderr)
                records[blob["metric"] + "__regressed"] = blob
                blob = prev  # keep the best verified record
            else:
                blob["best_value"] = max(blob["value"], best)
                records.pop(blob["metric"] + "__regressed", None)
        records[blob["metric"]] = blob
        tmp = _LAST_TPU_FILE + ".tmp"
        with open(tmp, "w") as f:
            json.dump(records, f)
        os.replace(tmp, _LAST_TPU_FILE)
    except OSError:
        pass


_HISTORY_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_history.jsonl")


def _append_history(mode, summary):
    """One compact timestamped row per bench invocation, appended to
    BENCH_history.jsonl (every mode, every run — unlike the per-mode
    BENCH_*.json blobs, which only keep the latest). tools/dash.py
    --bench renders the trajectory from these rows."""
    row = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "mode": mode}
    for k in ("metric", "value", "unit", "vs_baseline", "mfu", "batch",
              "config", "platform", "device", "devices",
              "opt_state_shard_factor", "throughput_ratio", "fused_k",
              "speedup_vs_stepwise", "greedy_parity"):
        v = summary.get(k)
        if v is not None and not isinstance(v, (dict, list)):
            row[k] = v
    # per-K decode legs trend as a compact nested list (tools/dash.py
    # ignores keys it doesn't render)
    if isinstance(summary.get("legs"), list):
        row["legs"] = [
            {"k": leg.get("fused_k"),
             "tokens_per_s": leg.get("tokens_per_s"),
             "round_trips_per_token": leg.get("round_trips_per_token"),
             "itl_p99_ms": (leg.get("itl_ms") or {}).get("p99")}
            for leg in summary["legs"]]
    # the {spec on/off} x {native, int8 KV} matrix trends per leg too
    if isinstance(summary.get("spec_matrix"), list):
        row["spec_matrix"] = [
            {"spec": leg.get("spec"), "kv": leg.get("kv_dtype"),
             "k": leg.get("spec_k") or leg.get("fused_k"),
             "tokens_per_s": leg.get("tokens_per_s"),
             "acceptance_rate": leg.get("acceptance_rate"),
             "slots_factor": leg.get("slots_per_chip_factor")}
            for leg in summary["spec_matrix"]]
    # serving-fleet rows: replica count, reroutes/handoffs/migrations,
    # fleet p99 + the 1→N scaling ratio (tools/dash.py fleet panel)
    if isinstance(summary.get("fleet"), dict):
        fl = summary["fleet"]
        row["fleet"] = {k: fl.get(k) for k in (
            "replicas", "reroutes", "handoffs", "migrations",
            "slo_drains", "ttft_p99_ms", "scaling", "reconciled",
            "scrape_age_s", "stale_replicas", "slo_burn")}
    if isinstance(summary.get("scale_legs"), list):
        row["scale_legs"] = [
            {"replicas": leg.get("replicas"),
             "tokens_per_s": leg.get("tokens_per_s"),
             "ttft_p99_ms": (leg.get("ttft_ms") or {}).get("p99"),
             "reconciled": leg.get("metrics_reconciled")}
            for leg in summary["scale_legs"]]
    if isinstance(summary.get("spec"), dict):
        for key in ("tokens_per_s", "acceptance_rate",
                    "speedup_vs_stepwise"):
            v = summary["spec"].get(key)
            if v is not None:
                row["spec_" + key] = v
    # the comm ledger trends as flat comm_* scalars (the dash comm
    # panel reads comm_step_all_reduce_bytes / comm_reconciled)
    if isinstance(summary.get("comm_ledger"), dict):
        cl = summary["comm_ledger"]
        for key, hk in (("measured_step_all_reduce_bytes",
                         "comm_step_all_reduce_bytes"),
                        ("reconciliation_error", "comm_rec_error"),
                        ("reconciled", "comm_reconciled")):
            v = cl.get(key)
            if v is not None:
                row[hk] = v
    # the shared-prefix cache trends as flat prefix_* scalars (the
    # dash sparkline reads prefix_hit_rate / prefix_ttft_speedup)
    if isinstance(summary.get("prefix"), dict):
        for key in ("ttft_speedup", "hit_rate", "cow_forks",
                    "evicted_pages", "no_overlap_ttft_ratio"):
            v = summary["prefix"].get(key)
            if v is not None:
                row["prefix_" + key] = v
    for k, sub in (("ttft_p99_ms", ("ttft_ms", "p99")),
                   ("itl_p99_ms", ("itl_ms", "p99")),
                   ("continuous_p99_ms", ("modes", "continuous",
                                          "p99_ms")),
                   ("continuous_rps", ("modes", "continuous",
                                       "throughput_rps"))):
        v = summary
        for part in sub:
            v = v.get(part) if isinstance(v, dict) else None
        if v is not None:
            row[k] = v
    if summary.get("error"):
        row["error"] = True
    try:
        with open(_HISTORY_FILE, "a") as f:
            f.write(json.dumps(row) + "\n")
    # graft: allow(GL403): history is advisory; never fail the bench
    # over an unwritable artifact dir
    except OSError:
        pass


def _load_tpu_records():
    try:
        with open(_LAST_TPU_FILE) as f:
            blob = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if "metric" in blob:      # legacy single-record layout
        return {blob["metric"]: blob}
    return blob


def _load_last_tpu(metric):
    return _load_tpu_records().get(metric)


def _host_overhead_main():
    """`--host-overhead` mode: per-step wall time of the fit hot path in a
    host-overhead-dominated regime (a tiny MLP, where device compute is
    negligible and dispatch + scalar fetches are the cost). The legs drive
    the network's REAL fit-path step methods on pre-built same-shape
    batches, so ETL/iterator cost — which the prefetch iterators address
    separately and which is identical across modes — stays out of the
    comparison:

      sync      — `float(net._fit_batch(ds))` every step: the pre-async
                  behaviour, one forced host round-trip per step
      deferred  — `net._fit_batch(ds)` only (loss stays on device), one
                  block at the end: the default executor path
      fused     — `net._fused_dispatch(...)` in K-step lax.scan chunks:
                  the opt-in `steps_per_dispatch=K` path
      floor     — ONE scan over all steps: a single host dispatch for the
                  whole run, i.e. (approximately) pure device compute

    Host overhead per step is (wall − floor); `host_overhead_reduction`
    = (sync − floor) / (fused − floor) — how much of the per-step host
    cost the pipelined path removes. Emits one JSON line like the
    throughput modes so the win lands in the bench trajectory."""
    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.optim.updaters import Sgd

    batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = int(os.environ.get("BENCH_STEPS", "256"))
    k = int(os.environ.get("BENCH_STEPS_PER_DISPATCH", "8"))
    steps -= steps % k          # keep every mode at the same step count
    rng = np.random.default_rng(0)
    dss = [DataSet(rng.standard_normal((batch, 16)).astype(np.float32),
                   np.eye(4, dtype=np.float32)[rng.integers(0, 4, batch)])
           for _ in range(steps)]

    def build():
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(0.01))
                .list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(16)).build())
        return MultiLayerNetwork(conf).init()

    def measure(mode, kk=k):
        net = build()
        if mode in ("fused", "floor"):
            net._fused_dispatch(dss[:kk])        # compile the scan
        else:
            net._fit_batch(dss[0])
        jax.block_until_ready(net.params_tree)
        best = float("inf")
        for _ in range(3):                       # jitter only adds time
            t0 = time.perf_counter()
            if mode == "sync":
                for ds in dss:
                    float(net._fit_batch(ds))
            elif mode == "deferred":
                for ds in dss:
                    net._fit_batch(ds)
            else:
                for i in range(0, steps, kk):
                    net._fused_dispatch(dss[i:i + kk])
            jax.block_until_ready(net.params_tree)
            best = min(best, (time.perf_counter() - t0) / steps * 1e3)
        return best

    sync_ms = measure("sync")
    deferred_ms = measure("deferred")
    fused_ms = measure("fused")
    floor_ms = measure("floor", steps)

    def overhead(ms):
        return max(ms - floor_ms, 0.0)

    def reduction(ms):
        denom = overhead(ms)
        return round(overhead(sync_ms) / denom, 3) if denom > 0 else None

    # tie the JSON to the real fit() loop: host syncs per step as the
    # LossTracker counts them through a default (deferred) fit
    net = build()
    feats = np.concatenate([d.features for d in dss[:32]])
    labs = np.concatenate([d.labels for d in dss[:32]])
    net.fit(feats, labs, batch_size=batch, epochs=2)
    tracked = net._loss_tracker

    from deeplearning4j_tpu.observe.devicemon import device_memory_summary
    t0 = time.perf_counter()
    devices = device_memory_summary()
    devicemon_sample_ms = (time.perf_counter() - t0) * 1e3

    dev = jax.devices()[0]
    out = {
        "metric": "host_overhead",
        "unit": "ms/step",
        "value": round(overhead(fused_ms), 4),
        "batch": batch,
        "steps": steps,
        "steps_per_dispatch": k,
        "sync_ms_per_step": round(sync_ms, 4),
        "deferred_ms_per_step": round(deferred_ms, 4),
        "fused_ms_per_step": round(fused_ms, 4),
        "compute_floor_ms_per_step": round(floor_ms, 4),
        "host_overhead_ms_per_step": {
            "sync": round(overhead(sync_ms), 4),
            "deferred": round(overhead(deferred_ms), 4),
            "fused": round(overhead(fused_ms), 4),
        },
        "host_overhead_reduction": reduction(fused_ms),
        "host_overhead_reduction_deferred_only": reduction(deferred_ms),
        "host_sync_per_step": {
            "sync": 1.0,
            "deferred_fit": round(
                tracked.host_syncs / max(1, tracked.updates), 6),
        },
        "telemetry": {
            "devicemon_sample_ms": round(devicemon_sample_ms, 3),
        },
        "devices": devices,
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "registry": _registry_snapshot(),
    }
    _append_history("host-overhead", out)
    print(json.dumps(out))


def _serving_main():
    """`--serving` mode: a closed-loop HTTP client pool against the real
    model-serving server, once per scheduling mode:

      collect    — the legacy fixed collect-then-run loop
                   (ParallelInference BATCHED, max_wait_ms collector)
      continuous — the control plane's continuous-batching scheduler
                   (requests join the next dispatch as soon as the
                   device slot frees; no wait timer)

    Closed loop means every client immediately re-issues after each
    response, so both modes face the same offered load and the p50/95/99
    comparison is at (approximately) equal throughput. Client-side
    request counts are reconciled against the server's /metrics totals
    — the observability acceptance check. Emits one JSON line AND
    writes BENCH_serving.json (BENCH_SERVING_OUT overrides)."""
    import jax

    import threading
    import urllib.request

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.serving import InferenceServer

    clients = int(os.environ.get("BENCH_SERVING_CLIENTS", "8"))
    secs = float(os.environ.get("BENCH_SERVING_SECS", "6"))
    rows = int(os.environ.get("BENCH_SERVING_ROWS", "1"))
    max_batch = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "32"))
    buckets = [1, 4, 8, 16, 32]

    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=8, activation="softmax"))
            .set_input_type(InputType.feed_forward(16)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    payload = json.dumps({
        "ndarray": rng.standard_normal((rows, 16)).tolist()}).encode()

    def post(port, path="/output", data=payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def pct(sorted_ms, q):
        return round(sorted_ms[min(len(sorted_ms) - 1,
                                   int(q * len(sorted_ms)))], 3)

    modes = {}
    for mode in ("collect", "continuous"):
        srv = InferenceServer(net, port=0, scheduler=mode,
                              max_batch_size=max_batch,
                              batch_buckets=buckets, collect_wait_ms=5.0,
                              queue_capacity=max(64, 8 * clients))
        port = srv.start()
        n_warm = 2 * len(buckets)
        for _ in range(n_warm):            # compile every bucket path
            post(port)
        lat_ms = []
        counts = [0] * clients
        lock = threading.Lock()
        t_end = time.monotonic() + secs

        def client(i):
            mine = []
            while time.monotonic() < t_end:
                t0 = time.perf_counter()
                post(port)
                mine.append((time.perf_counter() - t0) * 1e3)
            with lock:
                lat_ms.extend(mine)
                counts[i] = len(mine)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            metrics = json.loads(r.read())
        srv.stop()
        total = sum(counts)
        lat_ms.sort()
        served = metrics["requests"]["completed"]
        modes[mode] = {
            "requests": total,
            "throughput_rps": round(total / wall, 2),
            "p50_ms": pct(lat_ms, 0.50),
            "p95_ms": pct(lat_ms, 0.95),
            "p99_ms": pct(lat_ms, 0.99),
            "mean_ms": round(sum(lat_ms) / len(lat_ms), 3),
            "mean_batch_occupancy_rows":
                metrics["batch"]["mean_occupancy_rows"],
            "occupancy_histogram":
                metrics["batch"]["occupancy_histogram"],
            "metrics_completed": served,
            "metrics_reconciled": served == total + n_warm,
        }

    import jax as _jax

    dev = _jax.devices()[0]
    p99_ratio = (modes["collect"]["p99_ms"]
                 / modes["continuous"]["p99_ms"])
    out = {
        "metric": "serving_continuous_vs_collect_p99_speedup",
        "value": round(p99_ratio, 3),
        "unit": "x",
        "vs_baseline": round(p99_ratio, 3),   # >1: continuous wins p99
        "clients": clients,
        "rows_per_request": rows,
        "duration_s": secs,
        "max_batch_size": max_batch,
        "throughput_ratio": round(
            modes["continuous"]["throughput_rps"]
            / modes["collect"]["throughput_rps"], 3),
        "modes": modes,
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "registry": _registry_snapshot(),
    }
    dest = os.environ.get("BENCH_SERVING_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_serving.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    _append_history("serving", out)
    print(json.dumps(out))


def _serving_decode_main():
    """`--serving-decode` mode: closed-loop prompt→stream workload
    against POST /generate — N concurrent clients, each opening a
    session, reading its SSE token stream to completion, and
    immediately opening the next (closed loop). Runs one LEG per
    fused-decode window size K (BENCH_DECODE_KS, default "1,4,8" —
    K=1 is the stepwise baseline) and reports device-truth decode
    serving numbers per leg:

      tokens/sec          aggregate streamed tokens over wall time
      round_trips/token   host dispatches per streamed token (the
                          quantity fused decode divides by K)
      TTFT p50/p99        request-start → first token (client-side)
      ITL p50/p99         gap between consecutive streamed tokens

    each reconciled against the server's /metrics decode section
    (tokens_streamed, window counters, shared-dispatch counters) plus
    the recompile watchdog: after the manager's warmup, session churn
    must cause ZERO compiles at every K (the fixed-shape decode
    contract). Every leg also streams one fixed-prompt greedy probe;
    `greedy_parity` asserts all legs emitted the bit-exact same
    sequence (the fused-decode parity contract, measured end-to-end).

    The primary (largest-K) leg runs its workload TWICE — once with
    request tracing off (the zero-allocation baseline) and once with
    DL4J_TPU_TRACE_SAMPLE=1 (every request traced) — so the artifact
    carries the measured sampled-on overhead
    (`tracing.trace_overhead_pct`, contract <2%) plus one exemplar
    trace tree (`trace`, renderable with tools/trace_view.py). Emits
    one JSON line AND writes BENCH_serving_decode.json
    (BENCH_DECODE_OUT overrides)."""
    import jax

    import threading
    import urllib.request

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.attention import (
        PositionEmbeddingLayer, TransformerEncoderBlock,
    )
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer,
    )
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.observe.watchdog import get_watchdog
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.serving import InferenceServer

    clients = int(os.environ.get("BENCH_DECODE_CLIENTS", "4"))
    rounds = int(os.environ.get("BENCH_DECODE_ROUNDS", "3"))
    max_tokens = int(os.environ.get("BENCH_DECODE_MAX_TOKENS", "32"))
    prompt_len = int(os.environ.get("BENCH_DECODE_PROMPT", "12"))
    chunk = int(os.environ.get("BENCH_DECODE_PREFILL_CHUNK", "8"))
    ks = sorted({int(x) for x in os.environ.get(
        "BENCH_DECODE_KS", "1,4,8").split(",") if x.strip()})
    V = 32
    probe_prompt = [(i % (V - 1)) + 1 for i in range(prompt_len)]

    def build_net():
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(1e-3)).activation("identity")
                .list(EmbeddingSequenceLayer(n_in=V, n_out=32),
                      PositionEmbeddingLayer(max_length=256),
                      TransformerEncoderBlock(num_heads=4, causal=True,
                                              window=32,
                                              rolling_cache=True,
                                              max_cache=64),
                      RnnOutputLayer(n_out=V, activation="softmax"))
                .set_input_type(InputType.recurrent(1, chunk)).build())
        return MultiLayerNetwork(conf).init()

    def pct(vals, q):
        vals = sorted(vals)
        return (None if not vals else
                round(vals[min(len(vals) - 1, int(q * len(vals)))], 3))

    def build_spec_pair():
        """Target + draft for the speculative matrix legs: the target
        is the bench transformer with a NON-rolling cache (spec decode
        rewinds positions; rolling rings can't) and its block's residual
        write-backs zeroed; the draft is the attention-free trunk
        (embed + pos + output) sharing the target's weights. Under
        pre-norm the silenced block is exact identity, so draft and
        target logits agree bit-for-bit — a distilled-draft stand-in
        that measures the MECHANISM's ceiling (greedy acceptance = 1.0,
        reported, and floored by the perf gate); real-model speedup
        scales with the measured acceptance rate."""
        import jax.numpy as jnp

        def build(blocks):
            layers = [EmbeddingSequenceLayer(n_in=V, n_out=32),
                      PositionEmbeddingLayer(max_length=256)]
            for _ in range(blocks):
                layers.append(TransformerEncoderBlock(
                    num_heads=4, causal=True, window=32,
                    rolling_cache=False, max_cache=128))
            layers.append(RnnOutputLayer(n_out=V, activation="softmax"))
            conf = (NeuralNetConfiguration.builder().seed(0)
                    .updater(Adam(1e-3)).activation("identity")
                    .list(*layers)
                    .set_input_type(InputType.recurrent(1, chunk))
                    .build())
            return MultiLayerNetwork(conf).init()

        tgt, drf = build(1), build(0)
        blk = tgt.params_tree["layer2_transformerencoderblock"]
        for key in ("attn_Wo", "attn_b", "ffn_w2", "ffn_b2"):
            blk[key] = jnp.zeros_like(blk[key])
        for name in drf.params_tree:
            src = ("layer3_rnnoutputlayer"
                   if name == "layer2_rnnoutputlayer" else name)
            drf.params_tree[name] = tgt.params_tree[src]
        return tgt, drf

    def run_leg(fused_k, *, traced_pass, nets=None, spec_k=None,
                kv_dtype=None):
        net, draft = nets() if nets else (build_net(), None)
        srv = InferenceServer(net, port=0, decode_slots=clients,
                              decode_prefill_chunk=chunk,
                              decode_fused_k=fused_k,
                              decode_draft_net=draft,
                              decode_spec_k=spec_k,
                              decode_kv_dtype=kv_dtype,
                              max_batch_size=max(8, clients),
                              queue_capacity=max(64, 8 * clients))
        port = srv.start()
        base = f"http://127.0.0.1:{port}"
        compiles_after_warmup = get_watchdog().compiles()

        rng = np.random.default_rng(0)
        lock = threading.Lock()
        ttfts, itls, tok_total, done_sessions = [], [], [0], [0]
        errors = []
        trace_ids = []

        def stream(body):
            req = urllib.request.Request(
                base + "/generate", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            first, prev, n, toks = None, None, 0, []
            with urllib.request.urlopen(req, timeout=120) as r:
                for line in r:
                    line = line.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    ev = json.loads(line[6:])
                    tid = ev.get("trace_id")
                    if tid:
                        with lock:
                            trace_ids.append(tid)
                    if "token" in ev:
                        now = time.perf_counter()
                        if first is None:
                            first = (now - t0) * 1e3
                        else:
                            with lock:
                                itls.append((now - prev) * 1e3)
                        prev = now
                        n += 1
                        toks.append(ev["token"])
                    elif "error" in ev:
                        raise RuntimeError(ev["error"])
            return first, n, toks

        def one_generation(seed):
            first, n, _ = stream({
                "prompt_ids": rng.integers(0, V, prompt_len).tolist(),
                "max_tokens": max_tokens, "seed": int(seed),
                "temperature": 0.9})
            if n != max_tokens or first is None:
                raise RuntimeError(f"short stream: {n}/{max_tokens}")
            with lock:
                ttfts.append(first)
                tok_total[0] += n
                done_sessions[0] += 1

        def client(i):
            try:
                for rd in range(rounds):
                    one_generation(i * 1000 + rd)
            except BaseException as e:  # surfaced in the artifact
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")

        def run_pass():
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            t_p = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.monotonic() - t_p

        prev_sample = os.environ.pop("DL4J_TPU_TRACE_SAMPLE", None)
        try:
            # pass 1: sampling off — the zero-allocation fast path
            wall_off = run_pass()
            toks_off = tok_total[0]
            wall_on, toks_on = 0.0, 0
            if traced_pass:
                # pass 2: every request traced — the sampled-on tax
                os.environ["DL4J_TPU_TRACE_SAMPLE"] = "1"
                wall_on = run_pass()
                toks_on = tok_total[0] - toks_off
            # the parity probe: one fixed-prompt greedy stream, same
            # at every K by the fused-decode parity contract
            _, _, probe = stream({"prompt_ids": probe_prompt,
                                  "max_tokens": max_tokens,
                                  "greedy": True})
        finally:
            if prev_sample is None:
                os.environ.pop("DL4J_TPU_TRACE_SAMPLE", None)
            else:
                os.environ["DL4J_TPU_TRACE_SAMPLE"] = prev_sample
        wall = wall_off + wall_on
        compile_delta = get_watchdog().compiles() - compiles_after_warmup

        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            metrics = json.loads(r.read())
        trace_block = None
        if trace_ids:
            with urllib.request.urlopen(
                    base + "/trace/" + trace_ids[-1], timeout=10) as r:
                trace_block = json.loads(r.read())
        srv.stop()
        decode = metrics["decode"]["default"]

        toks = tok_total[0]
        streamed = decode["tokens_streamed"]
        disp = decode["dispatches"]["total"]
        spec_block = decode.get("spec_decode") or {}
        leg = {
            "fused_k": fused_k,
            "spec": bool(spec_block.get("enabled")),
            "spec_k": spec_block.get("k") if spec_block.get("enabled")
            else None,
            "kv_dtype": (decode.get("slots") or {}).get("kv_dtype",
                                                        "native"),
            "acceptance_rate": spec_block.get("acceptance_rate"),
            "slots_per_chip_factor": (decode.get("slots") or {}).get(
                "slots_per_chip_factor"),
            "loop": decode["decode_loop"]["kind"],
            "tokens_per_s": round(toks / wall, 2),
            "duration_s": round(wall, 3),
            "sessions_completed": done_sessions[0],
            "round_trips_per_token": (round(disp / streamed, 4)
                                      if streamed else None),
            "windows": decode["dispatches"]["windows"],
            "window_tokens": decode["dispatches"]["window_tokens"],
            "ttft_ms": {"p50": pct(ttfts, 0.50),
                        "p99": pct(ttfts, 0.99)},
            "itl_ms": {"p50": pct(itls, 0.50), "p99": pct(itls, 0.99)},
            "compile_delta_after_warmup": compile_delta,
            "zero_recompiles": compile_delta == 0,
            "metrics_reconciled": (
                streamed == toks + len(probe)
                and decode["sessions"]["completed"]
                == done_sessions[0] + 1),
            "shared_dispatches": decode["dispatches"]["shared"],
            "interleaved": decode["dispatches"]["shared"] > 0,
            "errors": errors,
        }
        if traced_pass:
            leg["tracing"] = {
                "pass_off": {
                    "tokens": toks_off,
                    "duration_s": round(wall_off, 3),
                    "tokens_per_s": round(toks_off / wall_off, 2)},
                "pass_on": {
                    "tokens": toks_on,
                    "duration_s": round(wall_on, 3),
                    "tokens_per_s": (round(toks_on / wall_on, 2)
                                     if wall_on else None)},
                "trace_overhead_pct": round(
                    (1 - (toks_on / wall_on) / (toks_off / wall_off))
                    * 100, 2) if toks_off and toks_on else None,
                "traces_sampled": len(trace_ids),
            }
        return leg, probe, decode, trace_block

    def run_prefix_leg(label, *, cache_on, overlap):
        """One shared-prefix TTFT leg: a NON-rolling (pageable) net,
        one donor stream priming the radix index, then the closed-loop
        clients replaying prompts that share the donor's head. With
        `overlap` the clients reuse a long common stem (distinct
        tails, so every admission may CoW-fork once); without it every
        prompt is fresh (the zero-regression control). `cache_on`
        toggles DL4J_TPU_PREFIX_CACHE, so warm-vs-cold is the same
        binary, same workload, same shapes — only the radix differs.
        A small prefill chunk (4) keeps TTFT prefill-dominated, which
        is what the cache removes; decode windows are identical."""
        p_len = int(os.environ.get("BENCH_DECODE_PAGE_LEN", "8"))
        p_prompt = int(os.environ.get("BENCH_DECODE_PREFIX_PROMPT",
                                      "240"))
        # page-aligned tail: divergence lands exactly on a page
        # boundary, so the whole shared stem is reusable full pages
        p_tail = p_len if overlap else 0
        p_chunk = 2
        p_tokens = 8
        p_cache = p_prompt + 2 * p_tokens
        base = [(i % (V - 1)) + 1 for i in range(p_prompt)]
        prev = os.environ.pop("DL4J_TPU_PREFIX_CACHE", None)
        os.environ["DL4J_TPU_PREFIX_CACHE"] = ("on" if cache_on
                                               else "off")
        try:
            # a long-prompt variant of the bench net: non-rolling (the
            # pageable shape) with a cache big enough that cold prefill
            # dominates TTFT — the regime the radix index targets
            conf = (NeuralNetConfiguration.builder().seed(0)
                    .updater(Adam(1e-3)).activation("identity")
                    .list(EmbeddingSequenceLayer(n_in=V, n_out=32),
                          PositionEmbeddingLayer(max_length=512),
                          TransformerEncoderBlock(
                              num_heads=4, causal=True, window=32,
                              rolling_cache=False, max_cache=p_cache),
                          RnnOutputLayer(n_out=V,
                                         activation="softmax"))
                    .set_input_type(InputType.recurrent(1, p_chunk))
                    .build())
            net = MultiLayerNetwork(conf).init()
            srv = InferenceServer(net, port=0, decode_slots=clients,
                                  decode_prefill_chunk=p_chunk,
                                  decode_fused_k=primary_k,
                                  decode_page_len=p_len,
                                  max_batch_size=max(8, clients),
                                  queue_capacity=max(64, 8 * clients))
            port = srv.start()
            base_url = f"http://127.0.0.1:{port}"
            rng = np.random.default_rng(7)
            lock = threading.Lock()
            ttfts, toks, errors = [], [0], []

            def stream_one(prompt_ids):
                req = urllib.request.Request(
                    base_url + "/generate",
                    data=json.dumps({"prompt_ids": prompt_ids,
                                     "max_tokens": p_tokens,
                                     "greedy": True}).encode(),
                    headers={"Content-Type": "application/json"})
                t0 = time.perf_counter()
                first, n = None, 0
                with urllib.request.urlopen(req, timeout=120) as r:
                    for line in r:
                        line = line.decode().strip()
                        if not line.startswith("data: "):
                            continue
                        ev = json.loads(line[6:])
                        if "token" in ev:
                            if first is None:
                                first = (time.perf_counter() - t0) * 1e3
                            n += 1
                        elif "error" in ev:
                            raise RuntimeError(ev["error"])
                return first, n

            def follower_prompt(uid):
                if overlap:
                    tail = ((rng.integers(1, V, p_tail) + uid) % (V - 1)
                            + 1)
                    return base[:p_prompt - p_tail] + tail.tolist()
                return ((rng.integers(0, p_prompt, p_prompt) + uid)
                        % (V - 1) + 1).tolist()

            def client(i):
                try:
                    for rd in range(rounds):
                        first, n = stream_one(
                            follower_prompt(i * 1000 + rd))
                        if first is None or n != p_tokens:
                            raise RuntimeError(
                                f"short stream: {n}/{p_tokens}")
                        with lock:
                            ttfts.append(first)
                            toks[0] += n
                except BaseException as e:
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")

            # donor pass primes the radix (and, cache-off, is simply
            # one more cold stream — identical work either way)
            stream_one(base)
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            t_p = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.monotonic() - t_p
            with urllib.request.urlopen(base_url + "/metrics",
                                        timeout=10) as r:
                metrics = json.loads(r.read())
            srv.stop()
            pc = metrics["decode"]["default"].get("prefix_cache") or {}
            return {
                "label": label,
                "cache": "on" if cache_on else "off",
                "overlap_frac": (round(1 - p_tail / p_prompt, 3)
                                 if overlap else 0.0),
                "prompt_len": p_prompt,
                "page_len": p_len,
                "prefill_chunk": p_chunk,
                "ttft_ms": {"p50": pct(ttfts, 0.50),
                            "p99": pct(ttfts, 0.99)},
                "tokens_per_s": round(toks[0] / wall, 2) if wall
                else None,
                "prefix_cache": {k: pc.get(k) for k in (
                    "enabled", "hit_rate", "hit_tokens", "cow_forks",
                    "evicted_pages", "cached_pages")},
                "errors": errors,
            }
        finally:
            if prev is None:
                os.environ.pop("DL4J_TPU_PREFIX_CACHE", None)
            else:
                os.environ["DL4J_TPU_PREFIX_CACHE"] = prev

    primary_k = ks[-1]
    legs, probes = [], {}
    decode_primary, trace_block = None, None
    for k in ks:
        leg, probe, decode, tb = run_leg(k, traced_pass=(k == primary_k))
        legs.append(leg)
        probes[k] = probe
        if k == primary_k:
            decode_primary, trace_block = decode, tb

    # --- the {spec on/off} x {native, int8 KV} matrix: four legs over
    # the truncated-draft pair. Greedy parity is asserted WITHIN each
    # KV dtype (spec vs non-spec must be bit-exact; int8 legitimately
    # changes numerics vs native, so cross-dtype streams may differ).
    spec_k = int(os.environ.get("BENCH_DECODE_SPEC_K", str(primary_k)))
    spec_legs, spec_probes = [], {}
    spec_decode_native = None
    if os.environ.get("BENCH_DECODE_SPEC", "1") != "0":
        for use_spec, kv in ((False, "native"), (False, "int8"),
                             (True, "native"), (True, "int8")):
            leg, probe, dec, _ = run_leg(
                primary_k, traced_pass=False,
                nets=(build_spec_pair if use_spec else
                      (lambda: (build_spec_pair()[0], None))),
                spec_k=spec_k if use_spec else None,
                kv_dtype=None if kv == "native" else kv)
            spec_legs.append(leg)
            spec_probes[(use_spec, kv)] = probe
            if use_spec and kv == "native":
                spec_decode_native = dec

    # --- shared-prefix TTFT legs: warm (radix on) vs cold (radix off)
    # over the same ~92%-overlap workload, plus a no-overlap control
    # with the cache ON (the zero-regression contract: an enabled but
    # never-hit cache must not tax admission).
    prefix_legs = None
    if os.environ.get("BENCH_DECODE_PREFIX", "1") != "0":
        prefix_legs = [
            run_prefix_leg("warm-shared", cache_on=True, overlap=True),
            run_prefix_leg("cold-shared", cache_on=False, overlap=True),
            run_prefix_leg("no-overlap", cache_on=True, overlap=False),
        ]

    by_k = {leg["fused_k"]: leg for leg in legs}
    primary = by_k[primary_k]
    stepwise = by_k.get(1)
    out = {
        "metric": "serving_decode_tokens_per_s",
        "value": primary["tokens_per_s"],
        "unit": "tokens/s",
        "fused_k": primary_k,
        "clients": clients,
        "rounds": rounds,
        "prompt_len": prompt_len,
        "max_tokens": max_tokens,
        "prefill_chunk": chunk,
        "legs": legs,
        "speedup_vs_stepwise": (
            round(primary["tokens_per_s"] / stepwise["tokens_per_s"], 2)
            if stepwise and stepwise["tokens_per_s"] else None),
        "greedy_parity": all(probes[k] == probes[ks[0]] for k in ks),
        "zero_recompiles": all(leg["zero_recompiles"] for leg in legs),
        "metrics_reconciled": all(leg["metrics_reconciled"]
                                  for leg in legs),
        "errors": [e for leg in legs for e in leg["errors"]],
        "tracing": primary.get("tracing"),
        "server_decode": decode_primary,
        "trace": trace_block,
        "registry": _registry_snapshot(),
    }
    if spec_legs:
        by_cfg = {(leg["spec"], leg["kv_dtype"]): leg
                  for leg in spec_legs}
        spec_on = by_cfg[(True, "native")]
        spec_int8 = by_cfg[(True, "int8")]
        out["spec_matrix"] = spec_legs
        out["spec"] = {
            "spec_k": spec_k,
            "tokens_per_s": spec_on["tokens_per_s"],
            "tokens_per_s_int8": spec_int8["tokens_per_s"],
            "acceptance_rate": spec_on["acceptance_rate"],
            "speedup_vs_stepwise": (
                round(spec_on["tokens_per_s"]
                      / stepwise["tokens_per_s"], 2)
                if stepwise and stepwise["tokens_per_s"] else None),
            "speedup_vs_fused": (
                round(spec_on["tokens_per_s"]
                      / by_cfg[(False, "native")]["tokens_per_s"], 2)
                if by_cfg[(False, "native")]["tokens_per_s"] else None),
            "greedy_parity": (
                spec_probes[(True, "native")]
                == spec_probes[(False, "native")]
                and spec_probes[(True, "int8")]
                == spec_probes[(False, "int8")]),
            "zero_recompiles": all(leg["zero_recompiles"]
                                   for leg in spec_legs),
            "int8_slots_per_chip_factor":
                spec_int8["slots_per_chip_factor"],
            "server_decode": spec_decode_native,
        }
        out["errors"] += [e for leg in spec_legs for e in leg["errors"]]
    if prefix_legs:
        warm, cold, noov = prefix_legs
        w50 = (warm["ttft_ms"]["p50"] or 0)
        c50 = (cold["ttft_ms"]["p50"] or 0)
        n50 = (noov["ttft_ms"]["p50"] or 0)
        out["prefix"] = {
            "page_len": warm["page_len"],
            "overlap_frac": warm["overlap_frac"],
            "ttft_ms_warm_p50": w50 or None,
            "ttft_ms_cold_p50": c50 or None,
            "ttft_speedup": round(c50 / w50, 2) if w50 else None,
            # the headline contract: >=5x TTFT at >=80% prompt overlap
            "ttft_speedup_target_met": (w50 > 0 and c50 / w50 >= 5.0),
            "hit_rate": warm["prefix_cache"].get("hit_rate"),
            "hit_tokens": warm["prefix_cache"].get("hit_tokens"),
            "cow_forks": warm["prefix_cache"].get("cow_forks"),
            "evicted_pages": noov["prefix_cache"].get("evicted_pages"),
            # no-overlap, cache ON vs cache OFF: ~1.0 means the radix
            # probe costs nothing when it never hits
            "no_overlap_ttft_ratio": (round(n50 / c50, 2)
                                      if c50 else None),
            "legs": prefix_legs,
        }
        out["errors"] += [e for leg in prefix_legs
                          for e in leg["errors"]]
    dev = jax.devices()[0]
    out["device"] = getattr(dev, "device_kind", str(dev))
    out["platform"] = dev.platform
    dest = os.environ.get("BENCH_DECODE_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_serving_decode.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    _append_history("serving-decode", out)
    print(json.dumps(out))
    print(_decode_doc_line(out), file=sys.stderr)


def _decode_doc_line(out) -> str:
    """The doc-facing decode summary sentence, printed verbatim by
    `--serving-decode` — README/ROADMAP/PERF_NOTES quote THIS line, so
    refreshing the docs is a re-run + paste, never a hand-transcription
    (that's how 3292-vs-3364 drift happened)."""
    line = (f"decode serving: {out['value']} tok/s @ K={out['fused_k']} "
            f"fused ({out['speedup_vs_stepwise']}x vs stepwise)")
    sp = out.get("spec")
    if sp:
        line += (f"; spec D={sp['spec_k']}: {sp['tokens_per_s']} tok/s "
                 f"({sp['speedup_vs_stepwise']}x vs stepwise, "
                 f"acceptance {sp['acceptance_rate']}); int8 KV: "
                 f"{sp['int8_slots_per_chip_factor']}x slots/chip at "
                 f"{sp['tokens_per_s_int8']} tok/s")
    pf = out.get("prefix")
    if pf:
        line += (f"; prefix cache: {pf['ttft_speedup']}x TTFT p50 at "
                 f"{pf['overlap_frac']} overlap (hit rate "
                 f"{pf['hit_rate']}, no-overlap ratio "
                 f"{pf['no_overlap_ttft_ratio']})")
    return line


def _kernels_main():
    """`bench.py --kernels`: banded-attention / decode / fused-update
    microbench → BENCH_kernels.json.

    Per shape bucket it records BOTH wall-clock ms (kernel vs its dense
    XLA contender — meaningful on TPU; on CPU the banded side runs
    interpret-mode and the ms column documents only that it ran) and the
    XLA compile-cost flops/bytes of each side. The compile costs are the
    platform-independent evidence the acceptance contract keys on: the
    dense contender's flops grow ~T² across buckets while the banded
    program's grow ~T·w. Dispatch policies are consulted per bucket so
    the kernel_dispatch_total counters land in the embedded registry
    snapshot. Knobs: BENCH_KERNELS_SHAPES="256x32,512x64",
    BENCH_KERNELS_REPS, BENCH_KERNELS_OUT.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.banded_attention import (
        banded_attention, banded_decode_attention, banded_reference,
        decode_reference,
    )
    from deeplearning4j_tpu.ops.kernel_defaults import (
        banded_policy, decode_attention_policy,
    )

    on_tpu = jax.default_backend() == "tpu"
    interp = not on_tpu
    reps = int(os.environ.get("BENCH_KERNELS_REPS", "5"))
    shapes = [tuple(int(v) for v in s.split("x"))
              for s in os.environ.get("BENCH_KERNELS_SHAPES",
                                      "256x32,512x64").split(",")]

    def _cost(fn, *args):
        try:
            c = jax.jit(fn).lower(*args).cost_analysis()
            if isinstance(c, (list, tuple)):
                c = c[0] if c else {}
            c = c or {}
            return {"flops": float(c.get("flops") or 0.0),
                    "bytes_accessed": float(c.get("bytes accessed")
                                            or 0.0)}
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}

    def _ms(fn, *args):
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))   # compile + warmup
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            dt = (time.perf_counter() - t0) * 1e3
            best = dt if best is None else min(best, dt)
        return round(best, 3)

    b, h, hkv, dh = 2, 4, 2, 64
    buckets = []
    for t, w in shapes:
        key = jax.random.PRNGKey(t)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
        k = jax.random.normal(kk, (b, t, hkv, dh), jnp.float32)
        v = jax.random.normal(kv, (b, t, hkv, dh), jnp.float32)
        pol = banded_policy(t, h, hkv)          # records dispatch
        dense = lambda q, k, v: banded_reference(q, k, v, w, True,
                                                 dh ** -0.5)
        banded = lambda q, k, v: banded_attention(
            q, k, v, w, True, None, 256, 256, interp)
        buckets.append({
            "kind": "banded_attention", "t": t, "window": w,
            "heads": h, "kv_heads": hkv, "head_dim": dh,
            "policy": pol.kind,
            "dense": {"ms": _ms(dense, q, k, v),
                      **_cost(dense, q, k, v)},
            "banded": {"ms": _ms(banded, q, k, v),
                       **_cost(banded, q, k, v)},
        })

    # single-query decode over the KV-cache layout [B, L, Hkv, Dh]
    for cache_len in (512,):
        key = jax.random.PRNGKey(cache_len)
        kq, kk, kv = jax.random.split(key, 3)
        q1 = jax.random.normal(kq, (b, h, dh), jnp.float32)
        ck = jax.random.normal(kk, (b, cache_len, hkv, dh), jnp.float32)
        cv = jax.random.normal(kv, (b, cache_len, hkv, dh), jnp.float32)
        qpos = jnp.full((b,), cache_len - 1, jnp.int32)
        dpol = decode_attention_policy(cache_len, h, hkv)
        ddense = lambda q1, ck, cv: decode_reference(
            q1, ck, cv, qpos, qpos, None, False, dh ** -0.5)
        dband = lambda q1, ck, cv: banded_decode_attention(
            q1, ck, cv, qpos, qpos, window=None, rolling=False,
            block_l=512, interpret=interp)
        buckets.append({
            "kind": "decode_attention", "cache_len": cache_len,
            "heads": h, "kv_heads": hkv, "head_dim": dh,
            "policy": dpol.kind,
            "dense": {"ms": _ms(ddense, q1, ck, cv),
                      **_cost(ddense, q1, ck, cv)},
            "banded": {"ms": _ms(dband, q1, ck, cv),
                       **_cost(dband, q1, ck, cv)},
        })

    dev = jax.devices()[0]
    out = {
        "metric": "kernel_microbench",
        "buckets": buckets,
        "reps": reps,
        "interpret_mode": interp,
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "registry": _registry_snapshot(),
    }
    dest = os.environ.get("BENCH_KERNELS_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_kernels.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    _append_history("kernels", out)
    print(json.dumps(out))


def _sharding_main():
    """`bench.py --sharding`: the GSPMD spine's memory + dispatch profile
    on a forced-8-device CPU mesh → BENCH_sharding.json.

    Two legs of the SAME ParallelWrapper fit, differing only in
    `shard_opt_state` (the spine's escape hatch): the replicated leg
    holds full Adam moments on every device, the sharded leg splits
    them across the replica axis (arXiv:2004.13336). Per-device bytes
    come from addressable-shard metadata via
    observe.devicemon.tree_device_bytes (the CPU runtime reports no
    memory_stats), and the blob embeds the devicemon sample list +
    registry snapshot like every other mode. Also records steady-state
    syncs/step and post-warmup recompiles for the sharded leg — the
    numbers the perf gate budgets. Knobs: BENCH_SHARDING_OUT,
    BENCH_SHARDING_HIDDEN (default 256).
    """
    force = "--xla_force_host_platform_device_count=8"
    if "jax" in sys.modules:
        # too late to fake host devices in this process — re-exec with
        # the flag in place and let the child write the blob
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + force).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_SHARDING"] = "1"
        sys.exit(subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
        ).returncode)
    if force not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + force).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"    # this mode counts bytes on a
    import jax                             # CPU mesh and says so

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.feedforward import (
        DenseLayer, OutputLayer,
    )
    from deeplearning4j_tpu.observe.devicemon import tree_device_bytes
    from deeplearning4j_tpu.observe.syncmon import HostSyncMonitor
    from deeplearning4j_tpu.observe.watchdog import (
        RecompileWatchdog, get_watchdog, set_watchdog,
    )
    from deeplearning4j_tpu.optim.updaters import Adam
    from deeplearning4j_tpu.parallel import ParallelWrapper

    hidden = int(os.environ.get("BENCH_SHARDING_HIDDEN", "256"))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((128, 64)).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.integers(0, 8, 128)]

    def build():
        conf = (NeuralNetConfiguration.builder().seed(0)
                .updater(Adam(1e-3)).activation("relu")
                .list(DenseLayer(n_in=64, n_out=hidden),
                      DenseLayer(n_in=hidden, n_out=hidden),
                      OutputLayer(n_in=hidden, n_out=8,
                                  activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def leg(shard_opt_state):
        prev = set_watchdog(RecompileWatchdog(threshold=10_000))
        try:
            net = build()
            wrap = ParallelWrapper(net, shard_opt_state=shard_opt_state)
            wrap.fit(x, y, batch_size=32, epochs=1)      # compile epoch
            warm0 = get_watchdog().snapshot()["total_compiles"]
            mon = HostSyncMonitor().install()
            try:
                wrap.fit(x, y, batch_size=32, epochs=2)  # steady state
            finally:
                mon.uninstall()
            warm_recompiles = (get_watchdog().snapshot()["total_compiles"]
                               - warm0)
            # comm ledger while the leg's private watchdog is still
            # installed: per-owner-class collective totals plus the
            # single heaviest all-reduce program — the wrapper's train
            # step, the figure the analytic DP expectation prices
            comm = {}
            for tag, orow in get_watchdog().snapshot()["per_owner"].items():
                cols = orow.get("collectives") or {}
                if not cols:
                    continue
                cls = tag.split("@", 1)[0]
                agg = comm.setdefault(cls, {
                    "programs": 0, "ops": 0, "wire_bytes": 0,
                    "step_all_reduce_bytes": 0})
                for srow in cols.values():
                    agg["programs"] += 1
                    agg["ops"] += srow.get("ops", 0)
                    agg["wire_bytes"] += srow.get("wire_bytes", 0)
                    ar = (srow.get("by_kind") or {}).get("all-reduce", {})
                    agg["step_all_reduce_bytes"] = max(
                        agg["step_all_reduce_bytes"],
                        ar.get("wire_bytes", 0))
        finally:
            set_watchdog(prev)
        steps = 2 * (128 // 32)
        params_dev = tree_device_bytes(net.params_tree)
        opt_dev = tree_device_bytes(net.updater_state)

        def mean(d):
            return int(sum(d.values()) / max(len(d), 1))

        return {
            "shard_opt_state": shard_opt_state,
            "per_device_param_bytes": mean(params_dev),
            "per_device_opt_state_bytes": mean(opt_dev),
            "per_device_opt_state_bytes_by_device": dict(
                sorted(opt_dev.items())),
            "syncs_per_step": round(mon.syncs / steps, 3),
            "warm_recompiles": int(warm_recompiles),
            "final_score": float(net.score_),
            "comm": comm,
        }, wrap

    replicated, _ = leg(False)
    sharded, wrap = leg(True)
    total_opt = sum(int(leaf.nbytes) for leaf in
                    jax.tree_util.tree_leaves(wrap.net.updater_state))
    factor = (replicated["per_device_opt_state_bytes"]
              / max(sharded["per_device_opt_state_bytes"], 1))
    # comm-ledger reconciliation: on the REPLICATED (pure-DP) leg the
    # train step's gradient all-reduce must price at the textbook
    # 4 * param_count * (n-1)/n per-device ring bytes — the ledger's
    # one-pass-ring convention makes the two directly comparable (the
    # scalar loss all-reduce adds ~n/(n-1) bytes of slack, inside tol)
    ndev = jax.device_count()
    param_count = sum(int(leaf.size) for leaf in
                      jax.tree_util.tree_leaves(wrap.net.params_tree))
    expected_ar = 4.0 * param_count * (ndev - 1) / ndev
    measured_ar = (replicated["comm"].get("ParallelWrapper", {})
                   .get("step_all_reduce_bytes", 0))
    rec_err = (abs(measured_ar - expected_ar) / expected_ar
               if expected_ar else 1.0)
    comm_ledger = {
        "convention": "one-pass ring: wire = payload*(g-1)/g per device",
        "param_count": param_count,
        "expected_dp_all_reduce_bytes": int(round(expected_ar)),
        "measured_step_all_reduce_bytes": int(measured_ar),
        "reconciliation_error": round(rec_err, 4),
        "reconciled": bool(rec_err <= 0.1),
        "sharded_step_all_reduce_bytes": int(
            sharded["comm"].get("ParallelWrapper", {})
            .get("step_all_reduce_bytes", 0)),
    }
    out = {
        "metric": "sharding_spine",
        "devices": jax.device_count(),
        "mesh_axes": {str(a): int(wrap.mesh.shape[a])
                      for a in wrap.mesh.axis_names},
        "opt_state_bytes_total": int(total_opt),
        "opt_state_shard_factor": round(factor, 2),
        "losses_match": abs(replicated["final_score"]
                            - sharded["final_score"]) < 1e-4,
        "comm_ledger": comm_ledger,
        "replicated": replicated,
        "sharded": sharded,
        "device_memory": _devices_summary(),
        "observability": _registry_snapshot(),
    }
    dest = os.environ.get("BENCH_SHARDING_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_sharding.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    _append_history("sharding", out)
    print(json.dumps(out))


def _serving_fleet_main():
    """`--serving-fleet` mode: the FleetRouter tier over N replica
    PROCESSES (each its own interpreter + JAX runtime), three legs:

      scale    — closed-loop client pool through the router at each
                 replica count (BENCH_FLEET_REPLICAS, default "1,4"):
                 aggregate streamed tok/s, client-side TTFT/ITL
                 p50/p99, and an EXACT reconcile of the router's
                 /metrics token+request counters against the sum of
                 every replica's own /metrics
      handoff  — disaggregated prefill→handoff→decode greedy probe,
                 bit-identical to the single-replica stream of the
                 same prompt (quantized pages ship as bytes; the
                 decode admission matches the whole stem)
      slo      — a forced burn-rate breach on one replica drains it
                 mid-flight: every in-flight stream completes (zero
                 failed), traffic reroutes to the healthy replica

    The 1→N scaling contract (>2.5x at N=4) is asserted only where
    the host can physically scale (cpu_count >= N or
    BENCH_FLEET_REQUIRE_SCALING=1); a single-core CI box still
    measures and records the ratio. Writes BENCH_serving_fleet.json
    (BENCH_FLEET_OUT overrides) + one fleet row in
    BENCH_history.jsonl.

    The replicas come up on the CPU (`JAX_PLATFORMS=cpu` in their
    environment): a chip belongs to one process, and nothing assigns one
    to each replica yet (ROADMAP R6). This parent is the router's
    process and stays off JAX altogether — it builds no network and
    creates no array — so the platform in the result is the one a
    replica reports."""
    import threading

    from deeplearning4j_tpu.serving.fleet import client as fclient
    from deeplearning4j_tpu.serving.fleet.launcher import launch_replica
    from deeplearning4j_tpu.serving.fleet.router import FleetRouter

    counts = sorted({int(x) for x in os.environ.get(
        "BENCH_FLEET_REPLICAS", "1,4").split(",") if x.strip()})
    clients = int(os.environ.get("BENCH_FLEET_CLIENTS", "4"))
    rounds = int(os.environ.get("BENCH_FLEET_ROUNDS", "2"))
    max_tokens = int(os.environ.get("BENCH_FLEET_MAX_TOKENS", "16"))
    prompt_len = int(os.environ.get("BENCH_FLEET_PROMPT", "12"))
    V = 32
    spec = {"kind": "bench_lm", "seed": 0, "vocab": V, "chunk": 8,
            "max_cache": 64, "blocks": 1}
    probe = [(i % (V - 1)) + 1 for i in range(prompt_len)]

    def cfg(name, role="mixed", **kw):
        c = {"name": name, "role": role, "model": dict(spec),
             "decode_slots": max(clients, 4), "prefill_chunk": 8,
             "page_len": 16}
        c.update(kw)
        return c

    def pct(vals, q):
        vals = sorted(vals)
        return (None if not vals else
                round(vals[min(len(vals) - 1, int(q * len(vals)))], 3))

    def counter_sum(snap, name):
        return sum(e.get("value", 0) for e in
                   snap.get("series", {}).get(name, ()))

    def hist_p99(snap, name):
        rows = snap.get("series", {}).get(name, ())
        vals = [e.get("p99") for e in rows if e.get("p99") is not None]
        return round(max(vals), 3) if vals else None

    def stream(url, body):
        """One router stream → (tokens, ttft_ms, itls_ms, error)."""
        t0 = time.monotonic()
        last = t0
        toks, itls, ttft, err = [], [], None, None
        for ev in fclient.sse_events(url, "/generate", dict(body),
                                     timeout=300.0):
            if "token" in ev:
                now = time.monotonic()
                if ttft is None:
                    ttft = (now - t0) * 1000.0
                else:
                    itls.append((now - last) * 1000.0)
                last = now
                toks.append(int(ev["token"]))
            if "error" in ev:
                err = ev["error"]
        return toks, ttft, itls, err

    def start_fleet(cfgs, **router_kw):
        procs = [launch_replica(c, env={"JAX_PLATFORMS": "cpu"})
                 for c in cfgs]
        router_kw.setdefault("poll_interval", None)
        router = FleetRouter([(p.name, p.url, p.role) for p in procs],
                             **router_kw)
        rport = router.start()
        return procs, router, f"http://127.0.0.1:{rport}"

    def stop_fleet(procs, router):
        router.stop()
        for p in procs:
            p.terminate()

    # ---------------------------------------------------- scale legs
    legs = []
    probe_tokens = None
    replica_platform = None
    for n in counts:
        procs, router, url = start_fleet(
            [cfg(f"r{i}") for i in range(n)])
        try:
            if replica_platform is None:
                replica_platform = fclient.get_json(
                    procs[0].url, "/devices")["devices"][0][
                        "device"].split(":")[0]
            # warm every replica's compiled windows (and record the
            # single-replica greedy probe as the parity reference)
            for _ in range(n):
                toks, _, _, err = stream(url, {
                    "prompt_ids": probe, "max_tokens": max_tokens,
                    "greedy": True})
                assert err is None, f"warmup failed: {err}"
            if n == counts[0]:
                probe_tokens = toks
            ttfts, itls, lock = [], [], threading.Lock()
            streamed = [0]
            errors = []

            def worker(ci):
                for r in range(rounds):
                    p = [((7 * ci + 3 * r + i) % (V - 1)) + 1
                         for i in range(prompt_len)]
                    toks, ttft, it, err = stream(url, {
                        "prompt_ids": p, "max_tokens": max_tokens,
                        "greedy": True})
                    with lock:
                        if err is not None:
                            errors.append(err)
                        streamed[0] += len(toks)
                        if ttft is not None:
                            ttfts.append(ttft)
                        itls.extend(it)

            t0 = time.monotonic()
            threads = [threading.Thread(target=worker, args=(ci,))
                       for ci in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.monotonic() - t0
            assert not errors, f"fleet leg {n}: {errors[:3]}"

            rsnap = fclient.get_json(url, "/metrics", timeout=10.0)
            router_tokens = counter_sum(rsnap, "fleet_tokens_streamed_total")
            router_reqs = counter_sum(rsnap, "fleet_requests_total")
            rep_tokens = rep_reqs = 0
            rep_p99s = {}
            for p in procs:
                snap = fclient.get_json(p.url, "/metrics", timeout=10.0)
                p99s = []
                for d in (snap.get("decode") or {}).values():
                    rep_tokens += int(d.get("tokens_streamed") or 0)
                    p99 = (d.get("ttft_ms") or {}).get("p99")
                    if p99 is not None:
                        p99s.append(p99)
                    rep_reqs += int((d.get("sessions") or {})
                                    .get("opened", 0))
                rep_p99s[p.name] = (round(max(p99s), 3)
                                    if p99s else None)
            client_tokens = streamed[0] + n * len(probe_tokens or ())
            reconciled = (router_tokens == rep_tokens == client_tokens
                          and router_reqs == rep_reqs)
            if not reconciled:
                print(f"[bench] fleet reconcile MISMATCH n={n}: "
                      f"router={router_tokens} replicas={rep_tokens} "
                      f"clients={client_tokens} "
                      f"reqs {router_reqs}/{rep_reqs}", file=sys.stderr)
            legs.append({
                "replicas": n,
                "tokens_per_s": round(streamed[0] / wall, 2),
                "streamed_tokens": streamed[0],
                "wall_s": round(wall, 3),
                "ttft_ms": {"p50": pct(ttfts, 0.50),
                            "p99": pct(ttfts, 0.99)},
                "itl_ms": {"p50": pct(itls, 0.50),
                           "p99": pct(itls, 0.99)},
                "fleet_ttft_p99_ms": hist_p99(rsnap, "fleet_ttft_ms"),
                "replica_ttft_p99_ms": rep_p99s,
                "router_tokens": router_tokens,
                "replica_tokens": rep_tokens,
                "client_tokens": client_tokens,
                "metrics_reconciled": reconciled,
            })
        finally:
            stop_fleet(procs, router)

    scaling = None
    if len(legs) > 1 and legs[0]["tokens_per_s"]:
        scaling = round(legs[-1]["tokens_per_s"]
                        / legs[0]["tokens_per_s"], 3)
    can_scale = (os.cpu_count() or 1) >= counts[-1]
    require = bool(os.environ.get("BENCH_FLEET_REQUIRE_SCALING")) \
        or (can_scale and counts[-1] >= 4)
    if require and scaling is not None and scaling < 2.5:
        print(f"[bench] FLEET SCALING BELOW CONTRACT: "
              f"{counts[0]}→{counts[-1]} replicas = {scaling}x < 2.5x",
              file=sys.stderr)

    # --------------------------------------------------- handoff leg
    procs, router, url = start_fleet(
        [cfg("pf0", role="prefill"), cfg("dc0", role="decode")])
    try:
        toks, _, _, err = stream(url, {"prompt_ids": probe,
                                       "max_tokens": max_tokens,
                                       "greedy": True})
        assert err is None, f"handoff leg failed: {err}"
        rsnap = fclient.get_json(url, "/metrics", timeout=10.0)
        handoff_leg = {
            "tokens": toks,
            "parity_vs_single_replica": toks == probe_tokens,
            "handoffs": counter_sum(rsnap, "fleet_handoffs_total"),
            "handoff_bytes": counter_sum(rsnap,
                                         "fleet_handoff_bytes_total"),
        }
        assert handoff_leg["parity_vs_single_replica"], (
            f"disaggregated stream diverged: {toks} vs {probe_tokens}")
        assert handoff_leg["handoffs"] >= 1
    finally:
        stop_fleet(procs, router)

    # ------------------------------------------------------- SLO leg
    slo_cfg = {"interval": 0.1, "objectives": [
        {"name": "bench-forced-breach",
         "series": "serving_ttft_ms:p99", "threshold": 0.0,
         "budget": 1.0, "fast_s": 30.0, "slow_s": 60.0,
         "burn_threshold": 0.5}]}
    procs, router, url = start_fleet(
        [cfg("s0", slo=slo_cfg), cfg("s1")], auto_drain_on_slo=True)
    try:
        # land traffic on s0 so its breached series has points
        fclient.post_json(procs[0].url, "/generate",
                          {"prompt_ids": probe, "max_tokens": 2,
                           "greedy": True, "stream": False},
                          timeout=120.0)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            hz = fclient.get_json(procs[0].url, "/healthz", timeout=5.0)
            if any(r.startswith("slo firing")
                   for r in hz.get("reasons", ())):
                break
            time.sleep(0.1)
        inflight_err, inflight_ok, lock = [], [0], threading.Lock()

        def inflight(ci):
            toks, _, _, err = stream(url, {
                "prompt_ids": [((ci + i) % (V - 1)) + 1
                               for i in range(prompt_len)],
                "max_tokens": max_tokens, "greedy": True})
            with lock:
                if err is None and toks:
                    inflight_ok[0] += 1
                else:
                    inflight_err.append(err or "empty stream")

        threads = [threading.Thread(target=inflight, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        router.poll_once()          # the breach verdict → drain s0
        for t in threads:
            t.join()
        rsnap = fclient.get_json(url, "/metrics", timeout=10.0)
        post_toks, _, _, err = stream(url, {"prompt_ids": probe,
                                            "max_tokens": 4,
                                            "greedy": True})
        slo_leg = {
            "slo_drains": counter_sum(rsnap, "fleet_slo_drains_total"),
            "migrations": counter_sum(rsnap, "fleet_migrations_total"),
            "reroutes": counter_sum(rsnap, "fleet_reroutes_total"),
            "inflight_completed": inflight_ok[0],
            "inflight_failed": len(inflight_err),
            "failed_requests": counter_sum(rsnap,
                                           "fleet_failed_requests_total"),
            "rerouted_stream_ok": err is None and bool(post_toks),
        }
        # federation health off the same poll tick: scrape freshness,
        # stale count, and the worst fleet-SLO burn (dash.py row)
        fed_rows = router.obsplane.federation.replicas()
        ages = [r["age_s"] for r in fed_rows.values()
                if r["age_s"] is not None]
        slo_snap = router.obsplane.slo_engine.snapshot()
        slo_leg["scrape_age_s"] = max(ages) if ages else None
        slo_leg["stale_replicas"] = sum(
            1 for r in fed_rows.values() if r["stale"])
        slo_leg["slo_burn"] = max(
            (float(s.get("burn_fast") or 0.0)
             for s in slo_snap.get("slos", ())), default=0.0)
        assert slo_leg["slo_drains"] >= 1, "forced SLO breach never drained"
        assert slo_leg["inflight_failed"] == 0, inflight_err[:3]
        assert slo_leg["failed_requests"] == 0
    finally:
        stop_fleet(procs, router)

    best = legs[-1]
    out = {
        "metric": "serving_fleet_tokens_per_s",
        "value": best["tokens_per_s"],
        "unit": "tokens/s",
        "mode": "serving-fleet",
        "platform": replica_platform,
        "replica_counts": counts,
        "clients": clients,
        "rounds": rounds,
        "max_tokens": max_tokens,
        "scaling_1_to_max": scaling,
        "scaling_contract_25x_enforced": bool(require),
        "scale_legs": legs,
        "handoff": handoff_leg,
        "slo": slo_leg,
        "fleet": {
            "replicas": counts[-1],
            "reroutes": slo_leg["reroutes"],
            "handoffs": handoff_leg["handoffs"],
            "migrations": slo_leg["migrations"],
            "slo_drains": slo_leg["slo_drains"],
            "ttft_p99_ms": best["fleet_ttft_p99_ms"],
            "scaling": scaling,
            "reconciled": all(l["metrics_reconciled"] for l in legs),
            "scrape_age_s": slo_leg.get("scrape_age_s"),
            "stale_replicas": slo_leg.get("stale_replicas"),
            "slo_burn": slo_leg.get("slo_burn"),
        },
    }
    path = os.environ.get("BENCH_FLEET_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_serving_fleet.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    _append_history("serving-fleet", out)
    print(json.dumps({k: out[k] for k in
                      ("metric", "value", "unit", "scaling_1_to_max",
                       "fleet")}))


def main() -> int:
    if "--sharding" in sys.argv or os.environ.get("BENCH_SHARDING"):
        _sharding_main()
        return 0
    if "--kernels" in sys.argv or os.environ.get("BENCH_KERNELS"):
        _kernels_main()
        return 0
    if "--serving-decode" in sys.argv or os.environ.get(
            "BENCH_SERVING_DECODE"):
        _serving_decode_main()
        return 0
    if "--serving-fleet" in sys.argv or os.environ.get(
            "BENCH_SERVING_FLEET"):
        _serving_fleet_main()
        return 0
    if "--serving" in sys.argv or os.environ.get("BENCH_SERVING"):
        _serving_main()
        return 0
    if "--host-overhead" in sys.argv or os.environ.get("BENCH_HOST_OVERHEAD"):
        _host_overhead_main()
        return 0
    if os.environ.get("BENCH_CHILD"):
        _child_main()
        return 0

    models = os.environ.get("BENCH_MODEL", "resnet50")
    if "," in models:
        # multi-config sweep (BASELINE configs 1-4 in one invocation):
        # one JSON line per model, each through the same child-process
        # ladder + TPU persistence. The driver's default single-model
        # invocation still prints exactly one line.
        rc = 0
        try:
            for m in [m.strip() for m in models.split(",") if m.strip()]:
                os.environ["BENCH_MODEL"] = m
                rc = max(rc, _run_ladder())
        finally:  # restore the caller's comma list — in-process callers
            os.environ["BENCH_MODEL"] = models  # must not see the last model
        return rc
    return _run_ladder()


def _run_ladder() -> int:
    """Run the attempts in order; 0 with one result line on the first that
    measures, 1 with one error line (no value) when none does."""
    timeout = float(os.environ.get("BENCH_ATTEMPT_TIMEOUT", "600"))
    backoffs = [15.0, 45.0, 90.0]
    errors = []
    hangs = 0
    degens = 0
    plans = _attempt_plans()
    for i, (overrides, label) in enumerate(plans):
        if hangs >= 2 or degens >= 2:
            # two full-timeout hangs mean the backend is dead (not
            # flaky), and two degenerate timings mean latency noise
            # deterministically swamps this model's steps — either way,
            # don't burn the remaining attempts
            errors.append(f"{label}: skipped "
                          f"({'timed out' if hangs >= 2 else 'timing degenerate'} twice)")
            continue
        env = dict(os.environ, BENCH_CHILD="1", **overrides)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            errors.append(f"{label}: timeout after {timeout}s")
            hangs += 1
            continue
        if proc.returncode == 0:
            for line in reversed(proc.stdout.strip().splitlines()):
                try:
                    result = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
            else:
                errors.append(f"{label}: rc=0 but no JSON in output")
                continue
            result["attempt"] = i + 1
            result["config"] = label
            if errors:
                result["prior_errors"] = errors
            _record_last_tpu(result)
            _append_history("ladder", result)
            print(json.dumps(result))
            return 0
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        errors.append(f"{label}: rc={proc.returncode}: "
                      + " | ".join(tail[-3:]))
        if proc.returncode == _RC_NO_CHIP:
            break       # no chip now means no chip on the next attempt
        if proc.returncode == _RC_DEGENERATE_TIMING:
            # measurement noise, not backend flakiness: one immediate
            # retry is worth it (noise varies run to run) but backoffs
            # and batch-halving cannot help — shorter steps only make
            # the dominance condition harder.
            degens += 1
            continue
        if i < len(backoffs):
            time.sleep(backoffs[i])

    # Every attempt failed: say why in one structured line, with no value
    # under the metric's name, and fail.
    model = os.environ.get("BENCH_MODEL", "resnet50")
    _, _, unit, _ = _BENCHES.get(model, _BENCHES["resnet50"])
    out = {
        "metric": _metric_name(model),
        "value": None,
        "unit": unit,
        "error": errors,
    }
    _append_history("ladder", out)
    print(json.dumps(out))
    return 1


if __name__ == "__main__":
    sys.exit(main())
