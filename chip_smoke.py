#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: train, lstm, serve
    python chip_smoke.py --chips 4    four chips: the data-parallel path and
                                      its one-device comparison, nothing else

One process, because a chip belongs to one process at a time. Each phase
drives the entry points a user calls (`fit()`, `ParallelWrapper.fit()`, an
`InferenceServer` over HTTP) at the full width of a model the repo ships,
checks what comes out, and prints one JSON line of facts. A phase that
raises, or whose check fails, ends the run with a non-zero exit code. The
last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only when every phase passed. The platform is never set
here: the script reads what JAX resolved and refuses to run off the chip.
Data and weights are synthetic, made from `--seed`; nothing is downloaded
and nothing is asked of git. No speed is measured: the seconds printed are
set-up and compile times, not a benchmark.

The phases are plain functions that take their sizes as arguments, so
`tests/test_chip_smoke.py` rehearses them at tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class SmokeError(RuntimeError):
    """A phase ran to its end and one of its checks did not hold."""


def _check(ok, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def _say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


class _XlaLog:
    """Counts XLA compiles and persistent-cache hits and misses, from
    `jax.monitoring`, while installed. A hit also fires the compile
    event (it times compile-or-fetch), so `compiled` = events - hits."""

    def __init__(self):
        self.events = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    @property
    def compiled(self) -> int:
        return self.events - self.cache_hits

    def _on_event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1
        elif event == _CACHE_MISS:
            self.cache_misses += 1

    def _on_duration(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.events += 1
            self.seconds += duration

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_listener(self._on_event)
        mon.unregister_event_duration_listener(self._on_duration)

    def facts(self) -> dict:
        return {"xla_compiles": self.compiled,
                "xla_compile_or_fetch_s": round(self.seconds, 2),
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_misses}


class _LossLog:
    """Training listener that keeps every step's loss on the device (no
    host sync inside `fit`) with the host time and the XLA compile count
    at which it was handed over."""

    def __init__(self, xla: _XlaLog):
        self._xla = xla
        self.losses, self.times, self.compiles = [], [], []

    def iteration_done(self, model, iteration, epoch, score):
        self.losses.append(score)
        self.times.append(time.perf_counter())
        self.compiles.append(self._xla.compiled)

    def on_fit_start(self, model):
        pass

    on_fit_end = on_fit_start

    def on_epoch_start(self, model, epoch):
        pass

    on_epoch_end = on_epoch_start

    def host(self):
        return [float(x) for x in self.losses]


def _dispatched(op: str, impl: str) -> float:
    from deeplearning4j_tpu.observe import get_registry

    return get_registry().counter(
        "kernel_dispatch_total", op=op, impl=impl).value


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def _platforms(*trees) -> list:
    """Platforms of the devices that hold the trees' leaves."""
    return sorted({d.platform for tree in trees for leaf in _leaves(tree)
                   for d in leaf.devices()})


def _specs(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


# ------------------------------------------------------------------ train
def _resnet50(image: int, classes: int, dtype: str, seed: int):
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.optim.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    model = ResNet50(num_classes=classes, input_shape=(image, image, 3),
                     seed=seed, updater=Nesterovs(0.1, 0.9))
    return ComputationGraph(
        dataclasses.replace(model.conf(), dtype=dtype)).init()


def _images(seed: int, n: int, image: int, classes: int):
    from deeplearning4j_tpu import native

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, image, image, 3), dtype=np.float32)
    y = native.one_hot(rng.integers(0, classes, n).astype(np.int32),
                       classes)
    return x, y


def train(*, image: int = 224, classes: int = 1000, batch: int = 128,
          steps: int = 8, dtype: str = "bfloat16", seed: int = 0) -> dict:
    """ResNet-50 through `ComputationGraph.fit()` with the default
    prefetch iterator and executor: the flagship configuration."""
    import jax

    from deeplearning4j_tpu.observe.syncmon import HostSyncMonitor
    from deeplearning4j_tpu.observe.watchdog import get_watchdog

    net = _resnet50(image, classes, dtype, seed)
    x, y = _images(seed, steps * batch, image, classes)
    with _XlaLog() as xla:
        log = _LossLog(xla)
        net.set_listeners(log)
        t0 = time.perf_counter()
        with HostSyncMonitor() as mon:
            net.fit(x, y, epochs=1, batch_size=batch)
        fit_s = time.perf_counter() - t0
        losses = log.host()
    owner = get_watchdog().snapshot()["per_owner"].get(
        net._jit_cache.owner_tag, {})
    facts = {
        "model": "zoo.ResNet50", "dtype": dtype, "batch": batch,
        "image": image, "classes": classes, "steps": len(losses),
        "loss_first": losses[0], "loss_last": losses[-1],
        "first_dispatch_s": round(log.times[0] - t0, 2),
        "fit_s": round(fit_s, 2),
        "step_programs": owner.get("compiles"),
        "step_flops": next(iter(owner.get("costs", {}).values()),
                           {}).get("flops"),
        "xla_compiles_after_step_2": xla.compiled - log.compiles[1],
        "host_syncs": mon.syncs,
        "params_on": _platforms(net.params_tree, net.updater_state),
        **xla.facts(),
    }
    _say("train", **facts)
    _check(len(losses) == steps, f"train: {len(losses)} of {steps} steps")
    _check(np.all(np.isfinite(losses)), f"train: loss not finite: {losses}")
    _check(facts["params_on"] == [jax.devices()[0].platform],
           f"train: parameters and updater state on {facts['params_on']}")
    _check(facts["step_programs"] == 1,
           f"train: {facts['step_programs']} train-step programs, want 1")
    _check(facts["xla_compiles_after_step_2"] == 0,
           "train: XLA compiled again after the second step")
    _check(mon.syncs <= 1, f"train: {mon.syncs} host syncs in one epoch")
    return facts


# ------------------------------------------------------------------- lstm
def _lstm_net(features, hidden, classes, dtype, seed, fused):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.recurrent import (
        GravesLSTM, RnnOutputLayer,
    )
    from deeplearning4j_tpu.optim.updaters import Adam

    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-3)).activation("tanh")
            .list(GravesLSTM(n_out=hidden, fused=fused),
                  GravesLSTM(n_out=hidden, fused=fused),
                  RnnOutputLayer(n_out=classes, activation="softmax"))
            .set_input_type(InputType.recurrent(features))
            .build())
    return MultiLayerNetwork(dataclasses.replace(conf, dtype=dtype)).init()


def _train_step_hlo(net, x_shape, y_shape) -> str:
    """Compiled text of the train step `fit()` ran, lowered again from
    the cached jit at the same shapes."""
    import jax
    import jax.numpy as jnp

    fn = net._get_train_step((False, False, False))
    sds = jax.ShapeDtypeStruct
    return fn.lower(
        _specs(net.params_tree), _specs(net.updater_state),
        _specs(net.state_tree), sds((), jnp.int32),
        sds(x_shape, net.dtype), sds(y_shape, jnp.float32), None, None,
        _specs(net._rng), None).compile().as_text()


# Loss is near ln(classes) ~ 4.2; outputs are compared as a share of the
# largest output. The scan path rounds every gate to the storage dtype
# where the kernel keeps f32. On the v5e the losses differed by 1.4e-6
# (f32) and 6.4e-5 (bf16), the outputs by nothing (f32) and one bf16 ulp
# (chip run, PR 21); bf16 is allowed four ulps.
LSTM_LOSS_TOL = {"float32": 1e-4, "bfloat16": 1e-3}
LSTM_OUTPUT_TOL = {"float32": 1e-3, "bfloat16": 3.2e-2}


def lstm(*, timesteps: int = 128, features: int = 128, hidden: int = 512,
         classes: int = 64, batch: int = 64, steps: int = 3,
         dtypes=("float32", "bfloat16"), seed: int = 0) -> dict:
    """The two-layer GravesLSTM configuration through `fit()`, once per
    dtype: the fused Pallas kernel the default policy dispatches on the
    chip against the same net on the `lax.scan` path."""
    import jax

    on_chip = jax.default_backend() == "tpu"
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(
        (steps * batch, timesteps, features), dtype=np.float32)
    y = np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, (steps * batch, timesteps))]
    out = {}
    for dtype in dtypes:
        with _XlaLog() as xla:
            fused_before = _dispatched("lstm", "fused")
            runs = {}
            for name, fused in (("policy", None), ("scan", False)):
                net = _lstm_net(features, hidden, classes, dtype, seed,
                                fused)
                probs = np.asarray(net.output(x[:batch]), np.float32)
                log = _LossLog(xla)
                net.set_listeners(log)
                net.fit(x, y, epochs=1, batch_size=batch)
                runs[name] = (net, probs, log.host())
            net, probs, losses = runs["policy"]
            _, probs_scan, losses_scan = runs["scan"]
            hlo = _train_step_hlo(net, (batch, timesteps, features),
                                  (batch, timesteps, classes))
        facts = {
            "dtype": dtype, "timesteps": timesteps, "features": features,
            "hidden": hidden, "batch": batch, "steps": len(losses),
            "losses": losses, "losses_scan": losses_scan,
            "loss_diff_max": float(np.max(np.abs(
                np.subtract(losses, losses_scan)))),
            "loss_tolerance": LSTM_LOSS_TOL[dtype],
            "output_diff_max": float(np.max(np.abs(probs - probs_scan))
                                     / np.max(probs_scan)),
            "output_tolerance": LSTM_OUTPUT_TOL[dtype],
            "fused_dispatches": _dispatched("lstm", "fused") - fused_before,
            "tpu_custom_call": "tpu_custom_call" in hlo,
            **xla.facts(),
        }
        _say("lstm", **facts)
        _check(np.all(np.isfinite(losses)) and np.all(np.isfinite(probs)),
               f"lstm {dtype}: not finite")
        _check(facts["fused_dispatches"] > 0,
               f"lstm {dtype}: the policy did not dispatch the fused kernel")
        _check(facts["tpu_custom_call"] or not on_chip,
               f"lstm {dtype}: no tpu_custom_call in the compiled step")
        _check(facts["loss_diff_max"] <= LSTM_LOSS_TOL[dtype],
               f"lstm {dtype}: losses {losses} against scan {losses_scan}")
        _check(facts["output_diff_max"] <= LSTM_OUTPUT_TOL[dtype],
               f"lstm {dtype}: outputs differ from scan by "
               f"{facts['output_diff_max']}")
        out[dtype] = facts
    return out


# ------------------------------------------------------------------ serve
# A served token passes when the reference, given the same prefix, ranks
# it first or within this share of its first choice (a near-tie that
# rounding may flip); at least EXACT_SHARE of all tokens must be first.
# On the v5e 255 of 256 f32 tokens were first and the other was 0.5%
# short (chip run, PR 21).
NEAR_TIE = {"float32": 0.02, "bfloat16": 0.08}
EXACT_SHARE = 0.9


def _generate(base: str, body: dict, timeout: float):
    """POST /generate and drain its SSE stream with the repo's own
    client: (tokens, terminal event)."""
    from deeplearning4j_tpu.serving.fleet.client import sse_events

    events = list(sse_events(base, "/generate", body, timeout=timeout))
    return [int(e["token"]) for e in events if "token" in e], events[-1]


def _reference_ranks(net, prompts, streams, width: int):
    """Teacher-forced greedy reference: ONE full forward of the net (no
    KV cache, no slots, no server) over prompt + served tokens, right
    padded to the configured length. Returns per stream the reference's probability of each
    served token over the probability of its own first choice."""
    x = np.zeros((len(prompts), width, 1), np.float32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        seq = list(p) + list(s)
        x[i, :len(seq), 0] = seq
    probs = np.asarray(net.output(x), np.float64)
    ratios = []
    for i, (p, s) in enumerate(zip(prompts, streams)):
        at = probs[i, len(p) - 1:len(p) - 1 + len(s)]
        ratios.append(at[np.arange(len(s)), s] / at.max(axis=-1))
    return ratios


@contextlib.contextmanager
def _environ(**values):
    old = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# (leg, model dtype, environment of that leg's fresh server)
SERVE_LEGS = (
    ("dense", "float32", {}),
    ("banded", "float32", {"DL4J_TPU_DECODE_ATTN": "banded"}),
    ("banded_unpaged", "float32", {"DL4J_TPU_DECODE_ATTN": "banded",
                                   "DL4J_TPU_PREFIX_CACHE": "off"}),
    ("dense_bf16", "bfloat16", {}),
)


def serve(*, d_model: int = 512, heads: int = 8, kv_heads: int = 2,
          blocks: int = 6, vocab: int = 256, cache: int = 1024,
          slots: int = 8, fused_k: int = 8, prefill_chunk: int = 64,
          requests: int = 8, prompt_min: int = 64, prompt_max: int = 128,
          new_tokens: int = 32, seed: int = 0, legs=SERVE_LEGS) -> dict:
    """`zoo.TextGenerationTransformer` (RoPE, RMSNorm, SwiGLU, GQA)
    behind an `InferenceServer` on an ephemeral port: concurrent greedy
    `/generate` streams over the slot pool and the fused decode window,
    once per leg in a fresh server. `dense` is the default policy, in
    f32 and in bf16; the `banded` legs force the Pallas decode kernels
    (paged, then per-slot) so they run compiled on the chip and are held
    to the same reference."""
    import jax

    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.observe.watchdog import get_watchdog
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.serving.fleet.client import get_json
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer

    conf = TextGenerationTransformer(
        num_classes=vocab, input_shape=(cache, 1), seed=seed,
        d_model=d_model, num_heads=heads, num_kv_heads=kv_heads,
        num_blocks=blocks, pos_encoding="rope", norm="rms",
        ffn_activation="swiglu").conf()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(n)).tolist()
               for n in rng.integers(prompt_min, prompt_max + 1, requests)]
    platform = jax.devices()[0].platform
    out, first = {}, None
    for leg, dtype, env in legs:
        # a net per leg (same seed, same weights): the policies read the
        # environment at trace time and a net keeps its traced programs
        net = MultiLayerNetwork(
            dataclasses.replace(conf, dtype=dtype)).init()
        near = NEAR_TIE[dtype]
        with _environ(**env), _XlaLog() as xla:
            banded_before = _dispatched("decode_attention", "banded")
            t0 = time.perf_counter()
            srv = InferenceServer(
                net, port=0, decode_slots=slots, decode_fused_k=fused_k,
                decode_prefill_chunk=prefill_chunk,
                max_batch_size=max(8, slots),
                queue_capacity=max(64, 8 * slots))
            base = f"http://127.0.0.1:{srv.start()}"
            try:
                warm_s = time.perf_counter() - t0
                warm = (get_watchdog().compiles(), xla.compiled)
                results = [None] * requests

                def client(i):
                    results[i] = _generate(
                        base, {"prompt_ids": prompts[i], "greedy": True,
                               "max_tokens": new_tokens}, timeout=600)

                threads = [threading.Thread(target=client, args=(i,),
                                            daemon=True)
                           for i in range(requests)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=900)
                compiles = (get_watchdog().compiles() - warm[0],
                            xla.compiled - warm[1])
                decode = get_json(base, "/metrics")["decode"]["default"]
                devices = get_json(base, "/devices")["devices"]
            finally:
                srv.stop()
            _check(all(r is not None for r in results),
                   f"serve {leg}: a client did not return")
            streams = [r[0] for r in results]
            ratios = np.concatenate(_reference_ranks(
                net, prompts, streams, cache))
        facts = {
            "leg": leg, "dtype": dtype, "d_model": d_model,
            "blocks": blocks, "heads": heads, "kv_heads": kv_heads,
            "cache": cache, "slots": slots, "requests": requests,
            "prompt_lens": [len(p) for p in prompts],
            "tokens_generated": sum(len(s) for s in streams),
            "outcomes": sorted({str(r[1].get("done", r[1]))
                                for r in results}),
            "reference_first_choice": int((ratios >= 1.0).sum()),
            "reference_near_tie": int(((ratios < 1.0)
                                       & (ratios >= 1.0 - near)).sum()),
            "near_tie_tolerance": near,
            "reference_worst_ratio": float(ratios.min()),
            "decode_attention": sorted({p["kind"] for p in
                                        decode["kernel_policy"]}),
            "banded_dispatches": _dispatched("decode_attention", "banded")
            - banded_before,
            "decode_loop": decode["decode_loop"]["kind"],
            "fused_k": decode["decode_loop"]["k"],
            "paged": decode["prefix_cache"]["enabled"],
            "windows": decode["dispatches"]["windows"],
            "warmup_s": round(warm_s, 2),
            "jit_programs_after_warmup": compiles[0],
            "xla_compiles_after_warmup": compiles[1],
            "devices": [d["device"] for d in devices],
            **xla.facts(),
        }
        if first is None:
            first = (leg, dtype, streams)
        elif dtype == first[1]:
            facts[f"streams_equal_{first[0]}"] = sum(
                a == b for a, b in zip(streams, first[2]))
        _say("serve", **facts)
        _check(all(len(s) == new_tokens and "done" in r[1]
                   for s, r in zip(streams, results)),
               f"serve {leg}: a stream did not complete: {facts['outcomes']}")
        _check(ratios.min() >= 1.0 - near,
               f"serve {leg}: a served token is not the reference's "
               f"choice (ratio {ratios.min():.4f})")
        _check((ratios >= 1.0).mean() >= EXACT_SHARE,
               f"serve {leg}: only {(ratios >= 1.0).mean():.2f} of tokens "
               "are the reference's first choice")
        # the watchdog counts model programs; one-op eager conversions XLA
        # compiles on the first live window are printed, not refused
        _check(compiles[0] == 0,
               f"serve {leg}: {compiles[0]} programs compiled after "
               "warm-up")
        _check(decode["decode_loop"]["kind"] == "fused",
               f"serve {leg}: decode loop is not the fused window")
        _check(all(d["device"].startswith(platform + ":")
                   for d in devices) and devices,
               f"serve {leg}: /devices reports {devices}")
        if env.get("DL4J_TPU_DECODE_ATTN") == "banded":
            _check(facts["banded_dispatches"] > 0,
                   f"serve {leg}: the banded decode kernel never dispatched")
        out[leg] = facts
    return out


# ---------------------------------------------------------- data parallel
# Relative to the one-device loss. bf16 sums in another order on four
# devices and later steps compound it: on four v5e chips the first step
# differed by 3.5e-5 and the worst of four by 1.6e-3 (chip run, PR 21).
DP_FIRST_TOL = 2e-3
DP_ALL_TOL = 5e-2


def data_parallel(*, chips: int = 4, image: int = 224, classes: int = 1000,
                  batch: int = 128, steps: int = 4,
                  dtype: str = "bfloat16", seed: int = 0) -> dict:
    """ResNet-50 through `ParallelWrapper.fit()` on a `{"data": chips}`
    mesh with sharded optimizer moments, against the same seed and
    batches through `fit()` on one device in the same process."""
    import jax

    from deeplearning4j_tpu.observe import get_registry
    from deeplearning4j_tpu.observe.watchdog import get_watchdog
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    devs = jax.devices()
    _check(len(devs) >= chips, f"data_parallel: {len(devs)} devices, "
                               f"need {chips}")
    x, y = _images(seed, steps * batch, image, classes)
    with _XlaLog() as xla:
        one = _resnet50(image, classes, dtype, seed)
        log_one = _LossLog(xla)
        one.set_listeners(log_one)
        one.fit(x, y, epochs=1, batch_size=batch)
        losses_one = log_one.host()
        del one

        net = _resnet50(image, classes, dtype, seed)
        pw = ParallelWrapper(
            net, mesh=make_mesh({"data": chips}, devices=devs[:chips]),
            shard_opt_state=True)
        log = _LossLog(xla)
        net.set_listeners(log)
        placed = pw.spine.put_batch(x[:batch])
        pw.fit(x, y, epochs=1, batch_size=batch)
        losses = log.host()
    owner = get_watchdog().snapshot()["per_owner"].get(
        pw._jit_cache.owner_tag, {})
    kinds = {}
    for row in owner.get("collectives", {}).values():
        for kind, agg in row.get("by_kind", {}).items():
            kinds[kind] = kinds.get(kind, 0) + agg["ops"]
    moments = _leaves(net.updater_state)
    rel = np.abs(np.subtract(losses, losses_one)) / np.abs(losses_one)
    facts = {
        "model": "zoo.ResNet50", "dtype": dtype, "global_batch": batch,
        "chips": chips, "steps": len(losses),
        "losses": losses, "losses_one_device": losses_one,
        "loss_rel_diff": [float(r) for r in rel],
        "tolerance_first": DP_FIRST_TOL, "tolerance_all": DP_ALL_TOL,
        "param_leaves": len(_leaves(net.params_tree)),
        "param_devices_min": min(len(leaf.sharding.device_set)
                                 for leaf in _leaves(net.params_tree)),
        "batch_shard_devices": sorted(
            str(s.device) for s in placed.addressable_shards),
        "batch_shard_rows": sorted({s.data.shape[0]
                                    for s in placed.addressable_shards}),
        "moment_leaves_split": sum(
            leaf.addressable_shards[0].data.shape != leaf.shape
            for leaf in moments),
        "moment_bytes_per_device_share": round(
            sum(leaf.addressable_shards[0].data.nbytes for leaf in moments)
            / max(1, sum(leaf.nbytes for leaf in moments)), 4),
        "collectives_in_step": kinds,
        "all_reduce_counted": get_registry().counter(
            "jit_collective_ops_total", owner="ParallelWrapper",
            kind="all-reduce").value,
        "devices": [str(d) for d in devs[:chips]],
        **xla.facts(),
    }
    _say("data_parallel", **facts)
    _check(len(losses) == steps and np.all(np.isfinite(losses)),
           f"data_parallel: losses {losses}")
    _check(rel[0] <= DP_FIRST_TOL and rel.max() <= DP_ALL_TOL,
           f"data_parallel: losses {losses} against one device "
           f"{losses_one}")
    _check(facts["param_devices_min"] == chips,
           "data_parallel: a parameter leaf does not span every device")
    _check(len(set(facts["batch_shard_devices"])) == chips
           and facts["batch_shard_rows"] == [batch // chips],
           f"data_parallel: batch shards on {facts['batch_shard_devices']}")
    _check(facts["moment_leaves_split"] > 0,
           "data_parallel: no optimizer moment is sharded")
    _check(kinds.get("all-reduce", 0) > 0
           and facts["all_reduce_counted"] > 0,
           f"data_parallel: no all-reduce counted ({kinds})")
    return facts


# ------------------------------------------------------------------- main
def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data-parallel path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX resolved platform "
              f"{dev.platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}). Nothing was run.",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s). Nothing was run.",
              file=sys.stderr)
        return 2

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
    from deeplearning4j_tpu.utils.profiling import peak_flops

    cache_dir = enable_compile_cache()
    entries_before = _cache_entries(cache_dir)
    built = native.rebuild()
    peak = peak_flops(dev.device_kind)
    _say("setup", device_kind=dev.device_kind, devices=len(jax.devices()),
         jax=jax.__version__, peak_flops=peak,
         compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         compile_cache_entries=entries_before,
         native_rebuilt_from_csrc=built,
         host_library="native" if native.available() else "numpy")
    _check(peak is not None,
           f"no peak FLOP/s known for device kind {dev.device_kind!r}")

    t0 = time.perf_counter()
    with _XlaLog() as xla:
        if args.chips == 4:
            data_parallel(chips=4, seed=args.seed)
        else:
            train(seed=args.seed)
            lstm(seed=args.seed)
            serve(seed=args.seed)
    _say("done", seconds=round(time.perf_counter() - t0, 1),
         compile_cache_entries_before=entries_before,
         compile_cache_entries_after=_cache_entries(cache_dir),
         **xla.facts())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
