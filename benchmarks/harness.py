"""What every runner shares: finding files by the names in
`BENCHMARK.json`, the compile cache, the chip check, the counters copied
from `chip_smoke.py`, and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SCRATCH = os.path.join(ROOT, ".bench_out")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX resolved no TPU, or fewer chips than the cell asks for."""


def say(kind: str, **facts) -> None:
    print(json.dumps({kind: facts}), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_module(*parts):
    """Import `benchmarks/<parts>` by path: file names are metric, config
    and runner names, which need not be Python identifiers."""
    path = os.path.join(BENCH_DIR, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def enable_compile_cache() -> str:
    """The program's own entry-point call (`<checkout>/.jax_cache`, or
    where `JAX_COMPILATION_CACHE_DIR` says), with every program cached
    however quickly it compiled, so that a second run compiles nothing."""
    import jax

    from deeplearning4j_tpu.utils.compile_cache import (
        enable_compile_cache as program_cache,
    )

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def init_in_one_program(net) -> dict:
    """`net.init()` traced into ONE compiled program that gives the net
    its layer state and its optimizer's state and NOT its parameters,
    whose shapes it returns (`{vertex: {leaf: ShapeDtypeStruct}}`): the
    benchmark places its own weights, and the net's own initial values,
    alive beside them for a moment, were a second set of the model's size
    on the device. Run eagerly `init()` is some eighty small programs, and
    fetching each from the compile cache cost 0.17 s: 14 s of every run's
    set-up (chip run, PR 24)."""
    import jax

    shapes = {}

    def traced():
        net.init()
        shapes.update(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            net.params_tree))
        return net.state_tree, net.updater_state

    net.state_tree, net.updater_state = jax.jit(traced)()
    net.params_tree = None      # the trace's own: placed by the caller
    return shapes


def require_chips(chips: int):
    """The devices of the cell, or NoChip. Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX resolved platform {devs[0].platform!r} "
                     f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_facts(used) -> dict:
    import jax

    # The TPU runtime counts a running program's temporaries as reserved,
    # not in use: a ResNet-50 step of 8.1 GiB of temporaries read 2.4 GB
    # in use and 8.7 GB reserved at its peak (chip run, PR 24). The peak a
    # job needs of the chip is the larger of the two.
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(stats.get("peak_bytes_reserved", 0))))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


class XlaLog:
    """Counts XLA compiles and persistent-cache hits and misses, from
    `jax.monitoring`, while installed. A hit also fires the compile event
    (it times compile-or-fetch), so `compiled` = events - hits.
    (A copy of `_XlaLog` in the repo's chip smoke script, PR 21.)"""

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
                "xla_compile_or_fetch_s": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_misses}


class Listener:
    """The program's training-listener protocol, with nothing in it."""

    def iteration_done(self, model, iteration, epoch, score):
        pass

    def on_fit_start(self, model):
        pass

    def on_fit_end(self, model):
        pass

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass


class LossLog(Listener):
    """Keeps every step's loss on the device (no host sync inside `fit`)
    with the host time at which it was handed over. (After chip_smoke.py's
    `_LossLog`.)"""

    def __init__(self):
        self.losses, self.times = [], []

    def iteration_done(self, model, iteration, epoch, score):
        self.losses.append(score)
        self.times.append(time.perf_counter())

    def host(self):
        import jax.numpy as jnp
        import numpy as np

        if not self.losses:
            return np.zeros((0,), np.float32)
        return np.asarray(jnp.stack(
            [jnp.asarray(x, jnp.float32) for x in self.losses]))


def platforms(*trees) -> list:
    """Platforms of the devices that hold the trees' leaves."""
    import jax

    return sorted({d.platform for tree in trees
                   for leaf in jax.tree_util.tree_leaves(tree)
                   for d in leaf.devices()})
