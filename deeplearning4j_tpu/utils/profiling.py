"""Profiling utilities: JAX profiler traces, step FLOP analysis, MFU.

SURVEY §5 tracing gap: the reference has PerformanceListener counters but
"no kernel-level profiler in-repo"; the TPU equivalent named there is
"JAX profiler traces + per-step host metrics" — this module provides
both seams: `trace()` wraps `jax.profiler` (TensorBoard-compatible trace
directories), and `step_flops()` pulls the exact HLO flop count of a
model's compiled train step so listeners can report MFU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import logging
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observe.watchdog import note_cost_analysis_failure

logger = logging.getLogger("deeplearning4j_tpu")

# Peak dense bf16 matmul throughput per chip, FLOP/s (public spec sheets).
PEAK_FLOPS_BY_KIND = (
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

# Peak inter-chip interconnect (ICI) bandwidth per chip, bytes/s —
# aggregate across links, one direction (public spec sheets; v5e/v6e
# figures are the 4-link 2D-torus aggregates, v4/v5p the 6-link 3D).
# Paired with the commsmon comm ledger's per-device wire bytes these
# price a program's collective time — tools/comm_report.py joins the
# two to classify jit owners compute-bound vs comm-bound.
PEAK_ICI_BYTES_BY_KIND = (
    ("v6", 0.448e12),     # Trillium / v6e: 4 x ~112 GB/s
    ("v5p", 0.600e12),    # 6 x 100 GB/s
    ("v5 lite", 0.200e12),
    ("v5litepod", 0.200e12),
    ("v5e", 0.200e12),    # 4 x 50 GB/s
    ("v5", 0.600e12),
    ("v4", 0.300e12),     # 6 x 50 GB/s
    ("v3", 0.280e12),
    ("v2", 0.160e12),
)


_warned_kinds: set = set()


def peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak bf16 FLOP/s for a device kind (default: device 0).

    Unknown kinds return None AND warn once naming the kind — callers
    (PerformanceListener) must then OMIT the MFU gauge rather than
    publish NaN, and the warning is the only trace of why."""
    if device_kind is None:
        # spec-sheet lookup keys off the chip model, not placement
        device_kind = jax.devices()[0].device_kind  # graft: allow(GL501): roofline reads device kind only
    kind = device_kind.lower()
    for key, peak in PEAK_FLOPS_BY_KIND:
        if key in kind:
            return peak
    if kind not in _warned_kinds:
        _warned_kinds.add(kind)
        logger.warning(
            "peak_flops: unrecognized device kind %r — no spec-sheet "
            "peak known, so MFU will not be reported. Add the kind to "
            "PEAK_FLOPS_BY_KIND or pass peak_flops= explicitly.",
            device_kind)
    return None


def peak_ici_bytes(device_kind: Optional[str] = None) -> Optional[float]:
    """Per-chip peak interconnect bandwidth (bytes/s, one direction) for
    a device kind (default: device 0). Same contract as `peak_flops`:
    unknown kinds return None and warn once — callers must omit, never
    fabricate, a comm roofline."""
    if device_kind is None:
        # spec-sheet lookup keys off the chip model, not placement
        device_kind = jax.devices()[0].device_kind  # graft: allow(GL501): roofline reads device kind only
    kind = device_kind.lower()
    for key, peak in PEAK_ICI_BYTES_BY_KIND:
        if key in kind:
            return peak
    warn_key = ("ici", kind)
    if warn_key not in _warned_kinds:
        _warned_kinds.add(warn_key)
        logger.warning(
            "peak_ici_bytes: unrecognized device kind %r — no spec-sheet "
            "interconnect bandwidth known. Add the kind to "
            "PEAK_ICI_BYTES_BY_KIND or pass the peak explicitly.",
            device_kind)
    return None


@dataclasses.dataclass(frozen=True)
class CostReport:
    """XLA cost analysis of one compiled program: compute (flops),
    memory traffic (bytes_accessed) from `cost_analysis()`, and the
    buffer-level footprint from `compiled.memory_analysis()` —
    `peak_memory_bytes` approximates live HBM while the program runs
    (arguments + outputs + XLA temp scratch)."""

    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    peak_memory_bytes: Optional[float] = None
    argument_bytes: Optional[float] = None
    output_bytes: Optional[float] = None
    temp_bytes: Optional[float] = None

    def as_dict(self) -> dict:
        return {k: v for k, v in dataclasses.asdict(self).items()
                if v is not None}


def _normalize_cost(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def step_cost(model, features, labels) -> Optional[CostReport]:
    """Full CostReport for the model's train step: AOT-lower + compile
    the same pure step fn the fit loop jits, then read XLA's cost and
    memory analyses. Failures return None — DEBUG-logged once and
    counted in `profiling_cost_analysis_failures`, never raised."""
    try:
        fn = model.make_step_fn()
        feats = jnp.asarray(features, model.dtype)
        labs = jnp.asarray(labels)
        compiled = jax.jit(fn).lower(
            model.params_tree, model.updater_state, model.state_tree,
            jnp.asarray(0, jnp.int32), feats, labs, None, None,
            jax.random.PRNGKey(0), None).compile()
        cost = _normalize_cost(compiled.cost_analysis())
        arg = out = temp = peak = None
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        if mem is not None:
            arg = getattr(mem, "argument_size_in_bytes", None)
            out = getattr(mem, "output_size_in_bytes", None)
            temp = getattr(mem, "temp_size_in_bytes", None)
            if temp is not None:
                peak = float((arg or 0) + (out or 0) + temp)
        return CostReport(
            flops=float(cost.get("flops") or 0.0) or None,
            bytes_accessed=float(cost.get("bytes accessed") or 0.0) or None,
            peak_memory_bytes=peak,
            argument_bytes=arg, output_bytes=out, temp_bytes=temp)
    except Exception as e:
        note_cost_analysis_failure(
            f"step_cost AOT analysis failed: {type(e).__name__}")
        return None


def step_flops(model, features, labels) -> Optional[float]:
    """Exact HLO flop count of the model's train step (AOT cost analysis
    of the same pure step fn the fit loop jits)."""
    report = step_cost(model, features, labels)
    return report.flops if report is not None else None


BEACON = "dl4j_trace_beacon"     # a run is `jit_dl4j_trace_beacon(<id>)`
SPANS_SUFFIX = ".spans.jsonl"    # beside `<name>.xplane.pb`


class DeviceTrace:
    """One device-only profiler session whose file gets the program's
    spans written beside it, with what links the two clocks.

    The Python and host tracers are off. On a TPU v5e, with them on, 3 s of
    `fit()` wrote 240 to 330 MB (per-chunk events of every batch's
    host-side layout change), took 35 to 50 s to stop and idled the
    device 55 to 94% (chip runs, PR 24); device ops alone are 2 to 22 MB.

    The device trace counts from the moment its tracer started, 1 to 2 ms
    into opening the session (chip run, PR 25), which no host clock read
    can give to better than that. So `start()` and `stop()` each run a
    trivial jitted program (`BEACON`) a few times and block on it,
    between two reads of the span clock: each run's event in the trace
    lies inside its bracket, which ties the trace's zero to
    `time.perf_counter_ns()` to within the tightest bracket (about
    0.4 ms). `stop()` writes `<log_dir>/plugins/profile/<time>/
    <host>.xplane.pb` (TensorBoard's layout) and, beside it,
    `<host>.spans.jsonl`: a `span_clock` line with the brackets, then the
    spans recorded since `start()` (`observe/trace.write_spans`).
    `benchmarks/span_reduce.py` reads the pair."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.active = False
        self.spans_path: Optional[str] = None
        self._beacons: list = []
        self._from = 0

    def _beacon(self, runs: int) -> None:
        for _ in range(runs):
            t0 = time.perf_counter_ns()
            _beacon_program(self._one).block_until_ready()
            self._beacons.append([t0, time.perf_counter_ns()])

    def start(self) -> None:
        from deeplearning4j_tpu.observe.trace import get_span_store

        os.makedirs(self.log_dir, exist_ok=True)
        self._one = jnp.zeros((8, 128), jnp.float32)
        _beacon_program(self._one).block_until_ready()   # compiled outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        self._beacons, self._from = [], get_span_store().count
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.active = True
        self._beacon(3)

    def stop(self) -> Optional[str]:
        """Close the session and write both files; returns the spans'
        path (None if the profiler wrote no `.xplane.pb`)."""
        # the first run drains the device's queue, the second finds it idle
        self._beacon(2)
        jax.profiler.stop_trace()
        self.active = False
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not found:
            return None
        self.spans_path = found[-1][:-len(".xplane.pb")] + SPANS_SUFFIX
        self.write_spans()
        return self.spans_path

    def write_spans(self) -> None:
        """(Re)write the spans file of the stopped session with every span
        since `start()`: called again once `fit()` has ended, it holds the
        whole fit and not only what had finished at `stop()`."""
        from deeplearning4j_tpu.observe.trace import write_spans

        if self.spans_path is not None:
            write_spans(self.spans_path, self._from, beacon=BEACON,
                        beacons_ns=self._beacons)


def dl4j_trace_beacon(v):
    return v + 1


_beacon_program = jax.jit(dl4j_trace_beacon)


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace (viewable in TensorBoard / Perfetto) with
    the program's spans beside it: see `DeviceTrace`."""
    session = DeviceTrace(log_dir)
    session.start()
    try:
        yield log_dir
    finally:
        session.stop()


class ProfilerListener:
    """TrainingListener that captures a `DeviceTrace` over iterations
    [start_iteration, start_iteration + num_iterations): the device's ops
    and, beside them, the fit loop's spans on one clock. Attach alongside
    PerformanceListener for numbers + timeline in one run."""

    def __init__(self, log_dir: str, *, start_iteration: int = 5,
                 num_iterations: int = 5):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.num_iterations = num_iterations
        self._trace = DeviceTrace(log_dir)
        self.captured = False

    @property
    def _active(self) -> bool:
        return self._trace.active

    # TrainingListener protocol (duck-typed; no import cycle with optim)
    def on_fit_start(self, model):
        # re-arm: a listener reused across fit() calls captures one
        # trace window per fit, not one per listener lifetime
        self.captured = False

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass

    def iteration_done(self, model, iteration, epoch, score):
        if self.captured:
            return
        if not self._active and iteration >= self.start_iteration:
            self._trace.start()
            self._started_at = iteration
            self._t0 = time.perf_counter_ns()
            return
        if self._active and \
                iteration >= self._started_at + self.num_iterations:
            self._close_trace(iteration)

    def on_fit_end(self, model):
        if self._active:   # fit ended mid-capture: close the trace cleanly
            self._close_trace(getattr(model, "iteration", None))
        elif self.captured:
            self._trace.write_spans()   # now with the steps after the stop

    def _close_trace(self, end_iteration):
        from deeplearning4j_tpu.observe import emit_manual_span

        # the capture window as a span of its own, so that the spans file
        # (and a SpanLog) says which iterations the device trace covers
        emit_manual_span("jax.profiler.trace", self._t0,
                         time.perf_counter_ns(), log_dir=self.log_dir,
                         start_iteration=self._started_at,
                         end_iteration=end_iteration)
        self._trace.stop()
        self.captured = True
