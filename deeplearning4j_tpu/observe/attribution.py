"""Step-time attribution: etl / dispatch / host / device segments.

`train_step_ms` says a step took 12 ms; it cannot say whether that was
the input pipeline, Python overhead, or the device actually computing —
and under async dispatch the naive fix (time the step call) measures
only the ENQUEUE, because the device runs behind the host on purpose.
Reading the device clock directly would mean forcing a sync, which is
exactly what the deferred-dispatch pipeline forbids (PyGraph's rule for
capture instrumentation, arXiv:2503.19779: near-zero steady-state
overhead or it lies to you).

But the pipeline already owns one guaranteed block point: the
LossTracker materialization at each epoch boundary (the ≤1-sync/epoch
contract). Attribution measures around it:

- per iteration (host clock, no syncs): `etl_ms` (batch wait),
  `dispatch_ms` (the step call — trace/enqueue), `host_ms` (listener
  fan-out + after_step);
- per window (epoch sync to epoch sync): `block_ms`, the time the
  epoch's one `float(loss)` waited for the device to drain the queue.

None of these is timed here. The fit loop records each segment once, as a
span (`fit.etl`, `fit.dispatch`, `fit.listeners`, `fit.epoch_sync`;
`observe/trace.py`), and `close_window` reads the window's spans out of
the span store when the epoch's sync has returned. With span recording
off (`DL4J_TPU_FLIGHT=0` and no SpanLog) there is nothing to read and no
window closes.

Device-execute time for the window is then inferred:

    device_total = min(block + dispatch + host, wall - etl)

The device provably ran for `block` ms beyond everything the host did,
plus whatever it overlapped with host work — credited up to the
dispatch+host budget, capped by the wall time outside the input
pipeline. Device-bound runs converge to `wall - etl` (the queue never
drains early); host-bound runs are bounded by dispatch+host (an upper
bound: the device may have idled). Per-step device time is the window
total divided by its step count — published as the `device` segment of
`train_step_attribution_ms`, the `train_device_step_ms` gauge, and
`last_device_step_ms()` which PerformanceListener uses as the measured
MFU denominator. An epoch of more spans than the store holds is read
from its oldest span still held.

Env: DL4J_TPU_ATTRIBUTION=0 disables.

Stdlib-only; one instance per `TrainingExecutor.run`, so instrument
handles bind to the registry active at fit start.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from deeplearning4j_tpu.observe.registry import get_registry
from deeplearning4j_tpu.observe.trace import get_span_store

SEGMENTS = ("etl", "dispatch", "host", "device")
_SEGMENT_OF = {"fit.etl": "etl", "fit.dispatch": "dispatch",
               "fit.listeners": "host"}


def attribution_enabled() -> bool:
    return os.environ.get("DL4J_TPU_ATTRIBUTION", "1") != "0"


class StepAttribution:
    """Per-fit reader of the fit loop's spans: nothing on the hot path,
    one pass over the window's spans per epoch sync."""

    def __init__(self, registry=None, store=None):
        reg = registry or get_registry()
        self._store = store or get_span_store()
        self._hist = {seg: reg.histogram("train_step_attribution_ms",
                                         segment=seg)
                      for seg in SEGMENTS}
        self._g_device = reg.gauge("train_device_step_ms")
        self._lock = threading.Lock()
        self.windows = 0
        self._last_device_ms: Optional[float] = None
        self._from = self._store.count       # the window's first span
        self._t0 = time.perf_counter_ns()

    def close_window(self, sync_start_ns: int, sync_end_ns: int) -> None:
        """The epoch's `fit.epoch_sync` span just ended: sum this thread's
        segment spans since the last window and infer the device time."""
        store, thread = self._store, threading.current_thread().name
        with self._lock:
            since, t0 = self._from, self._t0
            self._from, self._t0 = store.count, sync_end_ns
        records = [r for r in store.records(since)
                   if r[5] == thread and r[2] in _SEGMENT_OF
                   and not r[6].get("exhausted")]
        if store.count - since > store.capacity and records:
            t0 = records[0][3]
        ms = dict.fromkeys(("etl", "dispatch", "host"), 0.0)
        steps = 0
        for _, _, name, start, end, _, attrs in records:
            seg, n = _SEGMENT_OF[name], attrs.get("steps", 1)
            ms[seg] += (end - start) / 1e6
            for _ in range(n):
                self._hist[seg].observe((end - start) / 1e6 / n)
            if seg == "dispatch":
                steps += n
        if steps == 0:
            return
        block = (sync_end_ns - sync_start_ns) / 1e6
        wall = (sync_end_ns - t0) / 1e6
        device_total = min(block + ms["dispatch"] + ms["host"],
                           max(wall - ms["etl"], block))
        per_step = device_total / steps
        with self._lock:
            self.windows += 1
            self._last_device_ms = per_step
        self._hist["device"].observe(per_step)
        self._g_device.set(per_step)

    # ---------------------------------------------------------- reporting
    def last_device_step_ms(self) -> Optional[float]:
        """Most recent window's inferred device time per step (the
        measured MFU denominator); None until a window has closed."""
        with self._lock:
            return self._last_device_ms
