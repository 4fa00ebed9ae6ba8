"""DeviceMonitor — device-truth memory & live-array telemetry.

The registry/spans layer measures the HOST clock; nothing in the spine
sees HBM. On TPU the failure mode this leaves invisible is the slow
creep toward an OOM hundreds of steps away — the same pressure that
motivates cross-replica sharding of updater state to fit HBM (Xu et
al., arXiv:2004.13336). The monitor polls `device.memory_stats()`
(bytes_in_use / peak_bytes_in_use / bytes_limit) and counts live
`jax.Array`s per device into labeled gauges:

  device_memory_bytes_in_use{device="tpu:0"}
  device_memory_peak_bytes{device="tpu:0"}
  device_memory_limit_bytes{device="tpu:0"}
  device_memory_used_fraction{device="tpu:0"}
  device_live_arrays{device="tpu:0"}

and warns ONCE per device when used_fraction crosses the headroom
threshold (DL4J_TPU_HBM_WARN_FRACTION, default 0.9) — before XLA's
allocator turns the creep into a crash.

Backends that report nothing (the CPU backend returns None from
`memory_stats()`) degrade gracefully: the sample carries
`"memory_stats": None` and only the live-array gauge is published, so
every test in this repo exercises the real code path.

Polling is pull-based: `sample_once()` costs one runtime query per
device and runs (a) on demand from the `/devices` endpoint and
StatsListener reports, and (b) optionally on a background thread
(`start()`, or DL4J_TPU_DEVICEMON=1 + `maybe_start_monitor()` which the
TrainingExecutor calls at every fit). Each sample also lands in the
FlightRecorder ring, so a crash dump always carries recent device
memory.

Stdlib-only at import time; jax is imported inside `sample_once()`.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional

logger = logging.getLogger("deeplearning4j_tpu")

DEFAULT_INTERVAL_S = float(os.environ.get("DL4J_TPU_DEVICEMON_INTERVAL",
                                          "10"))
DEFAULT_WARN_FRACTION = float(os.environ.get("DL4J_TPU_HBM_WARN_FRACTION",
                                             "0.9"))

# memory_stats key -> registry gauge name
_STAT_GAUGES = (
    ("bytes_in_use", "device_memory_bytes_in_use"),
    ("peak_bytes_in_use", "device_memory_peak_bytes"),
    ("bytes_limit", "device_memory_limit_bytes"),
)


def _label(device) -> str:
    return f"{getattr(device, 'platform', '?')}:{getattr(device, 'id', '?')}"


class DeviceMonitor:
    """Poll per-device memory + live-array counts into the registry."""

    def __init__(self, *, interval_s: Optional[float] = None,
                 warn_fraction: Optional[float] = None,
                 registry=None, record_flight: bool = True):
        self.interval_s = (DEFAULT_INTERVAL_S if interval_s is None
                           else float(interval_s))
        self.warn_fraction = (DEFAULT_WARN_FRACTION if warn_fraction is None
                              else float(warn_fraction))
        self._registry = registry     # None -> resolve per sample, so a
        self.record_flight = record_flight    # test registry swap is seen
        self._lock = threading.Lock()
        self._warned: set = set()
        self._last: List[dict] = []
        self.polls = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ sampling
    def sample_once(self, devices=None) -> List[dict]:
        """One poll over `devices` (default: all jax devices). Returns
        the per-device sample list it also publishes as gauges."""
        import jax   # lazy: the observe package stays jax-free to import

        reg = self._registry
        if reg is None:
            from deeplearning4j_tpu.observe.registry import get_registry
            reg = get_registry()
        if devices is None:
            # telemetry observes every addressable device regardless of
            # which spine (if any) is active — not a placement decision
            devices = jax.devices()  # graft: allow(GL501): observer enumerates all devices, no placement
        live = self._live_array_counts()
        samples = []
        for d in devices:
            label = _label(d)
            sample: Dict = {"device": label,
                            "kind": getattr(d, "device_kind", "?"),
                            "live_arrays": live.get(label, 0)}
            reg.gauge("device_live_arrays",
                      device=label).set(sample["live_arrays"])
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                # backend reports nothing (e.g. the CPU runtime): keep
                # the sample shape stable so consumers see the absence
                sample["memory_stats"] = None
            else:
                for key, gname in _STAT_GAUGES:
                    v = stats.get(key)
                    if v is not None:
                        sample[key] = int(v)
                        reg.gauge(gname, device=label).set(v)
                in_use, limit = stats.get("bytes_in_use"), \
                    stats.get("bytes_limit")
                if in_use and limit:
                    frac = in_use / float(limit)
                    sample["used_fraction"] = round(frac, 4)
                    reg.gauge("device_memory_used_fraction",
                              device=label).set(frac)
                    self._maybe_warn(label, frac, in_use, limit)
            samples.append(sample)
        with self._lock:
            self._last = samples
            self.polls += 1
        if self.record_flight:
            try:
                from deeplearning4j_tpu.observe.flight import get_flight
                get_flight().record("device_memory", devices=samples)
            # graft: allow(GL403): ring breadcrumb is best-effort; the
            # gauges above are the authoritative surface
            except Exception:
                pass
        return samples

    @staticmethod
    def _live_array_counts() -> Dict[str, int]:
        """Count live jax.Arrays per device — pure host-side metadata
        (shape/placement), never the values, so counting cannot sync."""
        import jax

        counts: Dict[str, int] = {}
        try:
            for a in jax.live_arrays():
                try:
                    devs = a.devices()
                # graft: allow(GL403): an array deleted mid-iteration is
                # expected churn; skip it, keep counting
                except Exception:
                    continue
                for d in devs:
                    lbl = _label(d)
                    counts[lbl] = counts.get(lbl, 0) + 1
        # graft: allow(GL403): live_arrays is a debug API — if the
        # runtime refuses, the sample degrades to zero counts
        except Exception:
            pass
        return counts

    def _maybe_warn(self, label: str, frac: float, in_use: int,
                    limit: int) -> None:
        if frac < self.warn_fraction:
            return
        with self._lock:
            if label in self._warned:
                return
            self._warned.add(label)
        logger.warning(
            "DeviceMonitor: HBM headroom low on %s — %.1f%% of %.0f MiB "
            "in use (%.0f MiB, warn threshold %.0f%%). The next "
            "allocation spike (optimizer state, activation peak, a new "
            "compile's temp buffers) may OOM; shard updater state across "
            "replicas or shrink the batch before XLA does it for you.",
            label, frac * 100.0, limit / 2**20, in_use / 2**20,
            self.warn_fraction * 100.0)
        try:
            from deeplearning4j_tpu.observe.flight import get_flight
            get_flight().record("hbm_headroom_warning", device=label,
                                used_fraction=round(frac, 4),
                                bytes_in_use=int(in_use),
                                bytes_limit=int(limit))
        # graft: allow(GL403): the ring breadcrumb is best-effort; the
        # warning above already reached the log
        except Exception:
            pass

    # ---------------------------------------------------------- background
    @property
    def running(self) -> bool:
        with self._lock:
            t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        """Start background polling (idempotent)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, name="dl4j-tpu-devicemon", daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        # pin this run's Event: start() replaces self._stop on restart,
        # and a straggling old loop must keep waiting on its own event
        with self._lock:
            stop = self._stop
        while not stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:
                logger.debug("DeviceMonitor: sample failed", exc_info=True)

    def stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            thread, self._thread = self._thread, None
            self._stop.set()
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)

    # ---------------------------------------------------------- reporting
    def last_samples(self) -> List[dict]:
        with self._lock:
            return list(self._last)

    def snapshot(self) -> dict:
        with self._lock:
            return {"interval_s": self.interval_s,
                    "warn_fraction": self.warn_fraction,
                    "polls": self.polls,
                    "running": self.running,
                    "devices": list(self._last)}


# ------------------------------------------------------------ process-wide
_monitor: Optional[DeviceMonitor] = None
_install_lock = threading.Lock()


def get_device_monitor() -> DeviceMonitor:
    global _monitor
    if _monitor is None:
        with _install_lock:
            if _monitor is None:
                _monitor = DeviceMonitor()
    return _monitor


def set_device_monitor(mon: DeviceMonitor) -> Optional[DeviceMonitor]:
    """Swap the process-wide monitor (tests pin intervals/registries);
    returns the previous one."""
    global _monitor
    with _install_lock:
        prev, _monitor = _monitor, mon
    return prev


def tree_device_bytes(tree) -> Dict[str, int]:
    """Per-device resident bytes for a pytree of jax.Arrays, summed from
    addressable shards. Works where memory_stats() reports nothing (the
    CPU runtime) and attributes bytes to the devices a sharded array
    actually occupies — a replicated leaf counts its full nbytes on every
    device, a sharded leaf only its shard. Pure host-side metadata
    (shape/sharding), never values, so sampling cannot sync."""
    per: Dict[str, int] = {}
    for leaf in _tree_leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()) or ():
            label = _label(sh.device)
            data = sh.data
            if data is not None:
                per[label] = per.get(label, 0) + int(data.nbytes)
    return per


def _tree_leaves(tree):
    import jax   # lazy: the observe package stays jax-free to import
    return jax.tree_util.tree_leaves(tree)


def device_memory_summary() -> Optional[List[dict]]:
    """One best-effort sample for embedding in reports (StatsListener,
    flight dumps); None when jax is unavailable or broken."""
    try:
        return get_device_monitor().sample_once()
    except Exception:
        return None


def maybe_start_monitor() -> bool:
    """Start background polling iff DL4J_TPU_DEVICEMON is truthy
    (default off — on-demand sampling is free; a poll thread is a
    choice). Idempotent; the TrainingExecutor calls this at every fit."""
    if os.environ.get("DL4J_TPU_DEVICEMON", "0").lower() in (
            "0", "", "false"):
        return False
    get_device_monitor().start()
    return True
