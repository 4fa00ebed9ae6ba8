"""MetricsRegistry — process-wide metric series with bounded memory.

Design constraints (they shape every choice here):

- **Hot-path cheap**: call sites hold an instrument handle
  (`registry.counter("train.iterations")`) and bump it — one short lock
  per update, no allocation proportional to traffic. Percentiles and
  rendering are computed by the READER (`snapshot()` / `to_prometheus()`),
  the way `ServingStats` already priced its `/metrics` endpoint.
- **Bounded**: histograms keep a fixed-size reservoir (`deque(maxlen=N)`)
  plus running count/sum/min/max, so an unbounded request stream cannot
  grow memory.
- **Async-dispatch safe**: instruments accept plain host numbers only.
  Passing a jax device array is the caller's sync, not ours — the
  framework call sites only ever record host-side wall times and counts
  (PERF_NOTES contract).
- **stdlib only**: importable from the dump tool / a metrics consumer
  without pulling in jax.
"""

from __future__ import annotations

import bisect
import json
import math
import re
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")

# Fixed bucket ladder shared by every process. Federation (observe/
# fedmon.py) merges replica histograms bucket-wise, which is only sound
# when all processes bucket identically — so the ladder is a module
# constant, never per-instrument. 1/2.5/5 per decade over 0.1..5e5
# (ms-ish dynamic range), plus an implicit +Inf overflow bin.
BUCKET_EDGES: Tuple[float, ...] = tuple(
    round(m * (10.0 ** e), 6)
    for e in range(-1, 6) for m in (1.0, 2.5, 5.0))
# bump when the ladder changes: merging two ladders is meaningless
BUCKET_VERSION = 1

# Prometheus exposition format version implemented by to_prometheus()
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def _prom_name(name: str) -> str:
    out = _NAME_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _prom_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    esc = lambda v: str(v).replace("\\", r"\\").replace(
        '"', r"\"").replace("\n", r"\n")
    return ("{" + ",".join(
        f'{_prom_name(k)}="{esc(v)}"' for k, v in labels) + "}")


def _prom_value(v) -> str:
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    return repr(f) if f != int(f) else str(int(f))


class Counter:
    """Monotonic count. `inc(v)` with v >= 0."""

    kind = "counter"
    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name: str, labels):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError("counters only go up; use a gauge")
        with self._lock:
            self._value += v

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def _render(self) -> dict:
        return {"value": self.value}


class Gauge:
    """Point-in-time value. `set(v)` / `inc()` / `dec()`."""

    kind = "gauge"
    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name: str, labels):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, v: float = 1.0) -> None:
        with self._lock:
            self._value += v

    def dec(self, v: float = 1.0) -> None:
        self.inc(-v)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def _render(self) -> dict:
        return {"value": self.value}


class Histogram:
    """Distribution with a bounded reservoir.

    Keeps running count/sum/min/max exactly, plus the most recent
    `reservoir` observations for quantiles (a sliding window, which is
    what a latency percentile should be anyway — ancient requests must
    not pin p99 forever)."""

    kind = "histogram"
    __slots__ = ("name", "labels", "_lock", "_reservoir", "count", "sum",
                 "_min", "_max", "_exemplars", "_buckets")

    def __init__(self, name: str, labels, reservoir: int = 4096):
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._reservoir: deque = deque(maxlen=max(8, int(reservoir)))
        self.count = 0
        self.sum = 0.0
        self._min: Optional[float] = None
        self._max: Optional[float] = None
        # per-bin (non-cumulative) counts over BUCKET_EDGES; the last
        # bin is the +Inf overflow. Exact forever (unlike the sliding
        # reservoir) so cross-process merges are loss-free.
        self._buckets = [0] * (len(BUCKET_EDGES) + 1)
        # OpenMetrics-style exemplars: recent observations that carry a
        # trace id, so a tail percentile can be joined back to the exact
        # request tree in the trace store (GET /trace/{id}).
        self._exemplars: deque = deque(maxlen=8)

    def observe(self, v: float, *, exemplar: Optional[str] = None) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            self._reservoir.append(v)
            self._buckets[bisect.bisect_left(BUCKET_EDGES, v)] += 1
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v
            if exemplar is not None:
                self._exemplars.append({"value": v,
                                        "trace_id": str(exemplar),
                                        "ts": round(time.time(), 3)})

    def buckets(self) -> List[int]:
        """Copy of the per-bin counts (len(BUCKET_EDGES) + 1 bins)."""
        with self._lock:
            return list(self._buckets)

    def exemplars(self) -> List[dict]:
        with self._lock:
            return [dict(e) for e in self._exemplars]

    def tail_exemplar(self) -> Optional[dict]:
        """The exemplar with the largest value in the window — the one
        the p99 quantile line links to."""
        exs = self.exemplars()
        return max(exs, key=lambda e: e["value"]) if exs else None

    def values(self) -> List[float]:
        """Copy of the current reservoir (reader-side percentile math)."""
        with self._lock:
            return list(self._reservoir)

    def percentiles(self, qs: Iterable[float] = (0.5, 0.95, 0.99)) -> dict:
        vals = sorted(self.values())
        if not vals:
            return {f"p{int(q * 100)}": None for q in qs}
        n = len(vals)
        return {f"p{int(q * 100)}": vals[min(n - 1, int(q * n))] for q in qs}

    def _render(self) -> dict:
        with self._lock:
            count, total = self.count, self.sum
            lo, hi = self._min, self._max
            window = len(self._reservoir)
            buckets = list(self._buckets)
        out = {"count": count, "sum": total, "min": lo, "max": hi,
               "window": window, "buckets": buckets,
               "bucket_v": BUCKET_VERSION}
        out.update(self.percentiles())
        exs = self.exemplars()
        if exs:
            out["exemplars"] = exs
        return out


class MetricsRegistry:
    """Named, labeled metric series; one per process by default
    (`get_registry()`), private instances for isolation in tests or
    per-server scoping.

    Series identity is (name, sorted label items): asking twice returns
    the SAME instrument, so handles can be cached at call sites and
    shared across threads."""

    def __init__(self, *, reservoir: int = 4096):
        self._lock = threading.Lock()
        self._series: Dict[tuple, object] = {}
        self._reservoir = reservoir
        self.created_at = time.time()

    # ------------------------------------------------------- instruments
    def _get(self, cls, name: str, labels: dict, **kw):
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        with self._lock:
            inst = self._series.get(key)
            if inst is None:
                inst = self._series[key] = cls(name, key[1], **kw)
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {inst.kind}, "
                    f"requested {cls.kind}")
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, reservoir: Optional[int] = None,
                  **labels) -> Histogram:
        return self._get(Histogram, name, labels,
                         reservoir=reservoir or self._reservoir)

    def series(self) -> List[object]:
        with self._lock:
            return list(self._series.values())

    def reset(self) -> None:
        """Drop every series (test isolation helper)."""
        with self._lock:
            self._series.clear()

    # --------------------------------------------------------- exporters
    def snapshot(self) -> dict:
        """Plain-dict view of every series — the JSON `/metrics` payload
        body."""
        out: Dict[str, list] = {}
        for inst in self.series():
            out.setdefault(inst.name, []).append({
                "type": inst.kind,
                "labels": dict(inst.labels),
                **inst._render(),
            })
        return {"ts": round(time.time(), 3), "series": out}

    def to_prometheus(self) -> str:
        """Prometheus text exposition (format version 0.0.4).

        Counters/gauges render natively; histograms render as summaries
        (quantiles from the bounded reservoir + exact _count/_sum).
        Histograms that carry exemplars append OpenMetrics-style
        `# {trace_id="..."} value ts` suffixes: the tail (max-value)
        exemplar on the 0.99 quantile line, the latest on _count."""
        by_name: Dict[str, list] = {}
        for inst in self.series():
            by_name.setdefault(inst.name, []).append(inst)
        lines: List[str] = []
        for name in sorted(by_name):
            insts = by_name[name]
            pname = _prom_name(name)
            kind = insts[0].kind
            lines.append(f"# TYPE {pname} "
                         f"{'summary' if kind == 'histogram' else kind}")
            for inst in insts:
                lab = inst.labels
                if inst.kind == "histogram":
                    tail = inst.tail_exemplar()
                    for q in (0.5, 0.95, 0.99):
                        p = inst.percentiles((q,))[f"p{int(q * 100)}"]
                        if p is None:
                            continue
                        qlab = lab + (("quantile", str(q)),)
                        line = f"{pname}{_prom_labels(qlab)} {_prom_value(p)}"
                        if q == 0.99 and tail is not None:
                            line += (f' # {{trace_id="{tail["trace_id"]}"}}'
                                     f' {_prom_value(tail["value"])}'
                                     f' {tail["ts"]}')
                        lines.append(line)
                    lines.append(f"{pname}_sum{_prom_labels(lab)} "
                                 f"{_prom_value(inst.sum)}")
                    count_line = (f"{pname}_count{_prom_labels(lab)} "
                                  f"{_prom_value(inst.count)}")
                    exs = inst.exemplars()
                    if exs:
                        last = exs[-1]
                        count_line += (
                            f' # {{trace_id="{last["trace_id"]}"}}'
                            f' {_prom_value(last["value"])} {last["ts"]}')
                    lines.append(count_line)
                else:
                    lines.append(
                        f"{pname}{_prom_labels(lab)} "
                        f"{_prom_value(inst.value)}")
        return "\n".join(lines) + "\n"

    def to_jsonl(self) -> str:
        """One JSON line per series — appendable to a log the dump tool
        tails."""
        ts = round(time.time(), 3)
        lines = []
        for inst in self.series():
            lines.append(json.dumps({
                "ts": ts, "name": inst.name, "type": inst.kind,
                "labels": dict(inst.labels), **inst._render()}))
        return "\n".join(lines) + ("\n" if lines else "")

    def export_jsonl(self, path: str) -> None:
        with open(path, "a") as f:
            f.write(self.to_jsonl())


# ------------------------------------------------------------ process-wide
_default_registry = MetricsRegistry()
_default_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every framework seam records into by
    default. Pass an explicit registry to components that should be
    isolated (tests, one-registry-per-server deployments)."""
    return _default_registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the process-wide registry; returns the previous one."""
    global _default_registry
    with _default_lock:
        prev, _default_registry = _default_registry, registry
    return prev
