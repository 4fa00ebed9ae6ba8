"""One span record for the program: host spans in memory, on one clock.

`span("fit.dispatch", iteration=7)` times HOST-side work. A finished span
is one tuple in a bounded in-memory store (`SpanStore`, a ring of
`CAPACITY` records): name, start and end in `time.perf_counter_ns()`
(monotonic; the ONE clock of every span), its id, its parent's id (the
span open on the same thread when it started), the thread's name and a
few plain attributes. Nothing is serialized or written while the program
runs: a `SpanLog` writes its JSON lines when it is closed or flushed (the
fit loop flushes it when `fit()` ends), the flight recorder reads the
store when a dump is taken, and `utils/profiling.ProfilerListener` writes
the store beside the device trace it records. The contract that makes a
span safe in the training hot loop:

- A span never calls `float()` / `block_until_ready()` / `repr()` on a
  device value. Attributes are kept only if they are plain JSON scalars;
  anything else (a jax array too) is recorded as its type name, NOT its
  value, so no span holds a device buffer or forces a host sync.
- Recording is on while a `SpanLog` is installed or the flight recorder
  is enabled (the default; `DL4J_TPU_FLIGHT=0` turns it off). It is on
  from this module's import, before anything asks for the recorder, so
  that the package's own `import.*` spans and a `net.init` before the
  first `fit()` are kept. Off, a span still reads the clock twice
  (callers take their durations from it) and records nothing.

The wall clock is read once per root span (`fit`): `SpanStore.anchor`
pairs it with the span clock, which dates a written record (`ts`) and
links the spans to a device trace that follows the wall clock.

A written record is {"name", "ts", "dur_ms", "start_ns", "end_ns",
"span_id", "parent_id", "thread", "attrs"}; `read_spans` loads a file
back and `python -m deeplearning4j_tpu.observe.dump` prints one.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

CAPACITY = 4096     # a 10 s window of 100 ms steps makes about 600 spans

_ids = itertools.count(1)
_tls = threading.local()
_active_log: Optional["SpanLog"] = None
_install_lock = threading.Lock()

# Truthy while spans are recorded with no SpanLog installed: from import
# by the flight recorder's own switch, then whatever observe/flight.py
# sets (`_set_flight_sink`: the process-wide recorder while it is enabled,
# None for a disabled one). This module never imports flight (no cycle).
_flight_sink = os.environ.get("DL4J_TPU_FLIGHT", "1") != "0" or None

_PLAIN = (str, int, float, bool, type(None))

# one finished span: (span_id, parent_id, name, start_ns, end_ns, thread,
# attrs). A tuple in a preallocated list: no dict and no JSON per span.
Record = Tuple[int, Optional[int], str, int, int, str, Dict[str, Any]]


def _set_flight_sink(sink) -> None:
    global _flight_sink
    _flight_sink = sink


def _sanitize(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """In place: JSON scalars stay, everything else becomes its type
    name, so an attribute can neither hold a device buffer nor force a
    device sync when it is written out."""
    for k, v in attrs.items():
        if not isinstance(v, _PLAIN):
            attrs[k] = type(v).__name__
    return attrs


class SpanStore:
    """Fixed-size ring of finished spans, oldest overwritten first."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = int(capacity)
        self._slots: List[Optional[Record]] = [None] * self.capacity
        self._lock = threading.Lock()
        self.count = 0                  # spans ever recorded
        # (wall ns, span-clock ns) read together at the last root span
        self.anchor = (time.time_ns(), time.perf_counter_ns())

    def add(self, rec: Record) -> int:
        with self._lock:
            self._slots[self.count % self.capacity] = rec
            self.count += 1
            return self.count

    def records(self, since: int = 0) -> List[Record]:
        """The spans numbered `since` and later (0-based, in the order
        they finished) that the ring still holds."""
        with self._lock:
            lo = max(since, self.count - self.capacity)
            return [self._slots[i % self.capacity]
                    for i in range(lo, self.count)]

    def event(self, rec: Record) -> dict:
        """One record as it is written: adds `ts` (wall seconds of the
        start, by the anchor) and `dur_ms`."""
        sid, parent, name, start, end, thread, attrs = rec
        wall, clock = self.anchor
        return {"name": name, "ts": round((wall + start - clock) / 1e9, 6),
                "dur_ms": round((end - start) / 1e6, 4),
                "start_ns": start, "end_ns": end, "span_id": sid,
                "parent_id": parent, "thread": thread, "attrs": attrs}

    def events(self, since: int = 0) -> List[dict]:
        return [self.event(r) for r in self.records(since)]


_store = SpanStore()


def get_span_store() -> SpanStore:
    """The process-wide store (what `span()` records into)."""
    return _store


class SpanLog:
    """JSONL file of the spans recorded while it is installed. Lines are
    written from the store by `flush()` / `close()`, never per span."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._f = open(path, "a")
        self._next = _store.count       # first span this log has to write
        self.events = 0

    def emit(self, event: dict) -> None:
        """Write one ready-made event (request spans of `reqtrace`)."""
        self._write([event])

    def flush(self) -> None:
        with self._lock:
            since, self._next = self._next, _store.count
        self._write(_store.events(since))

    def _write(self, events: List[dict]) -> None:
        text = "".join(json.dumps(e) + "\n" for e in events)
        with self._lock:
            if self._f is None:
                return
            self._f.write(text)
            self._f.flush()
            self.events += len(events)

    def close(self) -> None:
        self.flush()
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


def install_span_log(path_or_log) -> SpanLog:
    """Enable span recording process-wide; returns the active SpanLog."""
    global _active_log
    log = (path_or_log if isinstance(path_or_log, SpanLog)
           else SpanLog(path_or_log))
    with _install_lock:
        _active_log = log
    return log


def uninstall_span_log() -> None:
    global _active_log
    with _install_lock:
        log, _active_log = _active_log, None
    if log is not None:
        log.close()


def flush_span_log() -> None:
    """Write out what the installed SpanLog has not written yet (the fit
    loop calls this when `fit()` ends)."""
    log = _active_log
    if log is not None:
        log.flush()


def tracing_enabled() -> bool:
    return _active_log is not None


def recording_enabled() -> bool:
    """Are finished spans kept (a SpanLog or the flight switch)?"""
    return _active_log is not None or _flight_sink is not None


def _stack() -> List["span"]:
    """This thread's open spans, outermost first."""
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _record(rec: Record) -> None:
    n = _store.add(rec)
    log = _active_log
    if log is not None and n - log._next >= _store.capacity:
        log.flush()         # the ring is about to overwrite unwritten spans


class span:
    """Time a host-side region: `with span("fit.etl") as attrs: ...`.

    Yields the (mutable) attrs dict while a SpanLog is installed, so that
    callers can add host values found inside the span, and None otherwise.
    Kept as an object (`s = span(...)`; `with s: ...`) it gives the two
    clock reads back: `start_ns`, `end_ns`, `dur_ms`, so the caller needs
    no stopwatch of its own."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "start_ns",
                 "end_ns")

    def __init__(self, name: str, /, **attrs):
        self.name = name
        self.attrs = attrs
        self.span_id = self.parent_id = None
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> Optional[dict]:
        log = _active_log
        if log is not None or _flight_sink is not None:
            st = _stack()
            self.parent_id = st[-1].span_id if st else None
            self.span_id = next(_ids)
            st.append(self)
        self.start_ns = time.perf_counter_ns()
        if self.span_id is not None and self.parent_id is None:
            _store.anchor = (time.time_ns(), self.start_ns)
        return self.attrs if log is not None else None

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self.span_id is not None:
            _stack().pop()
            _record((self.span_id, self.parent_id, self.name, self.start_ns,
                     self.end_ns, threading.current_thread().name,
                     _sanitize(self.attrs)))
        return False

    @property
    def dur_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


def emit_manual_span(name: str, start_ns: int, end_ns: int, /,
                     **attrs) -> None:
    """Record a span whose bounds were read elsewhere, on the span clock
    (`time.perf_counter_ns()`): a profiler capture bracketed by listener
    callbacks, a window closed by a later event."""
    if not recording_enabled():
        return
    st = _stack()
    _record((next(_ids), st[-1].span_id if st else None, name,
             int(start_ns), int(end_ns), threading.current_thread().name,
             _sanitize(attrs)))


def write_spans(path: str, since: int = 0, **header) -> int:
    """Write the store's spans numbered `since` and later to `path` as
    JSON lines, after one `{"span_clock": ...}` line that holds the wall
    anchor and `header`. The calling thread's spans that are still open
    (`fit`, when a listener writes) follow, ended now and marked
    `open: true`. Returns the number of spans written."""
    events = _store.events(since)
    now = time.perf_counter_ns()
    events += [_store.event((s.span_id, s.parent_id, s.name, s.start_ns, now,
                             threading.current_thread().name,
                             dict(_sanitize(s.attrs), open=True)))
               for s in _stack()]
    wall, clock = _store.anchor
    with open(path, "w") as f:
        f.write(json.dumps({"span_clock": dict(
            header, clock="perf_counter_ns", anchor_wall_ns=wall,
            anchor_clock_ns=clock)}) + "\n")
        for e in events:
            f.write(json.dumps(e) + "\n")
    return len(events)


def read_spans(path: str) -> List[dict]:
    """Load a span JSONL back into dicts (a `span_clock` header line, if
    the file has one, is left out)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                ev = json.loads(line)
                if "span_clock" not in ev:
                    out.append(ev)
    return out
