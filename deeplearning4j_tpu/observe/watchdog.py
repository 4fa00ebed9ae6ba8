"""RecompileWatchdog — make jit-cache churn loud before it eats a run.

Every model-level compiled program in this framework lives in a
`_jit_cache` dict behind the `SeqCtxJitCache` mixin
(`parallel/ring_attention.py`): `MultiLayerNetwork` / `ComputationGraph`
train-step caches, `ParallelInference`'s per-bucket forwards,
`ParallelWrapper`'s sharded steps. A compile happens exactly when a NEW
key is inserted into one of those dicts — so `WatchedJitCache`
(installed by the mixin) reports every first-time insertion here, and
the watchdog:

- counts compiles per owning object and per owner class (the class-level
  count feeds the `jit_compiles` registry counter — bounded label
  cardinality);
- records each cache key's shape signature (the repr of the cache key,
  which embeds batch/feature/timestep shapes for the shape-keyed
  caches), so `snapshot()` shows exactly WHICH shapes churned;
- warns ONCE per owner when its compile count crosses the churn
  threshold — the signal that input shapes are unbucketed and every
  batch is paying a trace+compile (the classic silent 10x).

Counting costs one lock acquisition per COMPILE (not per step): compiles
are rare by construction, so the watchdog is always on.

The dicts see a model's programs only. `listen_for_compiles` (called when
the first `WatchedJitCache` is made, while span recording is on) hears
every compile of the process from `jax.monitoring` and turns JAX's three
timed regions into spans on the span clock: `xla.trace`, `xla.lower` and
`xla.compile` (compile or fetch from the persistent cache: `fetched`),
each with JAX's `fun_name`, under whatever span is open on the thread
(the first `fit.dispatch`, a `net.init`, a `data.put`); beside them the
counter `xla_compiles_total{fetched=}` and the histogram `xla_compile_ms`.
The watchdog's own probe of a first call is the span `compile.probe` with
a child for each leg: `compile.probe.lower`, `.compile`, `.cost` and, for
a program compiled for more than one device, `.text` (the compiled
module's text, walked for collectives; a program on one device holds none
and records the empty inventory unread).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

from deeplearning4j_tpu.observe.trace import (
    emit_manual_span, recording_enabled, span,
)

logger = logging.getLogger("deeplearning4j_tpu")

DEFAULT_THRESHOLD = int(os.environ.get("DL4J_TPU_RECOMPILE_THRESHOLD", "10"))
_MAX_SIGNATURES = 64   # per-owner bound on recorded shape signatures


def _flight():
    """The crash ring, or None — watchdog events are breadcrumbs, never
    load-bearing, so any flight failure is swallowed here."""
    try:
        from deeplearning4j_tpu.observe.flight import get_flight
        return get_flight()
    # graft: allow(GL403): breadcrumbs are optional by design — compile
    # accounting must survive a broken flight recorder
    except Exception:
        return None


def _static_rules() -> str:
    """The graft-lint rules that flag recompile-churn patterns at review
    time — every watchdog warning names its static counterpart so the
    fix loop is 'run the linter', not 'read the runtime trace'."""
    try:
        from deeplearning4j_tpu.analysis.rules import runtime_hint
        return runtime_hint("recompile")
    except Exception:
        return ""


class RecompileWatchdog:
    """Counts jit compiles per owner; warn-once past `threshold`."""

    def __init__(self, *, threshold: int = DEFAULT_THRESHOLD,
                 metrics=None):
        self.threshold = max(1, int(threshold))
        self._metrics = metrics
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._signatures: Dict[str, List[str]] = {}
        self._costs: Dict[str, Dict[str, dict]] = {}
        self._collectives: Dict[str, Dict[str, dict]] = {}
        self._warned: set = set()

    def _registry(self):
        with self._lock:
            if self._metrics is None:
                from deeplearning4j_tpu.observe.registry import (
                    get_registry,
                )
                self._metrics = get_registry()
            return self._metrics

    def record_compile(self, owner_tag: str, owner_class: str,
                       key) -> None:
        """One first-time jit-cache insertion on `owner_tag` (a
        per-instance id) of class `owner_class` under cache key `key`."""
        warn_count = None
        with self._lock:
            n = self._counts.get(owner_tag, 0) + 1
            self._counts[owner_tag] = n
            sigs = self._signatures.setdefault(owner_tag, [])
            if len(sigs) < _MAX_SIGNATURES:
                sigs.append(repr(key))
            if n >= self.threshold and owner_tag not in self._warned:
                self._warned.add(owner_tag)
                warn_count = n
        self._registry().counter("jit_compiles", owner=owner_class).inc()
        fr = _flight()
        if fr is not None:
            # compiles are rare by construction — a ring breadcrumb each
            fr.record("jit_compile", owner=owner_class, tag=owner_tag,
                      key=repr(key)[:160])
        if warn_count is not None:
            with self._lock:
                recent = self._signatures.get(owner_tag, [])[-5:]
            logger.warning(
                "RecompileWatchdog: %s has compiled %d distinct jit "
                "programs (threshold %d) — likely shape churn (dynamic "
                "batch/sequence sizes defeating the jit cache). Recent "
                "cache keys: %s. Bucket input shapes (pad to fixed "
                "batch/length buckets, as ParallelInference does) or "
                "raise DL4J_TPU_RECOMPILE_THRESHOLD if this workload "
                "legitimately needs many programs. graft-lint rules %s "
                "flag the source patterns (python -m "
                "deeplearning4j_tpu.analysis).",
                owner_tag, warn_count, self.threshold, recent,
                _static_rules() or "n/a")
            if fr is not None:
                # threshold trip = the black-box moment: dump the ring so
                # the churned signatures survive the run
                fr.record("recompile_threshold_trip", owner=owner_class,
                          tag=owner_tag, compiles=warn_count,
                          threshold=self.threshold)
                fr.dump("recompile_threshold")

    def record_cost(self, owner_tag: str, owner_class: str, key,
                    cost: dict) -> None:
        """Attach an XLA cost report (flops / bytes_accessed /
        peak_memory_bytes, absent keys omitted) to a compile — fed by
        the `_CostProbe` the WatchedJitCache installs, or by
        `utils.profiling.step_cost` on the AOT path."""
        entry = {k: v for k, v in cost.items() if v is not None}
        with self._lock:
            costs = self._costs.setdefault(owner_tag, {})
            sig = repr(key)
            if len(costs) < _MAX_SIGNATURES or sig in costs:
                costs[sig] = entry
        reg = self._registry()
        if entry.get("flops"):
            reg.counter("jit_compile_flops_total",
                        owner=owner_class).inc(entry["flops"])
        if entry.get("bytes_accessed"):
            reg.counter("jit_compile_bytes_total",
                        owner=owner_class).inc(entry["bytes_accessed"])
        fr = _flight()
        if fr is not None:
            fr.record("compile_cost", owner=owner_class, tag=owner_tag,
                      key=repr(key)[:160], **entry)

    def record_collectives(self, owner_tag: str, owner_class: str, key,
                           summary: dict) -> None:
        """Attach a compiled-module collective inventory (the comm
        ledger block from `commsmon.summarize_collectives`) to a
        compile — fed by the `_CostProbe`'s compiled-artifact walk.
        Publishes the `jit_collective_ops_total` /
        `jit_collective_bytes_total{owner,kind}` counters (owner-CLASS
        label, bounded cardinality like `jit_compiles`)."""
        with self._lock:
            rows = self._collectives.setdefault(owner_tag, {})
            sig = repr(key)
            if len(rows) < _MAX_SIGNATURES or sig in rows:
                rows[sig] = dict(summary)
        from deeplearning4j_tpu.observe.commsmon import (
            publish_collectives,
        )
        publish_collectives(owner_class, summary,
                            registry=self._registry())
        if summary.get("ops"):
            fr = _flight()
            if fr is not None:
                fr.record("compile_collectives", owner=owner_class,
                          tag=owner_tag, key=repr(key)[:160],
                          ops=summary["ops"],
                          wire_bytes=summary["wire_bytes"])

    # --------------------------------------------------------- reporting
    def warned(self, owner_tag: str) -> bool:
        """Has this owner tripped the churn threshold? The deploy-gate
        seam: `ModelRegistry.deploy` checks the fresh runner's tag after
        warmup and rolls back instead of flipping a version that would
        recompile per-request."""
        with self._lock:
            return owner_tag in self._warned

    def compiles(self, owner_tag: Optional[str] = None) -> int:
        with self._lock:
            if owner_tag is not None:
                return self._counts.get(owner_tag, 0)
            return sum(self._counts.values())

    def owner_comm_totals(self, owner_tag: str) -> Optional[dict]:
        """Collective totals across every program this owner compiled
        ({"programs", "ops", "wire_bytes"}), or None when the comm
        ledger recorded nothing — the cheap host-side read the dispatch
        spans attach. Zero really means zero: degenerate
        single-participant ops never count (commsmon contract)."""
        with self._lock:
            rows = self._collectives.get(owner_tag)
            if rows is None:
                return None
            return {"programs": len(rows),
                    "ops": sum(r.get("ops", 0) for r in rows.values()),
                    "wire_bytes": sum(r.get("wire_bytes", 0)
                                      for r in rows.values())}

    def comm_totals(self) -> dict:
        """Whole-process comm rollup keyed by owner tag (flight dumps
        embed this next to the per-owner snapshot)."""
        with self._lock:
            tags = list(self._collectives)
        out = {}
        for tag in tags:
            tot = self.owner_comm_totals(tag)
            if tot is not None:
                out[tag] = tot
        return out

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "threshold": self.threshold,
                "static_rules": _static_rules(),
                "total_compiles": sum(self._counts.values()),
                "per_owner": {
                    tag: {"compiles": n,
                          "signatures": list(self._signatures.get(tag, ())),
                          "costs": dict(self._costs.get(tag, {})),
                          "collectives": {
                              sig: dict(row) for sig, row in
                              self._collectives.get(tag, {}).items()},
                          "warned": tag in self._warned}
                    for tag, n in self._counts.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._signatures.clear()
            self._costs.clear()
            self._collectives.clear()
            self._warned.clear()


def _cost_probe_enabled() -> bool:
    return os.environ.get("DL4J_TPU_COMPILE_COST", "1") != "0"


def _comm_ledger_enabled() -> bool:
    """The compile-time collective ledger (commsmon's static leg).
    Default ON like the cost probe — it prices one extra AOT compile
    per first-seen program, never a hot-path call. `DL4J_TPU_COMPILE_COMM=0`
    drops back to the cost-analysis-only ledger."""
    return os.environ.get("DL4J_TPU_COMPILE_COMM", "1") != "0"


_cost_failure_logged = False


def note_cost_analysis_failure(detail: str) -> None:
    """Cost analysis breaking must be visible, not silent (before this,
    `step_flops` swallowed every exception and MFU just disappeared):
    DEBUG-log the first failure, count every one — and never raise on a
    training path."""
    global _cost_failure_logged
    try:
        from deeplearning4j_tpu.observe.registry import get_registry
        get_registry().counter("profiling_cost_analysis_failures").inc()
    # graft: allow(GL403): the counter is the reporting channel — if the
    # registry itself is broken, the DEBUG log below still fires
    except Exception:
        pass
    if not _cost_failure_logged:
        _cost_failure_logged = True
        logger.debug(
            "compile cost analysis unavailable (%s); further failures "
            "are counted in profiling_cost_analysis_failures", detail)


def _arg_specs(args, kw):
    """ShapeDtypeStructs for the array arguments of a jit call (non-array
    leaves pass through untouched, so static args keep their values).
    Committed shardings ride along: without them the probe's lowering is
    an unsharded program, GSPMD inserts no collectives, and the comm
    ledger would read zero on every sharded owner."""
    try:
        import jax

        def spec(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                sharding = getattr(x, "sharding", None)
                if sharding is not None and getattr(
                        x, "_committed", True):
                    try:
                        return jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=sharding)
                    # graft: allow(GL403): a sharding ShapeDtypeStruct
                    # rejects (e.g. non-XLA-compatible sharding) →
                    # degrade to the unsharded spec below; the ledger
                    # then under-reports collectives rather than
                    # poisoning the dispatch path
                    except Exception:
                        pass
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        return jax.tree_util.tree_map(spec, (args, kw))
    except Exception:
        note_cost_analysis_failure("argument spec capture failed")
        return None


def _cost_of(artifact) -> dict:
    cost = artifact.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def _record_lowered_cost(fn, specs, owner_tag, owner_class, key) -> None:
    """The probe of a cached step's first call, as the span
    `compile.probe` with one child a leg: a second lowering of the step
    (a second whole trace), its compile (the comm ledger's; served from
    a cache where the dispatch's own compile filled one), XLA's cost
    analysis of either, and, where the program runs on more than one
    device, the text of the compiled module with its parse."""
    with span("compile.probe", owner=owner_class):
        try:
            spec_args, spec_kw = specs
            with span("compile.probe.lower"):
                lowered = fn.lower(*spec_args, **spec_kw)
        except Exception as e:
            note_cost_analysis_failure(
                f"lowering cost analysis failed: {type(e).__name__}")
            return
        # the comm ledger rides the same lowering; a failed cost_analysis
        # does not forfeit the collective walk (and vice versa)
        compiled = None
        if _comm_ledger_enabled():
            try:
                with span("compile.probe.compile"):
                    compiled = lowered.compile()
            except Exception as e:
                note_cost_analysis_failure(
                    f"compiled-HLO comm walk failed: {type(e).__name__}")
        try:
            with span("compile.probe.cost"):
                cost = _cost_of(lowered)
                if not cost.get("flops") and compiled is not None:
                    # the TPU client prices compiled modules only: there
                    # Lowered.cost_analysis() is None
                    cost = _cost_of(compiled)
            get_watchdog().record_cost(owner_tag, owner_class, key, {
                "flops": float(cost.get("flops") or 0.0),
                "bytes_accessed": float(cost.get("bytes accessed") or 0.0),
            })
        except Exception as e:
            note_cost_analysis_failure(
                f"lowering cost analysis failed: {type(e).__name__}")
        if compiled is not None and _device_count(compiled) == 1:
            # nothing to walk for: what the parse of a one-device module
            # gives, without its text (nine tenths of the probe)
            from deeplearning4j_tpu.observe.commsmon import (
                summarize_collectives,
            )
            get_watchdog().record_collectives(
                owner_tag, owner_class, key, summarize_collectives(()))
        elif compiled is not None:
            with span("compile.probe.text"):
                _record_compiled_comm(compiled, owner_tag, owner_class, key)


def _device_count(compiled) -> int:
    """The devices a compiled program runs on, from its own input and
    output shardings (not the process's: a one-device program may run on
    a host with four); 0 where they do not say."""
    try:
        import jax

        return len(set().union(*(
            s.device_set for s in jax.tree_util.tree_leaves(
                (compiled.input_shardings, compiled.output_shardings)))))
    # graft: allow(GL403): unknown reads as "walk the text", as before
    except Exception:
        return 0


def _record_compiled_comm(compiled, owner_tag, owner_class, key) -> None:
    """Walk the compiled artifact for the collective inventory.

    Degradation contract (commsmon): a backend that cannot AOT-compile,
    or a jax version whose `as_text()` shape differs, degrades to the
    cost-analysis-only ledger — the failure logs once via
    `note_cost_analysis_failure`, counts in
    `profiling_cost_analysis_failures`, and NEVER raises into the jit
    cache seam. An artifact that compiles but yields unparseable text
    records an EMPTY inventory (parse tolerance lives in the parser)."""
    try:
        from deeplearning4j_tpu.observe.commsmon import (
            parse_hlo_collectives, summarize_collectives,
        )
        text = compiled.as_text()
        if not isinstance(text, str):       # as_text() shape drifted
            raise TypeError(type(text).__name__)
        summary = summarize_collectives(parse_hlo_collectives(text))
        get_watchdog().record_collectives(owner_tag, owner_class, key,
                                          summary)
    except Exception as e:
        note_cost_analysis_failure(
            f"collective inventory failed: {type(e).__name__}")


class _CostProbe:
    """Transparent wrapper around a cached jit callable that, on its
    FIRST invocation, AOT-lowers the same function against the call's
    shape specs and records the XLA cost report with the watchdog — so
    every first-time compile the watchdog counts also carries what it
    costs.

    Why at call time, not insert time: insertion sees only the callable;
    lowering needs the concrete argument avals. Why specs are captured
    BEFORE the call runs: donated input buffers are deleted by the call
    itself. `Lowered.cost_analysis()` traces but does not compile, so
    the cost leg costs one extra trace. The comm-ledger leg
    (`DL4J_TPU_COMPILE_COMM`, default on) additionally AOT-compiles the
    lowering and prices the program from that artifact where the lowering
    has no cost (the TPU client); where the artifact's shardings span
    more than one device it walks the post-GSPMD module's text for
    collectives, and records the empty inventory of a one-device program
    without reading the text. JAX serves that compile from its in-memory
    cache when the lowering matches the dispatch's (one XLA compile, not
    two: chip run, PR 21); it is never counted as a jit cache insertion
    and never on a steady-state path; nothing either leg touches can
    force a device sync."""

    __slots__ = ("fn", "_owner_tag", "_owner_class", "_key", "_done",
                 "_lock")

    def __init__(self, fn, owner_tag, owner_class, key):
        self.fn = fn
        self._owner_tag = owner_tag
        self._owner_class = owner_class
        self._key = key
        self._done = False
        self._lock = threading.Lock()

    def __call__(self, *args, **kw):
        with self._lock:
            probe, self._done = (not self._done), True
        specs = _arg_specs(args, kw) if probe else None
        out = self.fn(*args, **kw)
        if specs is not None:
            _record_lowered_cost(self.fn, specs, self._owner_tag,
                                 self._owner_class, self._key)
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


class WatchedJitCache(dict):
    """A jit-cache dict that reports first-time insertions (= compiles)
    to the watchdog, wrapping jit callables in a one-shot `_CostProbe`
    so the compile's XLA cost is recorded too. Holds only the owner's
    tag strings, never the owner itself — a cache must not keep its
    model alive."""

    __slots__ = ("owner_tag", "owner_class")

    def __init__(self, owner=None, *, owner_tag: Optional[str] = None,
                 owner_class: Optional[str] = None):
        super().__init__()
        listen_for_compiles()
        cls = owner_class or (type(owner).__name__ if owner is not None
                              else "unknown")
        self.owner_class = cls
        self.owner_tag = owner_tag or (
            f"{cls}@{id(owner):#x}" if owner is not None else cls)

    def __setitem__(self, key, value):
        if key not in self:
            get_watchdog().record_compile(
                self.owner_tag, self.owner_class, key)
            if (_cost_probe_enabled() and callable(value)
                    and hasattr(value, "lower")
                    and not isinstance(value, _CostProbe)):
                value = _CostProbe(value, self.owner_tag,
                                   self.owner_class, key)
        super().__setitem__(key, value)

    def setdefault(self, key, default=None):
        if key not in self:
            self[key] = default      # route through __setitem__
        return self[key]             # the stored (possibly probed) value

    def update(self, *args, **kw):
        for k, v in dict(*args, **kw).items():
            self[k] = v


# ----------------------------------------------------- every XLA compile
_XLA_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_xla_tls = threading.local()
_xla_listening = False
_lock = threading.Lock()


def _xla_open() -> list:
    """This thread's open timed regions of JAX, outermost first:
    [event, start on the span clock]."""
    st = getattr(_xla_tls, "open", None)
    if st is None:
        st = _xla_tls.open = []
    return st


def _on_xla_start(event, value, **kw) -> None:
    """JAX records a timed region's name as a scalar when the region
    OPENS (`dispatch.LogElapsedTimeContextManager.__enter__`): the span's
    start is read here, on the span clock."""
    if event in _XLA_SPANS:
        _xla_open().append((event, time.perf_counter_ns()))
        _xla_tls.hit = False


def _on_xla_event(event, **kw) -> None:
    # fires inside the compile region, before its duration
    if event == _CACHE_HIT:
        _xla_tls.hit = True


def _on_xla_duration(event, duration, **kw) -> None:
    name = _XLA_SPANS.get(event)
    if name is None:
        return
    end = time.perf_counter_ns()
    st = _xla_open()
    if st and st[-1][0] == event:
        start = st.pop()[1]
    else:       # the region opened before this listener was registered
        start = end - int(duration * 1e9)
    if st:
        # inside another region of this thread: an inner jit's trace in
        # its caller's, a lowering rule's traces in the lowering (a
        # ResNet-50 `init()` makes 800 such). The outer span holds them.
        return
    attrs = {"fun_name": kw.get("fun_name")}
    if name == "xla.compile":
        fetched = attrs["fetched"] = bool(getattr(_xla_tls, "hit", False))
        _xla_tls.hit = False
        from deeplearning4j_tpu.observe.registry import get_registry
        reg = get_registry()
        reg.counter("xla_compiles_total",
                    fetched="true" if fetched else "false").inc()
        reg.histogram("xla_compile_ms").observe((end - start) / 1e6)
    emit_manual_span(name, start, end, **attrs)


def listen_for_compiles() -> None:
    """Register the three callbacks above with `jax.monitoring`, once a
    process and only while span recording is on (`DL4J_TPU_FLIGHT=0`
    registers nothing). They run on compiles only, never per step. JAX is
    imported here, not at this package's import."""
    global _xla_listening
    if _xla_listening or not recording_enabled():
        return
    with _lock:
        if not _xla_listening:
            import jax.monitoring as mon

            mon.register_scalar_listener(_on_xla_start)
            mon.register_event_listener(_on_xla_event)
            mon.register_event_duration_secs_listener(_on_xla_duration)
            _xla_listening = True


# ------------------------------------------------------------ process-wide
_default_watchdog = RecompileWatchdog()


def get_watchdog() -> RecompileWatchdog:
    return _default_watchdog


def set_watchdog(watchdog: RecompileWatchdog) -> RecompileWatchdog:
    """Swap the process-wide watchdog (tests pin thresholds this way);
    returns the previous one."""
    global _default_watchdog
    with _lock:
        prev, _default_watchdog = _default_watchdog, watchdog
    return prev
