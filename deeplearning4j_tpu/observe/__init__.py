"""Unified observability core: one telemetry spine for training + serving.

Before this package, observability was four disconnected islands
(`optim/listeners.py` counters, `utils/profiling.py` traces, `ui/stats.py`
reports, `serving/metrics.py`'s private aggregator) and the two costs that
silently destroy TPU utilization — jit-cache recompiles from shape churn
and accidental host syncs in the deferred-dispatch pipeline — were
invisible at runtime. This package is the one instrumentation contract
every layer shares:

- `MetricsRegistry` (`registry.py`) — process-wide counters, gauges, and
  histograms with bounded reservoirs and labeled series; thread-safe;
  snapshot + Prometheus-text + JSONL exporters. The serving `/metrics`
  endpoint and the training listeners are renderers over this registry.
- `span()` (`trace.py`) — the program's one span record: name, start
  and end on `time.perf_counter_ns()`, id, parent, thread and plain
  attributes, kept in a bounded in-memory store and written out only
  when a `SpanLog` is closed, a flight dump is taken or `fit()` ends.
  Spans time HOST work only and never call `float()` /
  `block_until_ready()` on device values, so recording cannot stall the
  dispatch pipeline (pinned by the ≤1-sync-per-epoch test). The fit
  loop's segments (`fit.etl` > `data.put`, `fit.dispatch`,
  `fit.listeners`, `fit.epoch_sync`) are timed once, by their spans;
  `utils/profiling.ProfilerListener` writes them beside a device trace
  with what links the two clocks. Set-up is timed the same way, from
  this package's import on: `import.<subpackage>` round each
  `__init__.py`'s imports, `net.init`, `wrapper.init`, `step.build`.
- `RecompileWatchdog` (`watchdog.py`) — counts every jit-cache compile
  across the per-model `_jit_cache` seams and warns once per model when
  compiles cross a churn threshold (the classic silent 10x). Its
  `listen_for_compiles` hears every compile of the process from
  `jax.monitoring` and leaves JAX's timed regions as the spans
  `xla.trace`, `xla.lower` and `xla.compile` (`fun_name`, `fetched`)
  with the counter `xla_compiles_total{fetched=}` and the histogram
  `xla_compile_ms`; its probe of a cached step's first call is the span
  `compile.probe`, a child a leg.
- `HostSyncMonitor` (`syncmon.py`) — opt-in runtime generalization of the
  test-only dispatch-depth guard: counts device→host materializations so
  `PerformanceListener` can report syncs/step in production.
- `LockWitness` (`lockmon.py`) — opt-in (`DL4J_TPU_LOCKMON=1`) runtime
  cross-check for the GL7xx lockset rules: named-lock wrappers record
  per-thread acquisition orders (lock-order inversions → GL702) and
  guarded-field access races (→ GL701) during the thread-hammer suites.
- `DonationWitness` (`donatemon.py`) — opt-in (`DL4J_TPU_DONATEMON=1`)
  runtime cross-check for the GL8xx sharding/donation rules:
  `instrument()` wraps donating jitted entry points, marks donated
  buffers dead (id-pinned by strong refs), and emits GL801-tagged
  events when a stale buffer is passed back in; with the flag off the
  step function is returned unchanged (zero overhead, perf-gate
  pinned).
- comm ledger + `ReshardWitness` (`commsmon.py`) — collective-traffic
  observability: the watchdog's compile probe walks every compiled
  program's HLO for all-reduce/all-gather/reduce-scatter/
  collective-permute/all-to-all inventory
  (`jit_collective_{ops,bytes}_total{owner,kind}`, snapshot
  `collectives` blocks), and an opt-in (`DL4J_TPU_COMMSMON=1`) runtime
  witness compares committed argument shardings against the mesh
  spine's declared specs at the dispatch seams — divergences are
  GL802-tagged (`reshard_events_total{owner}`), string-comparable with
  static shardflow findings; off means the dispatch path is unchanged.
- `python -m deeplearning4j_tpu.observe.dump` (`dump.py`) — pretty-print
  a registry snapshot or tail a written span JSONL (a `SpanLog`'s, or
  the `.spans.jsonl` beside a device trace).
- `reqtrace.py` — request-scoped causal trace trees (TraceContext at the
  HTTP edge, fan-in dispatch spans, per-step session spans, training
  dispatch windows) with head-based sampling and a bounded TraceStore;
  served by `GET /trace/{id}` and embedded in flight dumps.
- `series.py` — bounded time-series history over the registry: a
  fixed-capacity ring per metric key fed by a background sampler thread
  (`DL4J_TPU_SERIES_INTERVAL`), with sliding-window rates for counters
  and windowed p50/95/99 for histograms. Host-side only: zero device
  syncs, zero compiles, zero allocation per sample (perf-gate pinned).
- `slo.py` — declarative objectives over those series with multi-window
  burn-rate alerting (fast 5m + slow 1h); firing SLOs dump the flight
  ring (`slo_breach`), mint a forced trace exemplar, degrade /healthz
  and publish `slo_burn_rate`/`slo_breaches_total`; plus the runtime
  AnomalyWatch (recompile-storm + sync-regression detectors).

The package imports only the stdlib (no jax) so the dump tool and the
registry work anywhere; jax seams are bound lazily at install time.
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.observe"):
    from deeplearning4j_tpu.observe.registry import (
        MetricsRegistry, get_registry, set_registry,
    )
    from deeplearning4j_tpu.observe.trace import (
        SpanLog, emit_manual_span, get_span_store, install_span_log, read_spans,
        span, tracing_enabled, uninstall_span_log,
    )
    from deeplearning4j_tpu.observe.watchdog import (
        RecompileWatchdog, WatchedJitCache, get_watchdog, set_watchdog,
    )
    from deeplearning4j_tpu.observe.syncmon import HostSyncMonitor, current_monitor
    from deeplearning4j_tpu.observe.lockmon import (
        LockWitness, MonitoredLock, get_witness, lockmon_enabled,
        reset_witness,
    )
    from deeplearning4j_tpu.observe.donatemon import (
        DonationWitness, UseAfterDonateError, donatemon_enabled,
        get_donation_witness, instrument, reset_donation_witness,
    )
    from deeplearning4j_tpu.observe.commsmon import (
        ReshardWitness, commsmon_enabled, get_reshard_witness,
        parse_hlo_collectives, reset_reshard_witness, summarize_collectives,
    )
    from deeplearning4j_tpu.observe.flight import (
        FlightRecorder, get_flight, latest_dump, read_dump, set_flight,
    )
    from deeplearning4j_tpu.observe.devicemon import (
        DeviceMonitor, device_memory_summary, get_device_monitor,
        maybe_start_monitor, set_device_monitor,
    )
    from deeplearning4j_tpu.observe.reqtrace import (
        TraceContext, TraceStore, active_dispatch, begin_dispatch,
        current_trace, end_dispatch, error_extra, error_trace, finish_root,
        get_trace_store, new_trace, record_span, set_trace_store,
    )
    from deeplearning4j_tpu.observe.series import (
        SeriesRing, SeriesSampler, SeriesStore, series_key,
    )
    from deeplearning4j_tpu.observe.slo import (
        SLO, AnomalyWatch, SLOEngine, default_slos,
    )

__all__ = [
    "MetricsRegistry", "get_registry", "set_registry",
    "SpanLog", "span", "install_span_log", "uninstall_span_log",
    "tracing_enabled", "read_spans", "emit_manual_span", "get_span_store",
    "RecompileWatchdog", "WatchedJitCache", "get_watchdog", "set_watchdog",
    "HostSyncMonitor", "current_monitor",
    "LockWitness", "MonitoredLock", "get_witness", "lockmon_enabled",
    "reset_witness",
    "DonationWitness", "UseAfterDonateError", "donatemon_enabled",
    "get_donation_witness", "instrument", "reset_donation_witness",
    "ReshardWitness", "commsmon_enabled", "get_reshard_witness",
    "reset_reshard_witness", "parse_hlo_collectives",
    "summarize_collectives",
    "FlightRecorder", "get_flight", "set_flight", "latest_dump", "read_dump",
    "DeviceMonitor", "device_memory_summary", "get_device_monitor",
    "maybe_start_monitor", "set_device_monitor",
    "TraceContext", "TraceStore", "get_trace_store", "set_trace_store",
    "new_trace", "finish_root", "record_span", "error_trace", "error_extra",
    "current_trace", "begin_dispatch", "active_dispatch", "end_dispatch",
    "SeriesRing", "SeriesSampler", "SeriesStore", "series_key",
    "SLO", "AnomalyWatch", "SLOEngine", "default_slos",
]
