"""Unified observability core tests: MetricsRegistry correctness under
threads, Prometheus exposition grammar, span JSONL round-trip,
RecompileWatchdog warn-once, HostSyncMonitor, serving /metrics content
negotiation over the shared registry, and the acceptance contract —
a full fit() with spans + watchdog enabled stays ≤1 host sync/epoch.
"""

import json
import logging
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.observe import (
    HostSyncMonitor, MetricsRegistry, RecompileWatchdog, SpanLog,
    WatchedJitCache, get_registry, get_watchdog, read_spans, set_registry,
    set_watchdog, span,
)
from deeplearning4j_tpu.observe.registry import PROMETHEUS_CONTENT_TYPE


@pytest.fixture
def fresh_registry():
    """Swap in an isolated process-wide registry; restore afterwards."""
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


@pytest.fixture
def fresh_watchdog(fresh_registry):
    wd = RecompileWatchdog(threshold=3, metrics=fresh_registry)
    prev = set_watchdog(wd)
    try:
        yield wd
    finally:
        set_watchdog(prev)


def _net(n_in=16, hidden=8, n_out=3, seed=0):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(seed)
         .list(DenseLayer(n_out=hidden, activation="relu"),
               OutputLayer(n_out=n_out, activation="softmax",
                           loss="mcxent"))
         .set_input_type(InputType.feed_forward(n_in))
         .build())).init()


def _data(n=64, n_in=16, n_out=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, n_in)).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, n)]
    return x, y


# ------------------------------------------------------------- registry
class TestMetricsRegistry:
    def test_counter_gauge_histogram_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs", model="a")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("depth")
        g.set(7)
        g.dec(2)
        assert g.value == 5
        h = reg.histogram("lat")
        for v in range(100):
            h.observe(v)
        assert h.count == 100 and h.sum == sum(range(100))
        p = h.percentiles()
        assert p["p50"] == 50 and p["p99"] == 99

    def test_same_handle_on_re_ask_and_kind_mismatch(self):
        reg = MetricsRegistry()
        assert reg.counter("x", a="1") is reg.counter("x", a="1")
        # label order does not split the series
        assert reg.counter("y", a="1", b="2") is reg.counter(
            "y", b="2", a="1")
        with pytest.raises(TypeError):
            reg.gauge("x", a="1")

    def test_histogram_reservoir_bounded(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", reservoir=16)
        for v in range(1000):
            h.observe(v)
        assert h.count == 1000          # exact running count survives
        assert len(h.values()) == 16    # memory stays bounded
        # sliding window: quantiles come from the most recent values
        assert min(h.values()) == 984

    def test_concurrent_increments_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("hammer")
        h = reg.histogram("hammer_h", reservoir=64)
        n_threads, per = 8, 1000

        def work():
            for _ in range(per):
                c.inc()
                h.observe(1.0)

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per
        assert h.count == n_threads * per
        assert h.sum == pytest.approx(n_threads * per)

    def test_snapshot_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a", k="v").inc(2)
        reg.histogram("b").observe(1.5)
        snap = reg.snapshot()
        assert snap["series"]["a"][0]["value"] == 2
        assert snap["series"]["a"][0]["labels"] == {"k": "v"}
        assert snap["series"]["b"][0]["count"] == 1
        p = tmp_path / "m.jsonl"
        reg.export_jsonl(str(p))
        lines = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert {ln["name"] for ln in lines} == {"a", "b"}


PROM_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                 # metric name
    r"(\{([a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")"    # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?" # more labels
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|NaN|[+-]Inf)$")

PROM_EXEMPLAR_SUFFIX = re.compile(
    r"^\{trace_id=\"[^\"]*\"\} "                 # exemplar labelset
    r"-?\d+(\.\d+)?([eE][+-]?\d+)? "             # exemplar value
    r"\d+(\.\d+)?$")                             # exemplar timestamp


def _assert_prometheus_grammar(text):
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            assert parts[3] in ("counter", "gauge", "summary"), line
            continue
        # OpenMetrics exemplar suffix: `<sample> # {labels} value ts`
        line, sep, exemplar = line.partition(" # ")
        if sep:
            assert PROM_EXEMPLAR_SUFFIX.match(exemplar), \
                f"bad exemplar suffix: {exemplar!r}"
        assert PROM_METRIC_LINE.match(line), f"bad exposition line: {line!r}"


class TestPrometheusExposition:
    def test_grammar(self):
        reg = MetricsRegistry()
        reg.counter("serving_requests_total", model="m", outcome="ok").inc()
        reg.gauge("queue.depth").set(3)            # dot sanitized to _
        h = reg.histogram("latency_seconds", model="m")
        for v in (0.001, 0.02, 0.5):
            h.observe(v)
        reg.gauge("weird name!").set(float("inf"))
        text = reg.to_prometheus()
        _assert_prometheus_grammar(text)
        assert "# TYPE queue_depth gauge" in text
        assert "# TYPE latency_seconds summary" in text
        assert 'latency_seconds{model="m",quantile="0.5"}' in text
        assert 'latency_seconds_count{model="m"} 3' in text
        assert "weird_name_ +Inf" in text

    def test_empty_histogram_renders_no_quantiles(self):
        reg = MetricsRegistry()
        reg.histogram("empty")
        text = reg.to_prometheus()
        _assert_prometheus_grammar(text)
        assert "quantile" not in text
        assert "empty_count 0" in text

    def test_exemplar_suffixes_only_where_observed(self):
        # the p99 line carries the max-value exemplar, _count the
        # latest; a histogram without exemplars renders plain lines,
        # and a never-observed one renders no quantile to hang an
        # exemplar on at all
        reg = MetricsRegistry()
        h = reg.histogram("lat", model="m")
        h.observe(0.2, exemplar="tr-small")
        h.observe(0.9, exemplar="tr-big")
        h.observe(0.1)
        reg.histogram("plain").observe(1.0)
        reg.histogram("bare")                     # never observed
        text = reg.to_prometheus()
        _assert_prometheus_grammar(text)
        p99 = [l for l in text.splitlines()
               if l.startswith('lat{model="m",quantile="0.99"}')][0]
        assert 'trace_id="tr-big"' in p99          # max value wins p99
        count = [l for l in text.splitlines()
                 if l.startswith('lat_count')][0]
        assert 'trace_id="tr-big"' in count        # latest with exemplar
        for line in text.splitlines():
            if line.startswith(("plain", "bare")):
                assert "trace_id" not in line
        assert "bare_count 0" in text
        assert 'bare{quantile' not in text

    def test_concurrent_observe_during_expose(self):
        # exposition walks live instruments while writers observe; the
        # reservoir copy under the instrument lock must keep every
        # render self-consistent and exception-free
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        c = reg.counter("hits")
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            try:
                while not stop.is_set():
                    h.observe(i % 100 / 10.0, exemplar=f"t{i}")
                    c.inc()
                    i += 1
            except Exception as e:       # pragma: no cover - failure path
                errors.append(e)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(50):
                text = reg.to_prometheus()
                _assert_prometheus_grammar(text)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert not errors
        final = reg.to_prometheus()
        _assert_prometheus_grammar(final)
        assert f"hits {int(c.value)}" in final
        assert h.count == int(c.value)


# ----------------------------------------------------------------- spans
class TestSpans:
    def test_disabled_is_noop(self):
        assert not observe.tracing_enabled()
        with span("x", a=1) as attrs:
            assert attrs is None

    def test_jsonl_round_trip_with_parent_linkage(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        observe.install_span_log(path)
        try:
            with span("outer", phase="warm") as oa:
                with span("inner", idx=3):
                    pass
                oa["result"] = "ok"      # host value added inside the span
        finally:
            observe.uninstall_span_log()
        evs = read_spans(path)
        assert [e["name"] for e in evs] == ["inner", "outer"]
        inner, outer = evs
        assert inner["parent_id"] == outer["span_id"]
        assert outer["parent_id"] is None
        assert outer["attrs"] == {"phase": "warm", "result": "ok"}
        assert inner["dur_ms"] <= outer["dur_ms"]

    def test_attrs_sanitized_never_serialize_arrays(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        observe.install_span_log(path)
        try:
            # "name" as an attr must not collide with the positional arg
            with span("s", arr=np.arange(3), ok=1, name="n"):
                pass
        finally:
            observe.uninstall_span_log()
        (ev,) = read_spans(path)
        # the array degraded to its TYPE NAME — its values (which for a
        # jax array would require a device sync to read) are never touched
        assert ev["attrs"] == {"arr": "ndarray", "ok": 1, "name": "n"}

    def test_emit_manual_span(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        observe.install_span_log(path)
        try:
            # bounds read elsewhere, on the span clock (nanoseconds)
            t0 = time.perf_counter_ns()
            observe.emit_manual_span("window", t0, t0 + 250_000_000,
                                     tag="t")
        finally:
            observe.uninstall_span_log()
        (ev,) = read_spans(path)
        assert ev["start_ns"] == t0 and ev["dur_ms"] == pytest.approx(250.0)
        assert abs(ev["ts"] - time.time()) < 5.0    # dated by the anchor
        assert ev["attrs"] == {"tag": "t"}

    def test_spanlog_threads_never_interleave(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        log = observe.install_span_log(SpanLog(path))
        try:
            def work(i):
                for j in range(50):
                    with span(f"t{i}", j=j):
                        pass

            ts = [threading.Thread(target=work, args=(i,))
                  for i in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            observe.uninstall_span_log()
        evs = read_spans(path)    # every line parses ⇒ no interleaving
        assert len(evs) == 200 == log.events
        assert len({e["span_id"] for e in evs}) == 200


# -------------------------------------------------------------- watchdog
class TestRecompileWatchdog:
    def test_counts_first_time_insertions_only(self, fresh_watchdog,
                                               fresh_registry):
        cache = WatchedJitCache(owner_tag="net@1", owner_class="Net")
        cache[("b32",)] = "prog1"
        cache[("b32",)] = "prog1b"          # overwrite: not a new compile
        cache.setdefault(("b64",), "prog2")
        cache.setdefault(("b64",), "IGNORED")
        cache.update({("b128",): "prog3"})
        assert fresh_watchdog.compiles("net@1") == 3
        assert fresh_registry.counter("jit_compiles", owner="Net").value == 3
        sigs = fresh_watchdog.snapshot()["per_owner"]["net@1"]["signatures"]
        assert any("b32" in s for s in sigs)

    def test_warns_exactly_once_past_threshold(self, fresh_watchdog,
                                               caplog):
        cache = WatchedJitCache(owner_tag="churny@2", owner_class="Net")
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            for i in range(10):      # threshold is 3
                cache[("shape", i)] = i
        warnings = [r for r in caplog.records
                    if "RecompileWatchdog" in r.getMessage()]
        assert len(warnings) == 1
        assert "churny@2" in warnings[0].getMessage()
        assert fresh_watchdog.compiles() == 10

    def test_jit_cache_seam_installs_watched_cache(self, fresh_watchdog):
        from deeplearning4j_tpu.parallel.ring_attention import SeqCtxJitCache

        class Holder(SeqCtxJitCache):
            pass

        h = Holder()
        cache = h._jit_cache
        assert isinstance(cache, WatchedJitCache)
        assert h._jit_cache is cache          # stable per context
        cache[(32, (16,))] = "compiled"
        assert fresh_watchdog.compiles() == 1
        tag = next(iter(fresh_watchdog.snapshot()["per_owner"]))
        assert tag.startswith("Holder@")


# --------------------------------------------------------- sync monitor
class TestHostSyncMonitor:
    def test_counts_and_take(self):
        import jax.numpy as jnp

        a = jnp.asarray(1.5)
        with HostSyncMonitor() as mon:
            float(a)
            a.block_until_ready()
            assert mon.syncs == 2
            assert mon.take() == 2
            assert mon.take() == 0        # delta semantics
            float(a)
            assert mon.syncs == 1
        # uninstalled: new syncs invisible
        float(a)
        assert mon.syncs == 1
        assert observe.current_monitor() is None

    def test_nested_monitors_share_one_patch(self):
        import jax.numpy as jnp

        a = jnp.asarray(2.0)
        with HostSyncMonitor() as outer:
            with HostSyncMonitor() as inner:
                assert observe.current_monitor() is inner
                float(a)
            assert observe.current_monitor() is outer
        assert outer.syncs == 1 and inner.syncs == 1


# ----------------------------------------------------------- listeners
class _FakeModel:
    iteration = 0
    last_batch_size = 32


class TestTimeIterationListener:
    def test_first_eligible_iteration_reports(self, caplog):
        from deeplearning4j_tpu.optim.listeners import TimeIterationListener

        lst = TimeIterationListener(total_iterations=10, frequency=1)
        m = _FakeModel()
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            lst.on_fit_start(m)
            lst.iteration_done(m, 1, 0, None)   # old code swallowed this
        assert any("iteration 1/10" in r.getMessage()
                   for r in caplog.records)

    def test_total_zero_reports_rate_without_eta(self, caplog):
        from deeplearning4j_tpu.optim.listeners import TimeIterationListener

        lst = TimeIterationListener(total_iterations=0, frequency=1)
        m = _FakeModel()
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            lst.on_fit_start(m)
            lst.iteration_done(m, 1, 0, None)
        msgs = [r.getMessage() for r in caplog.records]
        assert any("ms/iter" in s for s in msgs)
        assert not any("ETA" in s for s in msgs)

    def test_resumed_fit_rates_only_this_run(self, caplog):
        from deeplearning4j_tpu.optim.listeners import TimeIterationListener

        lst = TimeIterationListener(total_iterations=200, frequency=100)
        m = _FakeModel()
        m.iteration = 99          # resuming: 99 already-trained iterations
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            lst.on_fit_start(m)
            lst.iteration_done(m, 100, 0, None)
        # denominator is iterations done THIS run (1), not 100
        assert any("iteration 100/200" in r.getMessage()
                   for r in caplog.records)


class TestPerformanceListenerRegistry:
    def test_gauges_and_mfu_emitted(self, fresh_registry):
        from deeplearning4j_tpu.optim.listeners import PerformanceListener

        lst = PerformanceListener(frequency=1, report=lambda m: None,
                                  flops_per_step=1e9, peak_flops=1e12)
        assert lst.peak_flops == 1e12      # explicit peak is kept as-is
        m = _FakeModel()
        lst.iteration_done(m, 1, 0, None)
        lst.iteration_done(m, 2, 0, None)
        assert fresh_registry.gauge("train_samples_per_sec").value > 0
        assert fresh_registry.gauge("train_step_ms").value > 0
        mfu = fresh_registry.gauge("train_mfu").value
        assert mfu == pytest.approx(lst.last_mfu) and mfu > 0

    def test_syncs_per_step_with_monitor(self, fresh_registry):
        import jax.numpy as jnp

        from deeplearning4j_tpu.optim.listeners import PerformanceListener

        lst = PerformanceListener(frequency=1, report=lambda m: None)
        m = _FakeModel()
        with HostSyncMonitor():
            lst.iteration_done(m, 1, 0, None)
            float(jnp.asarray(1.0))
            float(jnp.asarray(2.0))
            lst.iteration_done(m, 2, 0, None)
        assert lst.last_syncs_per_step == 2.0
        assert fresh_registry.gauge(
            "train_host_syncs_per_step").value == 2.0


# ------------------------------------------------- profiler correlation
class TestProfilerListenerMidCaptureClose:
    def test_end_of_fit_closes_capture_and_emits_span(self, tmp_path):
        from deeplearning4j_tpu.utils.profiling import ProfilerListener

        net = _net()
        x, y = _data()
        # window starts at iteration 1 but is far longer than the fit:
        # on_fit_end must close the capture cleanly
        pl = ProfilerListener(str(tmp_path / "trace"), start_iteration=1,
                              num_iterations=10_000)
        net.add_listener(pl)
        path = str(tmp_path / "spans.jsonl")
        observe.install_span_log(path)
        try:
            net.fit(x, y, epochs=1, batch_size=16)
        finally:
            observe.uninstall_span_log()
        assert pl.captured and not pl._active
        traces = [e for e in read_spans(path)
                  if e["name"] == "jax.profiler.trace"]
        assert len(traces) == 1
        assert traces[0]["attrs"]["start_iteration"] == 1
        assert traces[0]["dur_ms"] > 0


# ------------------------------------------------------------ serving
def _get_raw(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.headers.get("Content-Type"), r.read().decode()


class TestServingMetricsEndpoint:
    def test_content_negotiation_and_grammar(self):
        from deeplearning4j_tpu.serving.inference_server import (
            InferenceServer,
        )

        net = _net(n_in=4, hidden=8, n_out=2)
        srv = InferenceServer(net, batched=False)
        port = srv.start()
        try:
            body = json.dumps(
                {"ndarray": [[0.1, 0.2, 0.3, 0.4]]}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/output", data=body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=30).read()

            # default stays JSON (the pre-existing consumer contract)
            ctype, text = _get_raw(port, "/metrics")
            assert ctype.startswith("application/json")
            snap = json.loads(text)
            assert snap["requests"]["completed"] == 1

            # a scraper negotiates the Prometheus exposition
            ctype, text = _get_raw(port, "/metrics",
                                   {"Accept": "text/plain"})
            assert ctype == PROMETHEUS_CONTENT_TYPE
            _assert_prometheus_grammar(text)
            assert ('serving_requests_total{model="default",'
                    'outcome="completed"} 1') in text

            # ?format=prometheus works without an Accept header
            ctype, text = _get_raw(port, "/metrics?format=prometheus")
            assert ctype == PROMETHEUS_CONTENT_TYPE
            _assert_prometheus_grammar(text)
        finally:
            srv.stop()

    def test_shared_registry_unifies_training_and_serving(
            self, fresh_registry):
        from deeplearning4j_tpu.serving.inference_server import (
            InferenceServer,
        )

        # training side records into the process registry...
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        assert fresh_registry.counter("train_iterations").value == 4

        # ...and a server built on the SAME registry scrapes both
        snet = _net(n_in=4, hidden=8, n_out=2)
        srv = InferenceServer(snet, batched=False,
                              metrics=get_registry())
        port = srv.start()
        try:
            body = json.dumps(
                {"ndarray": [[1.0, 2.0, 3.0, 4.0]]}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/output", data=body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=30).read()
            _, text = _get_raw(port, "/metrics?format=prometheus")
        finally:
            srv.stop()
        _assert_prometheus_grammar(text)
        assert "train_iterations 4" in text          # training series
        assert "serving_requests_total" in text      # serving series


# ----------------------------------------------------------- acceptance
class TestFitSyncBudgetWithObservability:
    """The acceptance contract: enabling the full observability stack
    (span log + watchdog + registry instrumentation) must not add host
    syncs — the fit loop stays ≤1 materialization per epoch."""

    def _counting_patches(self, monkeypatch, counts):
        from jax._src import array as _jarray

        orig_float = _jarray.ArrayImpl.__float__
        orig_block = _jarray.ArrayImpl.block_until_ready

        def counting_float(a):
            counts["float"] += 1
            return orig_float(a)

        def counting_block(a):
            counts["block"] += 1
            return orig_block(a)

        monkeypatch.setattr(_jarray.ArrayImpl, "__float__", counting_float)
        monkeypatch.setattr(_jarray.ArrayImpl, "block_until_ready",
                            counting_block)

    def test_fit_with_spans_and_watchdog_one_sync_per_epoch(
            self, monkeypatch, tmp_path, fresh_watchdog):
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)    # compile outside guard
        warm_compiles = fresh_watchdog.compiles()
        assert warm_compiles >= 1      # the watchdog saw the warm-up trace

        counts = {"float": 0, "block": 0}
        self._counting_patches(monkeypatch, counts)
        observe.install_span_log(str(tmp_path / "spans.jsonl"))
        try:
            epochs = 3
            net.fit(x, y, epochs=epochs, batch_size=16)
        finally:
            observe.uninstall_span_log()
        assert counts["float"] + counts["block"] <= epochs, counts
        evs = read_spans(str(tmp_path / "spans.jsonl"))
        assert sum(e["name"] == "fit.epoch" for e in evs) == epochs
        # the warm second fit added no compiles
        assert fresh_watchdog.compiles() == warm_compiles


# -------------------------------------------------------------- dump tool
class TestDumpTool:
    def test_snapshot_and_jsonl_render(self, tmp_path, capsys):
        from deeplearning4j_tpu.observe import dump

        reg = MetricsRegistry()
        reg.counter("reqs", model="m").inc(5)
        reg.histogram("lat").observe(0.25)
        snap_path = tmp_path / "snap.json"
        snap_path.write_text(json.dumps(reg.snapshot()))
        out = dump.dump_file(str(snap_path))
        assert "reqs" in out and "model=m" in out and "5" in out
        assert "count=1" in out

        # a blob may embed the snapshot under "registry"
        bench_path = tmp_path / "BENCH_x.json"
        bench_path.write_text(json.dumps(
            {"metric": "ips", "registry": reg.snapshot()}))
        assert "reqs" in dump.dump_file(str(bench_path))

        # span JSONL path + --tail via main()
        jsonl = tmp_path / "spans.jsonl"
        observe.install_span_log(str(jsonl))
        try:
            for i in range(5):
                with span("step", i=i):
                    pass
        finally:
            observe.uninstall_span_log()
        assert dump.main([str(jsonl), "--tail", "2"]) == 0
        printed = capsys.readouterr().out
        assert printed.count("step") == 2 and "i=4" in printed
