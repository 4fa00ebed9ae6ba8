"""Device-truth telemetry tests: DeviceMonitor on a stats-less backend
(CPU memory_stats() is None), HBM warn-once via fake devices,
FlightRecorder ring eviction + crash dumps (valid JSON with the
triggering exception and a device-memory sample), the /devices and
/flight serving endpoints, and the compile-cost probe at the jit-cache
seam.
"""

import json
import logging
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.observe import (
    DeviceMonitor, FlightRecorder, MetricsRegistry, RecompileWatchdog,
    get_flight, set_flight, set_registry, set_watchdog,
)
from deeplearning4j_tpu.observe.devicemon import (
    device_memory_summary, maybe_start_monitor, set_device_monitor,
)
from deeplearning4j_tpu.observe.flight import read_dump


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


@pytest.fixture
def fresh_watchdog(fresh_registry):
    wd = RecompileWatchdog(threshold=100, metrics=fresh_registry)
    prev = set_watchdog(wd)
    try:
        yield wd
    finally:
        set_watchdog(prev)


@pytest.fixture
def fresh_flight(tmp_path):
    """Swap in a recorder whose dumps land in tmp_path; restore after."""
    fr = FlightRecorder(capacity=64, dump_dir=str(tmp_path), enabled=True)
    prev = set_flight(fr)
    try:
        yield fr
    finally:
        set_flight(prev)


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


def _get_raw(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.headers.get("Content-Type", ""), resp.read().decode()


class _FakeDevice:
    """A device whose memory_stats() reports whatever the test needs —
    the TPU-shaped path exercised without a TPU."""

    def __init__(self, platform="faketpu", id=0, kind="Fake TPU v9",
                 stats=None):
        self.platform = platform
        self.id = id
        self.device_kind = kind
        self._stats = stats

    def memory_stats(self):
        return self._stats


# --------------------------------------------------------- DeviceMonitor
class TestDeviceMonitor:
    def test_cpu_backend_reports_no_memory_stats(self, fresh_registry):
        mon = DeviceMonitor(registry=fresh_registry, record_flight=False)
        samples = mon.sample_once()
        assert samples, "at least one jax device expected"
        for s in samples:
            # CPU runtime: memory_stats() is None — the sample says so
            # explicitly instead of dropping the key
            assert s["memory_stats"] is None
            assert s["device"].startswith("cpu:")
            assert isinstance(s["live_arrays"], int)
        series = fresh_registry.snapshot()["series"]
        live = series.get("device_live_arrays", [])
        assert live and all(m["labels"]["device"].startswith("cpu:")
                            for m in live)
        # no memory gauges on a stats-less backend
        assert not any(n.startswith("device_memory_") for n in series)
        assert mon.polls == 1
        assert mon.last_samples() == samples

    def test_fake_device_memory_gauges(self, fresh_registry):
        dev = _FakeDevice(stats={"bytes_in_use": 600 * 2**20,
                                 "peak_bytes_in_use": 700 * 2**20,
                                 "bytes_limit": 1000 * 2**20})
        mon = DeviceMonitor(registry=fresh_registry, record_flight=False)
        (s,) = mon.sample_once(devices=[dev])
        assert s["device"] == "faketpu:0"
        assert s["bytes_in_use"] == 600 * 2**20
        assert s["used_fraction"] == pytest.approx(0.6)
        series = fresh_registry.snapshot()["series"]

        def val(name):
            return next(m["value"] for m in series[name]
                        if m["labels"].get("device") == "faketpu:0")

        assert val("device_memory_bytes_in_use") == 600 * 2**20
        assert val("device_memory_limit_bytes") == 1000 * 2**20
        assert val("device_memory_used_fraction") == pytest.approx(0.6)

    def test_hbm_headroom_warns_once_per_device(self, fresh_registry,
                                                caplog):
        dev = _FakeDevice(stats={"bytes_in_use": 950 * 2**20,
                                 "bytes_limit": 1000 * 2**20})
        mon = DeviceMonitor(registry=fresh_registry, warn_fraction=0.9,
                            record_flight=False)
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            mon.sample_once(devices=[dev])
            mon.sample_once(devices=[dev])      # second crossing: silent
        warns = [r for r in caplog.records
                 if "HBM headroom low" in r.getMessage()]
        assert len(warns) == 1
        assert "faketpu:0" in warns[0].getMessage()

    def test_hbm_warning_lands_in_flight_ring(self, fresh_registry,
                                              fresh_flight):
        dev = _FakeDevice(stats={"bytes_in_use": 99, "bytes_limit": 100})
        mon = DeviceMonitor(registry=fresh_registry, warn_fraction=0.9)
        mon.sample_once(devices=[dev])
        kinds = [e["kind"] for e in fresh_flight.events()]
        assert "device_memory" in kinds
        assert "hbm_headroom_warning" in kinds

    def test_background_polling_thread(self, fresh_registry):
        mon = DeviceMonitor(interval_s=0.01, registry=fresh_registry,
                            record_flight=False)
        assert not mon.running
        mon.start()
        try:
            assert mon.running
            mon.start()                          # idempotent
            deadline = time.monotonic() + 5.0
            while mon.polls == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert mon.polls > 0
        finally:
            mon.stop()
        assert not mon.running

    def test_maybe_start_monitor_env_gated(self, monkeypatch):
        mon = DeviceMonitor(interval_s=60)
        prev = set_device_monitor(mon)
        try:
            monkeypatch.delenv("DL4J_TPU_DEVICEMON", raising=False)
            assert maybe_start_monitor() is False
            assert not mon.running
            monkeypatch.setenv("DL4J_TPU_DEVICEMON", "1")
            assert maybe_start_monitor() is True
            assert mon.running
            mon.stop()
        finally:
            mon.stop()
            set_device_monitor(prev)

    def test_device_memory_summary_on_cpu(self, fresh_registry):
        dm = device_memory_summary()
        assert dm is not None and dm[0]["memory_stats"] is None


# -------------------------------------------------------- FlightRecorder
class TestFlightRecorder:
    def test_ring_evicts_oldest_preserving_order(self, tmp_path):
        fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                            enabled=True)
        for i in range(10):
            fr.record("tick", i=i)
        evs = fr.events()
        assert len(evs) == 4
        assert [e["data"]["i"] for e in evs] == [6, 7, 8, 9]
        assert [e["seq"] for e in evs] == [7, 8, 9, 10]
        assert fr.snapshot()["recorded_total"] == 10

    def test_disabled_recorder_is_inert(self, tmp_path):
        fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                            enabled=False)
        fr.record("tick", i=1)
        assert fr.events() == []
        assert fr.dump("nope") is None
        assert list(tmp_path.iterdir()) == []

    def test_payload_sanitizer_never_holds_arrays(self, tmp_path):
        import jax.numpy as jnp

        fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                            enabled=True)
        fr.record("mixed", loss=jnp.ones((3,)), name="ok",
                  nested={"arr": jnp.zeros(2), "n": 1})
        (ev,) = fr.events()
        assert ev["data"]["loss"] == "ArrayImpl"
        assert ev["data"]["name"] == "ok"
        assert ev["data"]["nested"] == {"arr": "ArrayImpl", "n": 1}

    def test_dump_is_valid_json_with_exception_and_device_sample(
            self, fresh_registry, tmp_path):
        fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                            enabled=True)
        fr.record("tick", i=1)
        try:
            raise ValueError("induced telemetry failure")
        except ValueError as e:
            path = fr.dump("training_exception", exc=e)
        assert path is not None
        doc = read_dump(path)                   # json.load must succeed
        assert doc["reason"] == "training_exception"
        assert doc["exception"]["type"] == "ValueError"
        assert "induced telemetry failure" in doc["exception"]["message"]
        assert "ValueError" in doc["exception"]["traceback"]
        assert any(e["kind"] == "tick" for e in doc["events"])
        # acceptance: every dump carries >=1 device-memory sample
        assert doc["devices"] and doc["devices"][0]["device"]
        assert fr.dumps == [path]

    def test_training_exception_dumps_flight_ring(self, fresh_registry,
                                                  fresh_flight):
        from deeplearning4j_tpu.optim.listeners import TrainingListener

        class Grenade(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                if iteration >= 3:
                    raise RuntimeError("listener grenade")

        net = _net()
        net.set_listeners(Grenade())
        x, y = _data()
        with pytest.raises(RuntimeError, match="listener grenade"):
            net.fit(x, y, epochs=2, batch_size=16)
        assert len(fresh_flight.dumps) == 1
        doc = read_dump(fresh_flight.dumps[0])
        assert doc["reason"] == "training_exception"
        assert doc["exception"]["type"] == "RuntimeError"
        # the ring carried the run's spans even with no SpanLog installed
        span_names = [e["data"].get("name") for e in doc["events"]
                      if e["kind"] == "span"]
        assert "fit" in span_names
        assert doc["devices"], "dump must carry a device-memory sample"

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_scheduler_worker_crash_dumps(self, fresh_flight):
        from deeplearning4j_tpu.serving.scheduler import (
            ContinuousBatchingScheduler,
        )

        class ExplodingRegistry:
            def acquire(self, name):
                raise SystemExit("registry detonated")   # BaseException

            def release(self, entry):
                pass

        sched = ContinuousBatchingScheduler(ExplodingRegistry(), slots=1)
        try:
            # acquire-failure is contained per batch (futures get the
            # error; the worker survives) — no dump for that path
            fut = sched.submit("m", np.zeros((1, 2), np.float32))
            with pytest.raises(SystemExit):
                fut.result(timeout=30)
        finally:
            sched.shutdown()

        # a crash INSIDE the worker loop itself leaves a dump behind
        class Boom(BaseException):
            pass

        sched2 = ContinuousBatchingScheduler(ExplodingRegistry(), slots=1)
        try:
            def bad_take():
                raise Boom("worker loop fault")

            sched2._take_batch = bad_take
            sched2.submit("m", np.zeros((1, 2), np.float32))
            deadline = time.monotonic() + 10.0
            while not fresh_flight.dumps and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            sched2.shutdown()
        assert any("scheduler_worker_crash" in p
                   for p in fresh_flight.dumps)


# ------------------------------------------------------ serving endpoints
class TestTelemetryEndpoints:
    def test_devices_and_flight_endpoints(self, fresh_registry,
                                          fresh_flight):
        from deeplearning4j_tpu.serving.inference_server import (
            InferenceServer,
        )

        net = _net(n_in=4, hidden=8, n_out=2)
        srv = InferenceServer(net, batched=False)
        port = srv.start()
        try:
            ctype, text = _get_raw(port, "/devices")
            assert ctype.startswith("application/json")
            doc = json.loads(text)
            assert doc["devices"][0]["device"].startswith("cpu:")
            assert doc["devices"][0]["memory_stats"] is None
            assert doc["monitor_running"] is False

            fresh_flight.record("marker", origin="endpoint-test")
            ctype, text = _get_raw(port, "/flight")
            assert ctype.startswith("application/json")
            doc = json.loads(text)
            assert doc["enabled"] is True
            assert any(e["kind"] == "marker" for e in doc["events"])
        finally:
            srv.stop()


# ----------------------------------------------------- compile-cost probe
class TestCompileCostProbe:
    def test_first_compile_carries_nonzero_flops(self, fresh_watchdog,
                                                 fresh_registry,
                                                 fresh_flight):
        net = _net()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        snap = fresh_watchdog.snapshot()
        costs = [c for owner in snap["per_owner"].values()
                 for c in owner["costs"].values()]
        assert costs, "the watched jit cache must record compile costs"
        assert any(c.get("flops", 0) > 0 for c in costs)
        series = fresh_registry.snapshot()["series"]
        flops_counters = [m["value"]
                          for m in series.get("jit_compile_flops_total", [])]
        assert flops_counters and sum(flops_counters) > 0
        # the compile breadcrumbs reached the black box too
        kinds = {e["kind"] for e in fresh_flight.events()}
        assert "jit_compile" in kinds
        assert "compile_cost" in kinds

    def test_cost_probe_env_kill_switch(self, fresh_watchdog,
                                        monkeypatch):
        from deeplearning4j_tpu.observe.watchdog import (
            WatchedJitCache, _CostProbe,
        )

        import jax

        monkeypatch.setenv("DL4J_TPU_COMPILE_COST", "0")
        cache = WatchedJitCache(owner_class="T", owner_tag="t@1")
        fn = jax.jit(lambda a: a + 1)
        cache["k"] = fn
        assert not isinstance(cache["k"], _CostProbe)
        monkeypatch.setenv("DL4J_TPU_COMPILE_COST", "1")
        cache["k2"] = fn
        assert isinstance(cache["k2"], _CostProbe)
        # the probe is transparent: same result, attrs delegate
        out = cache["k2"](jax.numpy.ones(2))
        assert float(out[0]) == 2.0
        assert hasattr(cache["k2"], "lower")

    def test_setdefault_returns_stored_probe(self, fresh_watchdog,
                                             monkeypatch):
        from deeplearning4j_tpu.observe.watchdog import (
            WatchedJitCache, _CostProbe,
        )

        import jax

        monkeypatch.setenv("DL4J_TPU_COMPILE_COST", "1")
        cache = WatchedJitCache(owner_class="T", owner_tag="t@2")
        fn = jax.jit(lambda a: a * 2)
        got = cache.setdefault("k", fn)
        assert isinstance(got, _CostProbe)
        assert cache.setdefault("k", None) is got
