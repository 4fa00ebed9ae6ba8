"""Model-serving control plane tests: registry + hot-swap, continuous
batching, admission control, drain/shutdown guarantees, /metrics.

The scheduler/admission tests run against fake registry entries (no jax
cost, deterministic via gate events); the hot-swap / shutdown / oversize
tests drive real nets through the real HTTP server.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future

import numpy as np
import pytest

from deeplearning4j_tpu.serving.http_base import HttpError, JsonHttpServer
from deeplearning4j_tpu.serving.metrics import ServingStats
from deeplearning4j_tpu.serving.scheduler import (
    AdmissionPolicy, ContinuousBatchingScheduler, DeadlineExceededError,
    RequestShedError, SchedulerClosedError,
)


def _make_net(seed):
    from deeplearning4j_tpu import InputType
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder()
         .seed(seed).list(DenseLayer(n_out=8, activation="relu"),
                          OutputLayer(n_out=2, activation="softmax"))
         .set_input_type(InputType.feed_forward(4))
         .build())).init()


def _post(port, path, payload, timeout=30):
    data = (payload if isinstance(payload, bytes)
            else json.dumps(payload).encode())
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------- fakes
class FakeEntry:
    """Registry entry whose dispatch can be gated for determinism."""

    def __init__(self, version=1, gate=None):
        self.version = version
        self.gate = gate
        self.started = threading.Event()
        self.batches = []

    def run_batch(self, xs):
        self.started.set()
        if self.gate is not None:
            assert self.gate.wait(10), "test gate never opened"
        self.batches.append(int(np.asarray(xs).shape[0]))
        return np.asarray(xs) * 2.0


class FakeRegistry:
    def __init__(self, entry):
        self.entry = entry

    def acquire(self, name):
        if name == "ghost":
            raise KeyError(name)
        return self.entry

    def release(self, entry):
        pass

    def names(self):
        return ["m"]

    def summary(self):
        return {"m": {"version": self.entry.version}}

    def close(self):
        pass


# ------------------------------------------------------ http_base fixes
class _ErrServer(JsonHttpServer):
    def get_routes(self):
        routes = super().get_routes()
        routes["/boom"] = self._boom_get
        return routes

    def post_routes(self):
        return {"/echo": lambda req: {"got": req["field"]},
                "/boom": self._boom_post,
                "/teapot": self._teapot}

    def _boom_get(self):
        raise RuntimeError("server-side fault")

    def _boom_post(self, req):
        raise RuntimeError("server-side fault")

    def _teapot(self, req):
        raise HttpError(418, "short and stout")


class TestHttpErrorMapping:
    """Satellite: clients can tell their bug (400) from ours (500)."""

    @pytest.fixture()
    def port(self):
        srv = _ErrServer(port=0)
        yield srv.start()
        srv.stop()

    def _code(self, port, path, payload=None):
        try:
            if payload is None:
                _get(port, path)
            else:
                _post(port, path, payload)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())
        return 200, None

    def test_malformed_json_is_400(self, port):
        code, body = self._code(port, "/echo", b"{not json!")
        assert code == 400 and "malformed JSON" in body["error"]

    def test_non_object_body_is_400(self, port):
        code, body = self._code(port, "/echo", b"[1, 2, 3]")
        assert code == 400 and "JSON object" in body["error"]

    def test_missing_field_is_400(self, port):
        code, _ = self._code(port, "/echo", {"wrong": 1})
        assert code == 400

    def test_handler_fault_is_500_post(self, port):
        code, body = self._code(port, "/boom", {"x": 1})
        assert code == 500 and "server-side fault" in body["error"]

    def test_handler_fault_is_500_get(self, port):
        code, _ = self._code(port, "/boom")
        assert code == 500

    def test_http_error_status_passthrough(self, port):
        code, _ = self._code(port, "/teapot", {})
        assert code == 418

    def test_unknown_route_is_404(self, port):
        code, _ = self._code(port, "/nope", {})
        assert code == 404


# -------------------------------------------- scheduler unit behaviour
class TestContinuousBatching:
    def test_requests_accumulate_while_slot_busy(self):
        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        sched = ContinuousBatchingScheduler(
            FakeRegistry(entry), max_batch_size=64, queue_capacity=64)
        try:
            first = sched.submit("m", np.ones((1, 2)))
            assert entry.started.wait(5)
            futs = [sched.submit("m", np.ones((1, 2))) for _ in range(4)]
            gate.set()
            assert np.asarray(first.result(5)).shape == (1, 2)
            for f in futs:
                f.result(5)
            # the 4 queued requests joined ONE dispatch, not 4
            assert entry.batches == [1, 4]
        finally:
            sched.shutdown()

    def test_batch_capped_at_max_rows(self):
        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        sched = ContinuousBatchingScheduler(
            FakeRegistry(entry), max_batch_size=4, queue_capacity=64)
        try:
            first = sched.submit("m", np.ones((1, 2)))
            assert entry.started.wait(5)
            futs = [sched.submit("m", np.ones((2, 2))) for _ in range(3)]
            gate.set()
            for f in [first] + futs:
                f.result(5)
            assert entry.batches[0] == 1
            assert all(b <= 4 for b in entry.batches)
        finally:
            sched.shutdown()

    def test_unknown_model_fails_future(self):
        sched = ContinuousBatchingScheduler(
            FakeRegistry(FakeEntry()), queue_capacity=8)
        try:
            with pytest.raises(KeyError):
                sched.submit("ghost", np.ones((1, 2))).result(5)
        finally:
            sched.shutdown()


class TestAdmissionControl:
    def _blocked(self, policy, capacity, **kw):
        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        sched = ContinuousBatchingScheduler(
            FakeRegistry(entry), max_batch_size=64,
            queue_capacity=capacity, policy=policy, **kw)
        blocker = sched.submit("m", np.ones((1, 2)))
        assert entry.started.wait(5)   # slot busy; queue now accumulates
        return gate, entry, sched, blocker

    def test_shed_policy_rejects_when_full(self):
        gate, entry, sched, blocker = self._blocked(
            AdmissionPolicy.SHED, capacity=2)
        try:
            q = [sched.submit("m", np.ones((1, 2))) for _ in range(2)]
            with pytest.raises(RequestShedError):
                sched.submit("m", np.ones((1, 2)))
            assert sched.stats.snapshot()["requests"]["shed"] == 1
            gate.set()
            for f in [blocker] + q:
                f.result(5)
        finally:
            sched.shutdown()

    def test_deadline_expired_work_never_dispatched(self):
        gate, entry, sched, blocker = self._blocked(
            AdmissionPolicy.DEADLINE, capacity=8,
            default_deadline_ms=10_000)
        try:
            doomed = sched.submit("m", np.ones((1, 2)), deadline_ms=60)
            time.sleep(0.15)           # expires while queued
            gate.set()
            with pytest.raises(DeadlineExceededError):
                doomed.result(5)
            blocker.result(5)
            sched.drain(5)
            # the expired request never reached the device
            assert entry.batches == [1]
            assert sched.stats.snapshot()["requests"]["expired"] == 1
        finally:
            sched.shutdown()

    def test_block_policy_waits_for_space(self):
        gate, entry, sched, blocker = self._blocked(
            AdmissionPolicy.BLOCK, capacity=1, block_timeout_s=10)
        try:
            q1 = sched.submit("m", np.ones((1, 2)))   # fills the queue
            got = {}

            def late_submit():
                got["fut"] = sched.submit("m", np.ones((1, 2)))

            t = threading.Thread(target=late_submit)
            t.start()
            time.sleep(0.1)
            assert t.is_alive()        # blocked on admission, not shed
            gate.set()
            t.join(5)
            assert not t.is_alive()
            for f in (blocker, q1, got["fut"]):
                np.asarray(f.result(5))
        finally:
            sched.shutdown()

    def test_block_policy_times_out_as_shed(self):
        gate, entry, sched, blocker = self._blocked(
            AdmissionPolicy.BLOCK, capacity=1, block_timeout_s=0.1)
        try:
            sched.submit("m", np.ones((1, 2)))
            with pytest.raises(RequestShedError):
                sched.submit("m", np.ones((1, 2)))
        finally:
            gate.set()
            sched.shutdown()

    def test_deadline_policy_requires_default(self):
        with pytest.raises(ValueError, match="default_deadline_ms"):
            ContinuousBatchingScheduler(
                FakeRegistry(FakeEntry()),
                policy=AdmissionPolicy.DEADLINE)

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            ContinuousBatchingScheduler(
                FakeRegistry(FakeEntry()), policy="yolo")


class TestSchedulerShutdown:
    def test_queued_requests_fail_explicitly_not_hang(self):
        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        sched = ContinuousBatchingScheduler(
            FakeRegistry(entry), queue_capacity=16)
        inflight = sched.submit("m", np.ones((1, 2)))
        assert entry.started.wait(5)
        queued = [sched.submit("m", np.ones((1, 2))) for _ in range(5)]
        done = threading.Event()

        def do_shutdown():
            sched.shutdown()
            done.set()

        t = threading.Thread(target=do_shutdown)
        t.start()
        # queued work is failed IMMEDIATELY, before the in-flight batch
        # is allowed to finish
        for f in queued:
            with pytest.raises(SchedulerClosedError):
                f.result(5)
        gate.set()                     # let the in-flight batch finish
        assert done.wait(10)
        np.asarray(inflight.result(5))  # in-flight completed normally
        with pytest.raises(SchedulerClosedError):
            sched.submit("m", np.ones((1, 2)))

    def test_drain_waits_for_quiet(self):
        sched = ContinuousBatchingScheduler(
            FakeRegistry(FakeEntry()), queue_capacity=16)
        try:
            futs = [sched.submit("m", np.ones((1, 2))) for _ in range(4)]
            assert sched.drain(5)
            assert all(f.done() for f in futs)
            assert sched.queue_depth() == 0
        finally:
            sched.shutdown()


# ------------------------------------------------- data-plane (real jax)
@pytest.fixture(scope="module")
def nets():
    return _make_net(0), _make_net(123)


class TestOversizedRequests:
    """Satellite: n > max(buckets) must chunk, not key the jit cache on
    an arbitrary shape (or violate data-axis divisibility)."""

    def test_oversized_chunked_and_correct(self, nets):
        from deeplearning4j_tpu.parallel.inference import (
            InferenceMode, ParallelInference,
        )

        net, _ = nets
        pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                               max_batch_size=8, batch_buckets=[1, 4, 8])
        x = np.random.default_rng(1).standard_normal((21, 4)).astype(
            np.float32)
        got = pi.run_batch(x)
        want = np.asarray(net.output(x))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert got.shape == (21, 2)
        # every compiled shape is a (rounded) bucket — never 21
        assert all(k[0] <= 8 for k in pi._jit_cache)

    def test_oversized_through_batched_collector(self, nets):
        from deeplearning4j_tpu.parallel.inference import (
            InferenceMode, ParallelInference,
        )

        net, _ = nets
        pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                               max_batch_size=8, batch_buckets=[1, 4, 8],
                               max_wait_ms=1.0)
        try:
            x = np.random.default_rng(2).standard_normal((19, 4)).astype(
                np.float32)
            got = np.asarray(pi.output(x))
            np.testing.assert_allclose(
                got, np.asarray(net.output(x)), rtol=1e-5, atol=1e-6)
        finally:
            pi.shutdown()

    def test_warmup_compiles_buckets(self, nets):
        from deeplearning4j_tpu.parallel.inference import (
            InferenceMode, ParallelInference,
        )

        net, _ = nets
        pi = ParallelInference(net, mode=InferenceMode.INPLACE,
                               max_batch_size=8, batch_buckets=[1, 4, 8])
        assert pi.warmup((4,)) == 3
        keys = set(pi._jit_cache)
        x = np.ones((3, 4), np.float32)
        pi.run_batch(x)
        assert set(pi._jit_cache) == keys   # no new compile post-warmup


class TestShutdownMidFlight:
    """Satellite: N threads hammering while shutdown() fires — every
    request completes or fails with an explicit error; nothing hangs."""

    N_THREADS = 6

    def test_parallel_inference_shutdown_under_load(self, nets):
        from deeplearning4j_tpu.parallel.inference import (
            InferenceMode, ParallelInference,
        )

        net, _ = nets
        pi = ParallelInference(net, mode=InferenceMode.BATCHED,
                               max_batch_size=8, batch_buckets=[1, 4, 8],
                               max_wait_ms=1.0)
        pi.warmup((4,))
        outcomes = []        # "ok" | "refused"
        lock = threading.Lock()
        x = np.ones((2, 4), np.float32)

        def hammer():
            # loop until this thread OBSERVES the shutdown refusal — so
            # the shutdown is guaranteed to land mid-traffic for every
            # thread, with no sleep-tuning
            while True:
                try:
                    y = np.asarray(pi.output(x))
                    with lock:
                        outcomes.append(
                            "ok" if y.shape == (2, 2) else "bad")
                except RuntimeError:
                    with lock:
                        outcomes.append("refused")
                    return

        threads = [threading.Thread(target=hammer)
                   for _ in range(self.N_THREADS)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        pi.shutdown()
        for t in threads:
            t.join(20)
        assert not any(t.is_alive() for t in threads), "a request hung"
        assert "bad" not in outcomes
        assert outcomes.count("ok") > 0          # served before shutdown
        # every thread ended on an explicit refusal, none hung
        assert outcomes.count("refused") == self.N_THREADS
        assert pi.drain(5)                       # nothing left pending

    def test_server_stop_under_load(self, nets):
        from deeplearning4j_tpu.serving import InferenceServer

        net, _ = nets
        srv = InferenceServer(net, port=0, max_batch_size=8,
                              batch_buckets=[1, 4, 8])
        port = srv.start()
        x = np.ones((1, 4), np.float32).tolist()
        _post(port, "/output", {"ndarray": x})   # warm path
        outcomes = []
        lock = threading.Lock()

        def hammer():
            for _ in range(25):
                try:
                    _post(port, "/output", {"ndarray": x}, timeout=15)
                    with lock:
                        outcomes.append("ok")
                except (urllib.error.HTTPError, urllib.error.URLError,
                        ConnectionError, OSError):
                    with lock:
                        outcomes.append("refused")

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        srv.stop()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads), "a request hung"
        assert len(outcomes) == 4 * 25


class TestHotSwap:
    """Tentpole acceptance: deploy v2 under sustained concurrent load —
    zero failed/hung requests, and every request started after deploy()
    returns is served by v2 (and computes v2's numbers)."""

    def test_hot_swap_under_load(self, nets):
        from deeplearning4j_tpu.serving import InferenceServer

        net1, net2 = nets
        srv = InferenceServer(net1, port=0, max_batch_size=8,
                              batch_buckets=[1, 4, 8])
        port = srv.start()
        x = np.random.default_rng(3).standard_normal((2, 4)).astype(
            np.float32)
        expect = {1: np.asarray(net1.output(x)),
                  2: np.asarray(net2.output(x))}
        _post(port, "/output", {"ndarray": x.tolist()})   # warm v1
        records, failures = [], []
        lock = threading.Lock()
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    out = _post(port, "/output",
                                {"ndarray": x.tolist()}, timeout=15)
                    with lock:
                        records.append(
                            (t0, out["version"], np.asarray(out["output"])))
                except Exception as e:   # noqa: BLE001 - recorded as failure
                    with lock:
                        failures.append(repr(e))

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        # hot-swap: warm v2's buckets, then flip — under live traffic
        srv.deploy("default", 2, net2, feat_shape=(4,))
        t_swap = time.monotonic()
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(20)
        srv.stop()
        assert not any(t.is_alive() for t in threads), "a request hung"
        assert failures == [], f"requests failed during swap: {failures[:3]}"
        assert len(records) > 20
        versions = {v for _, v, _ in records}
        assert versions == {1, 2}, f"expected traffic on both: {versions}"
        for t0, ver, y in records:
            # every response matches the version it claims
            np.testing.assert_allclose(y, expect[ver], rtol=1e-4,
                                       atol=1e-5)
            # zero post-swap requests served by v1
            if t0 > t_swap:
                assert ver == 2, "request started after swap served by v1"

    def test_multiple_named_models(self, nets):
        from deeplearning4j_tpu.serving import InferenceServer

        net1, net2 = nets
        srv = InferenceServer(port=0, max_batch_size=8,
                              batch_buckets=[1, 4, 8])
        srv.deploy("alpha", 1, net1, warm=False)
        srv.deploy("beta", 7, net2, warm=False)
        port = srv.start()
        try:
            x = np.ones((1, 4), np.float32)
            a = _post(port, "/output", {"ndarray": x.tolist(),
                                        "model": "alpha"})
            b = _post(port, "/output", {"ndarray": x.tolist(),
                                        "model": "beta"})
            assert a["version"] == 1 and b["version"] == 7
            np.testing.assert_allclose(
                a["output"], np.asarray(net1.output(x)), rtol=1e-4)
            np.testing.assert_allclose(
                b["output"], np.asarray(net2.output(x)), rtol=1e-4)
            models = _get(port, "/models")["models"]
            assert set(models) == {"alpha", "beta"}
            assert models["beta"]["version"] == 7
        finally:
            srv.stop()


class TestObservability:
    def test_metrics_reconcile_with_client_counts(self, nets):
        from deeplearning4j_tpu.serving import InferenceServer

        net1, _ = nets
        srv = InferenceServer(net1, port=0, max_batch_size=8,
                              batch_buckets=[1, 4, 8])
        port = srv.start()
        try:
            x = np.ones((2, 4), np.float32).tolist()
            n_ok = 12
            for _ in range(n_ok):
                _post(port, "/output", {"ndarray": x})
            with pytest.raises(urllib.error.HTTPError):
                _post(port, "/output", {"ndarray": x, "model": "ghost"})
            m = _get(port, "/metrics")
            assert m["requests"]["completed"] == n_ok
            assert m["per_model"]["default"]["completed"] == n_ok
            assert m["batch"]["dispatches"] >= 1
            assert m["batch"]["rows"] == n_ok * 2
            occ = m["batch"]["occupancy_histogram"]
            assert sum(occ.values()) == m["batch"]["dispatches"]
            lat = m["latency"]
            assert lat["p50_ms"] is not None
            assert lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
            assert m["queue"]["depth"] == 0
        finally:
            srv.stop()

    def test_healthz_degrades_when_queue_saturates(self):
        from deeplearning4j_tpu.serving import InferenceServer

        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        srv = InferenceServer(registry=FakeRegistry(entry),
                              queue_capacity=4, max_batch_size=64)
        try:
            assert srv._healthz()["status"] == "ok"
            blocker = srv.scheduler.submit("m", np.ones((1, 2)))
            assert entry.started.wait(5)
            futs = [srv.scheduler.submit("m", np.ones((1, 2)))
                    for _ in range(4)]
            health = srv._healthz()
            assert health["status"] == "degraded"
            assert health["queue_depth"] == 4
            gate.set()
            for f in [blocker] + futs:
                f.result(5)
            assert srv._healthz()["status"] == "ok"
        finally:
            gate.set()
            srv.scheduler.shutdown()

    def test_shed_maps_to_503_and_deadline_to_504(self):
        from deeplearning4j_tpu.serving import InferenceServer

        gate = threading.Event()
        entry = FakeEntry(gate=gate)
        srv = InferenceServer(registry=FakeRegistry(entry),
                              queue_capacity=2, max_batch_size=64,
                              admission=AdmissionPolicy.SHED)
        port = srv.start()
        try:
            results = {}

            def req(key, payload):
                try:
                    results[key] = ("ok",
                                    _post(port, "/output", payload))
                except urllib.error.HTTPError as e:
                    results[key] = ("err", e.code)

            def bg(key, payload):
                t = threading.Thread(target=req, args=(key, payload))
                t.start()
                return t

            t1 = bg("blocker", {"ndarray": [[1.0, 2.0]]})
            assert entry.started.wait(5)   # slot busy; queue accumulates
            t2 = bg("queued", {"ndarray": [[1.0, 2.0]]})
            t3 = bg("expired", {"ndarray": [[1.0, 2.0]],
                                "deadline_ms": 40})
            deadline = time.monotonic() + 5
            while srv.scheduler.queue_depth() < 2:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            req("shed", {"ndarray": [[1.0, 2.0]]})        # queue full
            assert results["shed"] == ("err", 503)
            time.sleep(0.15)               # "expired" passes its deadline
            gate.set()
            for t in (t1, t2, t3):
                t.join(10)
            assert results["blocker"][0] == "ok"
            assert results["queued"][0] == "ok"
            assert results["expired"] == ("err", 504)
            m = _get(port, "/metrics")
            assert m["requests"]["shed"] == 1
            assert m["requests"]["expired"] == 1
        finally:
            gate.set()
            srv.stop()


class TestCollectModeBackCompat:
    """The legacy fixed collect-then-run loop stays available and
    serves through the same routes."""

    def test_collect_mode_serves(self, nets):
        from deeplearning4j_tpu.serving import InferenceServer

        net1, _ = nets
        srv = InferenceServer(net1, port=0, scheduler="collect",
                              max_batch_size=8, batch_buckets=[1, 4, 8],
                              collect_wait_ms=1.0)
        port = srv.start()
        try:
            x = np.ones((2, 4), np.float32)
            out = _post(port, "/output", {"ndarray": x.tolist()})
            np.testing.assert_allclose(
                out["output"], np.asarray(net1.output(x)), rtol=1e-4)
            assert out["version"] == 1
            assert _get(port, "/metrics")["requests"]["completed"] == 1
        finally:
            srv.stop()
