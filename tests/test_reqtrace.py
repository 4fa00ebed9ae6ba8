"""End-to-end request & step tracing (observe/reqtrace.py).

What these pin:
  * head-based sampling is deterministic and the sampled-OFF path is
    zero-allocation: an untraced request storm records ZERO spans
  * the fan-in contract: N concurrent decode sessions under continuous
    batching reconstruct to trees of depth ≥3 — request root →
    admission wait → SHARED dispatch span (listing every co-batched
    trace id) → per-step session spans carrying slot id + the
    kernel-policy verdict
  * anomalies always trace: shed / queue-expired requests raise with a
    forced trace id regardless of the sampling rate
  * histogram exemplars: TTFT/ITL/latency reservoirs expose trace ids
    in the JSON snapshot AND the OpenMetrics exposition, and every
    exemplar id resolves in the trace store
  * FlightRecorder: dumps embed the last-K sampled traces and the dump
    dir keeps only the newest DL4J_TPU_FLIGHT_KEEP artifacts
  * tools/trace_view.py renders every JSON shape that carries a tree
"""

import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.observe import reqtrace
from deeplearning4j_tpu.observe.registry import MetricsRegistry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))

V, T = 13, 6


@pytest.fixture()
def store():
    """Fresh process-wide TraceStore, restored afterwards."""
    prev = reqtrace.set_trace_store(reqtrace.TraceStore())
    try:
        yield reqtrace.get_trace_store()
    finally:
        reqtrace.set_trace_store(prev)


@pytest.fixture()
def sampled(monkeypatch, store):
    monkeypatch.setenv(reqtrace.ENV_SAMPLE, "1")
    return store


@pytest.fixture()
def unsampled(monkeypatch, store):
    monkeypatch.delenv(reqtrace.ENV_SAMPLE, raising=False)
    return store


def _make_net(seed=0):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.attention import (
        PositionEmbeddingLayer, TransformerEncoderBlock,
    )
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer,
    )
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.optim.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(1e-3)).activation("identity")
            .list(EmbeddingSequenceLayer(n_in=V, n_out=12),
                  PositionEmbeddingLayer(max_length=64),
                  TransformerEncoderBlock(num_heads=2, causal=True,
                                          window=8, rolling_cache=True,
                                          max_cache=16),
                  RnnOutputLayer(n_out=V, activation="softmax"))
            .set_input_type(InputType.recurrent(1, T)).build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def net():
    return _make_net()


def _control_plane(net, slots=2, chunk=4):
    from deeplearning4j_tpu.serving import (
        ContinuousBatchingScheduler, ModelRegistry, ServingStats,
    )
    from deeplearning4j_tpu.serving.sessions import DecodeSessionManager

    registry = ModelRegistry()
    registry.deploy("default", 1, net, warm=False)
    stats = ServingStats()
    sched = ContinuousBatchingScheduler(registry, stats, max_batch_size=8)
    mgr = DecodeSessionManager(registry, sched, "default", slots=slots,
                               prefill_chunk=chunk,
                               metrics=stats.registry)
    return registry, sched, mgr


def _flatten(tree):
    """[(depth, name, attrs)] over a reconstructed tree document."""
    out = []

    def walk(nodes, d):
        for n in nodes:
            out.append((d, n["name"], n.get("attrs") or {}))
            walk(n.get("children") or [], d + 1)

    walk(tree["tree"], 0)
    return out


# -------------------------------------------------- sampling & the store
class TestSamplingAndStore:
    def test_off_is_none_and_every_seam_is_none_safe(self, unsampled):
        assert reqtrace.new_trace("http.x") is None
        reqtrace.finish_root(None, status=200)      # no-op, no raise
        assert reqtrace.begin_dispatch([]) is None
        reqtrace.end_dispatch(None, rows=1)
        assert unsampled.spans_recorded == 0
        assert len(unsampled) == 0

    def test_head_sampling_is_deterministic(self, monkeypatch, store):
        monkeypatch.setenv(reqtrace.ENV_SAMPLE, "0.5")
        got = [reqtrace.new_trace("r") is not None for _ in range(10)]
        assert sum(got) == 5                  # every 2nd, no randomness
        monkeypatch.setenv(reqtrace.ENV_SAMPLE, "bogus")
        assert reqtrace.new_trace("r") is None

    def test_attrs_degrade_never_serialize(self, sampled):
        class Arrayish:
            pass

        tid = "t-deg"
        reqtrace.record_span(tid, "s", loss=Arrayish(),
                             ids=list(range(100)),
                             mixed=[1, "a", Arrayish()])
        attrs = sampled.spans(tid)[0]["attrs"]
        assert attrs["loss"] == "Arrayish"
        assert len(attrs["ids"]) == 32        # capped shallow list
        assert attrs["mixed"] == [1, "a", "Arrayish"]

    def test_cap_evicts_oldest_trace(self):
        st = reqtrace.TraceStore(cap=2)
        prev = reqtrace.set_trace_store(st)
        try:
            for i in range(3):
                reqtrace.record_span(f"t{i}", "s")
            assert len(st) == 2 and "t0" not in st
            assert st.ids() == ["t1", "t2"]
        finally:
            reqtrace.set_trace_store(prev)

    def test_tree_reconstruction_and_unknown(self, sampled):
        rt = reqtrace.new_trace("root")
        child = reqtrace.record_span(rt.trace_id, "mid",
                                     parent_id=rt.span_id)
        reqtrace.record_span(rt.trace_id, "leaf", parent_id=child)
        reqtrace.finish_root(rt, status=200)
        doc = sampled.tree(rt.trace_id)
        assert doc["depth"] == 3 and doc["spans"] == 3
        assert doc["tree"][0]["name"] == "root"
        assert sampled.tree("nope") is None
        assert sampled.last_trees(5)[-1]["trace_id"] == rt.trace_id

    def test_error_trace_joins_or_mints(self, sampled):
        # joins an existing sampled trace, parented on its root
        rt = reqtrace.new_trace("http.x")
        tid = reqtrace.error_trace("request.shed", ctx=rt, model="m")
        assert tid == rt.trace_id
        ev = sampled.spans(tid)[0]
        assert ev["parent_id"] == rt.span_id and ev["attrs"]["error"]
        # no context (unsampled request): force-mints a new trace
        tid2 = reqtrace.error_trace("request.expired", where="queue")
        assert tid2 != tid and tid2 in sampled

        err = RuntimeError("x")
        err.trace_id = tid2
        assert reqtrace.error_extra(err) == {"trace_id": tid2}
        assert reqtrace.error_extra(RuntimeError("y")) == {}


# ------------------------------------------------ fan-in across sessions
class TestDecodeFanIn:
    def test_two_sessions_reconstruct_shared_dispatch_tree(self, sampled,
                                                           net):
        registry, sched, mgr = _control_plane(net)
        try:
            rt1 = reqtrace.new_trace("http.generate")
            rt2 = reqtrace.new_trace("http.generate")
            s1 = mgr.open_session([1, 2, 3, 4, 5], max_tokens=6, seed=1,
                                  trace=rt1)
            s2 = mgr.open_session([6, 7], max_tokens=6, seed=2,
                                  trace=rt2)
            s1.result(timeout=60), s2.result(timeout=60)
            reqtrace.finish_root(rt1, route="/generate", status=200)
            reqtrace.finish_root(rt2, route="/generate", status=200)

            doc = sampled.tree(rt1.trace_id)
            assert doc["depth"] >= 3
            spans = _flatten(doc)
            names = [n for _, n, _ in spans]
            assert names[0] == "http.generate"
            assert "queue.wait" in names and "session.close" in names

            dispatches = [a for _, n, a in spans if n == "dispatch"]
            assert dispatches, "no shared dispatch span in the tree"
            shared = [a for a in dispatches
                      if len(a.get("co_traces", [])) >= 2]
            assert shared, "sessions never fanned into one dispatch"
            assert {rt1.trace_id, rt2.trace_id} <= set(shared[0]
                                                       ["co_traces"])

            steps = [(d, a) for d, n, a in spans if n == "session.window"]
            assert steps, "no per-window session spans"
            for d, a in steps:
                assert d >= 2                 # child of a dispatch span
                assert a["session"] == s1.id and a["slot"] == s1.slot
                assert a["kernel"] and a["kernel"] != "n/a"
                assert a["loop"] in ("fused", "stepwise")
                assert a["win"] >= 1
            phases = {a["phase"] for _, a in steps}
            assert phases == {"prefill", "decode"}
            # per-token reconstruction: decode windows account for every
            # streamed token of the session
            emitted = sum(a["tokens"] for _, a in steps
                          if a["phase"] == "decode")
            assert emitted == len(s1.result())
            assert all(a["tokens"] == 0 for _, a in steps
                       if a["phase"] == "prefill")
            # the second trace sees the SAME shared dispatches
            doc2 = sampled.tree(rt2.trace_id)
            assert any(a.get("co_traces") == shared[0]["co_traces"]
                       for _, n, a in _flatten(doc2) if n == "dispatch")
        finally:
            sched.shutdown()
            registry.close()

    def test_sampled_off_allocates_no_spans(self, unsampled, net):
        registry, sched, mgr = _control_plane(net)
        try:
            s1 = mgr.open_session([1, 2, 3], max_tokens=4, seed=1,
                                  trace=reqtrace.new_trace("http.x"))
            s2 = mgr.open_session([4, 5], max_tokens=4, seed=2)
            s1.result(timeout=60), s2.result(timeout=60)
            assert s1.trace is None and s2.trace is None
            assert s1.describe()["trace_id"] is None
            assert unsampled.spans_recorded == 0, \
                "untraced requests allocated spans"
            assert len(unsampled) == 0
        finally:
            sched.shutdown()
            registry.close()


# --------------------------------------------------- forced error traces
class _GatedEntry:
    def __init__(self):
        self.version = 1
        self.gate = threading.Event()
        self.started = threading.Event()

    def run_batch(self, xs):
        self.started.set()
        assert self.gate.wait(10)
        return np.asarray(xs) * 2.0


class _OneEntryRegistry:
    def __init__(self, entry):
        self.entry = entry

    def acquire(self, name):
        return self.entry

    def release(self, entry):
        pass

    def names(self):
        return ["m"]

    def close(self):
        pass


class TestForcedErrorTraces:
    def _blocked_sched(self, **kw):
        from deeplearning4j_tpu.serving.scheduler import (
            ContinuousBatchingScheduler,
        )
        entry = _GatedEntry()
        sched = ContinuousBatchingScheduler(
            _OneEntryRegistry(entry), max_batch_size=64, **kw)
        blocker = sched.submit("m", np.ones((1, 2)))
        assert entry.started.wait(5)
        return entry, sched, blocker

    def test_shed_always_traces(self, unsampled):
        from deeplearning4j_tpu.serving.scheduler import (
            AdmissionPolicy, RequestShedError,
        )
        entry, sched, blocker = self._blocked_sched(
            queue_capacity=1, policy=AdmissionPolicy.SHED)
        try:
            q = sched.submit("m", np.ones((1, 2)))
            with pytest.raises(RequestShedError) as ei:
                sched.submit("m", np.ones((1, 2)))
            tid = ei.value.trace_id
            assert tid and tid in unsampled   # sampling OFF, still traced
            ev = unsampled.spans(tid)[0]
            assert ev["name"] == "request.shed" and ev["attrs"]["error"]
            assert ev["attrs"]["model"] == "m"
            entry.gate.set()
            blocker.result(5), q.result(5)
        finally:
            sched.shutdown()

    def test_queue_expiry_always_traces(self, unsampled):
        from deeplearning4j_tpu.serving.scheduler import (
            AdmissionPolicy, DeadlineExceededError,
        )
        entry, sched, blocker = self._blocked_sched(
            queue_capacity=8, policy=AdmissionPolicy.DEADLINE,
            default_deadline_ms=10_000)
        try:
            doomed = sched.submit("m", np.ones((1, 2)), deadline_ms=50)
            time.sleep(0.15)                  # expires while queued
            entry.gate.set()
            with pytest.raises(DeadlineExceededError) as ei:
                doomed.result(5)
            tid = ei.value.trace_id
            assert tid and tid in unsampled
            ev = unsampled.spans(tid)[0]
            assert ev["name"] == "request.expired"
            assert ev["attrs"]["where"] == "queue"
            blocker.result(5)
        finally:
            sched.shutdown()


# ------------------------------------------------------------- exemplars
class TestExemplars:
    def test_json_prometheus_and_store_reconcile(self, sampled):
        reg = MetricsRegistry()
        h = reg.histogram("decode_ttft_ms", model="default")
        rt = reqtrace.new_trace("http.generate")
        reqtrace.finish_root(rt, status=200)
        h.observe(12.5, exemplar=rt.trace_id)
        h.observe(3.0, exemplar=None)          # unsampled: no exemplar
        ex = h.exemplars()
        assert [e["trace_id"] for e in ex] == [rt.trace_id]
        assert h.tail_exemplar()["value"] == 12.5

        snap = reg.snapshot()
        (series,) = snap["series"]["decode_ttft_ms"]
        assert series["exemplars"][0]["trace_id"] == rt.trace_id

        prom = reg.to_prometheus()
        assert f'# {{trace_id="{rt.trace_id}"}}' in prom

        # every exposed exemplar resolves in the trace store
        for e in ex:
            assert e["trace_id"] in sampled
            assert sampled.tree(e["trace_id"])["spans"] >= 1

    def test_no_exemplars_key_when_empty(self):
        reg = MetricsRegistry()
        h = reg.histogram("plain_ms")
        h.observe(1.0)
        (series,) = reg.snapshot()["series"]["plain_ms"]
        assert "exemplars" not in series
        assert "# {" not in reg.to_prometheus()


# ------------------------------------------------- flight recorder seams
class TestFlightTraces:
    def test_dump_carries_last_traces(self, sampled, tmp_path):
        from deeplearning4j_tpu.observe.flight import FlightRecorder
        rt = reqtrace.new_trace("http.generate")
        reqtrace.finish_root(rt, status=200)
        fr = FlightRecorder(dump_dir=str(tmp_path))
        path = fr.dump("test_reason")
        doc = json.load(open(path))
        assert any(t["trace_id"] == rt.trace_id
                   for t in doc["traces"])

    def test_dump_dir_rotation_keeps_newest(self, monkeypatch, tmp_path):
        from deeplearning4j_tpu.observe.flight import (
            FlightRecorder, latest_dump,
        )
        monkeypatch.setenv("DL4J_TPU_FLIGHT_KEEP", "3")
        fr = FlightRecorder(dump_dir=str(tmp_path))
        paths = [fr.dump(f"r{i}") for i in range(5)]
        left = sorted(glob.glob(str(tmp_path / "flight_*.json")))
        assert len(left) == 3
        assert set(left) == set(paths[-3:]), "rotation dropped the wrong dumps"
        assert latest_dump(str(tmp_path)) == paths[-1]

    def test_rotation_disabled_with_nonpositive_keep(self, monkeypatch,
                                                     tmp_path):
        from deeplearning4j_tpu.observe.flight import FlightRecorder
        monkeypatch.setenv("DL4J_TPU_FLIGHT_KEEP", "0")
        fr = FlightRecorder(dump_dir=str(tmp_path))
        for i in range(4):
            fr.dump(f"r{i}")
        assert len(glob.glob(str(tmp_path / "flight_*.json"))) == 4


# ------------------------------------------------------------ trace_view
class TestTraceView:
    def _doc(self, sampled):
        rt = reqtrace.new_trace("http.generate")
        mid = reqtrace.record_span(rt.trace_id, "dispatch",
                                   parent_id=rt.span_id,
                                   co_traces=[rt.trace_id], rows=2)
        reqtrace.record_span(rt.trace_id, "session.step", parent_id=mid,
                             slot=0, kernel="banded")
        reqtrace.finish_root(rt, status=200)
        return sampled.tree(rt.trace_id)

    def test_extracts_every_json_shape(self, sampled):
        import trace_view
        doc = self._doc(sampled)
        assert trace_view.extract_trees(doc) == [doc]          # /trace/{id}
        assert trace_view.extract_trees({"traces": [doc]}) == [doc]
        assert trace_view.extract_trees({"metric": "x"}) == []

    def test_renders_waterfall(self, sampled, tmp_path, capsys):
        import trace_view
        doc = self._doc(sampled)
        p = tmp_path / "trace.json"
        p.write_text(json.dumps(doc))
        assert trace_view.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert f"trace {doc['trace_id']}" in out
        for name in ("http.generate", "dispatch", "session.step"):
            assert name in out
        # indentation encodes depth: step sits under dispatch
        step_line = [ln for ln in out.splitlines()
                     if "session.step" in ln][0]
        assert "    session.step" in step_line
