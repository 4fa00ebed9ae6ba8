"""The program's one span record (`observe/trace.py`): the store, the span
tree a `fit()` leaves in it, the histograms that share its clock reads, and
the layer names that `jax.named_scope` puts on the step's ops without
changing the compiled program."""

import contextlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.observe import get_registry, set_registry, span
from deeplearning4j_tpu.observe.registry import MetricsRegistry
from deeplearning4j_tpu.observe.trace import (
    SpanStore, get_span_store, read_spans, write_spans,
)


@pytest.fixture
def recording():
    """Span recording on (the default state: flight recorder enabled)."""
    observe.get_flight()
    store = get_span_store()
    return store, store.count


@pytest.fixture
def fresh_registry():
    prev = set_registry(MetricsRegistry())
    try:
        yield get_registry()
    finally:
        set_registry(prev)


def _mln(hidden=8):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(0)
         .list(DenseLayer(n_out=hidden, activation="relu", name="hid"),
               DenseLayer(n_out=hidden, activation="tanh", name="mid"),
               OutputLayer(n_out=3, activation="softmax", loss="mcxent",
                           name="head"))
         .set_input_type(InputType.feed_forward(16))
         .build())).init()


def _graph():
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    conf = (NeuralNetConfiguration.builder().seed(0)
            .graph_builder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=16), "in")
            .add_layer("d2", DenseLayer(n_out=16), "d1")
            .add_vertex("skip", ElementWiseVertex(op="add"), "d1", "d2")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "skip")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(16))
            .build())
    return ComputationGraph(conf).init()


def _data(n=64):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


# ------------------------------------------------------------------ store
class TestStore:
    def test_nesting_parent_ids_and_one_clock(self, recording):
        store, n0 = recording
        with span("outer", phase="warm"):
            with span("inner", idx=3):
                pass
            with span("inner", idx=4):
                pass
        a, b, outer = store.records(n0)
        assert [r[2] for r in (a, b, outer)] == ["inner", "inner", "outer"]
        assert a[1] == b[1] == outer[0] and outer[1] is None
        # start and end on one monotonic clock: children inside the parent
        assert outer[3] <= a[3] <= a[4] <= b[3] <= b[4] <= outer[4]
        assert a[6] == {"idx": 3} and outer[6] == {"phase": "warm"}

    def test_ring_is_bounded_and_drops_the_oldest(self):
        store = SpanStore(4)
        for i in range(10):
            store.add((i, None, "s", i, i + 1, "t", {}))
        assert store.count == 10 and len(store._slots) == 4
        assert [r[0] for r in store.records()] == [6, 7, 8, 9]
        assert [r[0] for r in store.records(8)] == [8, 9]

    def test_each_thread_has_its_own_parents(self, recording):
        store, n0 = recording

        def work(i):
            with span(f"root{i}"):
                for j in range(20):
                    with span(f"leaf{i}", j=j):
                        pass

        threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = store.records(n0)
        assert len(recs) == 84 and len({r[0] for r in recs}) == 84
        roots = {r[2]: r for r in recs if r[2].startswith("root")}
        for r in recs:
            if r[2].startswith("leaf"):
                root = roots["root" + r[2][4:]]
                assert r[1] == root[0] and r[5] == root[5] == "w" + r[2][4:]

    def test_an_array_attribute_is_recorded_as_its_type_name(self, recording):
        store, n0 = recording
        held = jnp.arange(3)
        with span("s", arr=held, host=np.arange(3), ok=1, name="n"):
            pass
        # (`jnp.arange`'s own compile leaves `xla.*` spans beside it)
        (rec,) = [r for r in store.records(n0) if r[2] == "s"]
        # no value read (a sync) and no buffer kept alive by the store
        assert rec[6] == {"arr": type(held).__name__, "host": "ndarray",
                          "ok": 1, "name": "n"}

    def test_off_means_nothing_recorded_but_the_clock_is_read(
            self, monkeypatch):
        from deeplearning4j_tpu.observe import trace

        monkeypatch.setattr(trace, "_flight_sink", None)
        store = get_span_store()
        n0 = store.count
        s = span("quiet")
        with s as attrs:
            assert attrs is None
        assert store.count == n0 and s.end_ns >= s.start_ns > 0
        assert s.dur_ms == (s.end_ns - s.start_ns) / 1e6

    def test_written_files_round_trip(self, recording, tmp_path):
        store, n0 = recording
        log = observe.install_span_log(str(tmp_path / "log.jsonl"))
        try:
            with span("outer"):
                with span("inner", idx=1):
                    pass
            assert log.events == 0          # nothing written per span
        finally:
            observe.uninstall_span_log()
        assert log.events == 2
        wrote = write_spans(str(tmp_path / "w.jsonl"), n0, beacons_ns=[[1, 2]])
        assert wrote == 2
        for path in ("log.jsonl", "w.jsonl"):
            evs = read_spans(str(tmp_path / path))
            assert evs == store.events(n0)
            inner, outer = evs
            assert inner["parent_id"] == outer["span_id"]
            assert inner["dur_ms"] == pytest.approx(
                (inner["end_ns"] - inner["start_ns"]) / 1e6, abs=1e-4)
        with open(tmp_path / "w.jsonl") as fh:
            head = __import__("json").loads(fh.readline())["span_clock"]
        assert head["clock"] == "perf_counter_ns"
        assert head["beacons_ns"] == [[1, 2]]
        assert head["anchor_clock_ns"] == store.anchor[1]

    def test_a_log_outlives_the_ring(self, tmp_path):
        store = get_span_store()
        log = observe.install_span_log(str(tmp_path / "long.jsonl"))
        try:
            for i in range(store.capacity + 50):
                with span("s", i=i):
                    pass
        finally:
            observe.uninstall_span_log()
        evs = read_spans(str(tmp_path / "long.jsonl"))
        assert [e["attrs"]["i"] for e in evs] == list(
            range(store.capacity + 50))


# --------------------------------------------------------------- fit tree
def _tree(events):
    """{span_id: event} and each parent's children, in start order."""
    by_id = {e["span_id"]: e for e in events}
    kids = {}
    for e in sorted(events, key=lambda e: e["start_ns"]):
        kids.setdefault(e["parent_id"], []).append(e)
    return by_id, kids


class TestFitSpans:
    @pytest.mark.parametrize("make", [_mln, _graph], ids=["mln", "graph"])
    def test_two_epochs_leave_exactly_the_tree(self, recording, make):
        store, _ = recording
        net = make()
        x, y = _data(64)
        net.fit(x, y, epochs=1, batch_size=16)          # compile
        syncs = net._loss_tracker.host_syncs
        n0 = store.count
        net.fit(x, y, epochs=2, batch_size=16)
        events = store.events(n0)
        by_id, kids = _tree(events)
        (fit,) = kids[None]
        assert fit["name"] == "fit" and fit["attrs"]["epochs"] == 2
        epochs = kids[fit["span_id"]]
        assert [e["name"] for e in epochs] == ["fit.epoch"] * 2
        for i, epoch in enumerate(epochs):
            names = [e["name"] for e in kids[epoch["span_id"]]]
            step = ["fit.etl", "fit.dispatch", "fit.listeners"]
            assert names == step * 4 + ["fit.etl", "fit.epoch_sync",
                                        "fit.counters"]
            last_etl = kids[epoch["span_id"]][-3]
            assert last_etl["attrs"] == {"exhausted": True}
            dispatches = [e["attrs"] for e in kids[epoch["span_id"]]
                          if e["name"] == "fit.dispatch"]
            assert [d["batch"] for d in dispatches] == [0, 1, 2, 3]
            assert all(d["steps"] == 1 and not d["fused"]
                       for d in dispatches)
        # every batch went to the device under a data.put inside an etl wait
        puts = [e for e in events if e["name"] == "data.put"]
        assert len(puts) == 8
        assert all(by_id[p["parent_id"]]["name"] == "fit.etl" for p in puts)
        assert all(p["attrs"]["bytes"] == 16 * 16 * 4 + 16 * 3 * 4
                   for p in puts)
        assert {e["name"] for e in events} == {
            "fit", "fit.epoch", "fit.etl", "data.put", "fit.dispatch",
            "fit.listeners", "fit.epoch_sync", "fit.counters"}
        # one host sync an epoch, as before the spans
        assert net._loss_tracker.host_syncs - syncs == 2
        # the children account for the fit span: what is left is the loop's
        # own bookkeeping (CPU here, so a count of spans, not a time)
        assert sum(e["name"] == "fit.epoch_sync" for e in events) == 2

    @pytest.mark.parametrize("k", [1, 2], ids=["per_step", "fused"])
    def test_histograms_share_the_spans_clock_reads(
            self, recording, fresh_registry, k):
        store, _ = recording
        net = _mln()
        x, y = _data(64)
        n0 = store.count
        net.fit(x, y, epochs=2, batch_size=16, steps_per_dispatch=k)
        events = store.events(n0)
        etl = sum(e["end_ns"] - e["start_ns"] for e in events
                  if e["name"] == "fit.etl" and not e["attrs"])
        disp = [e for e in events if e["name"] == "fit.dispatch"]
        assert sum(d["attrs"]["steps"] for d in disp) == 8
        assert all(d["attrs"]["fused"] == (k == 2) for d in disp)
        h_etl = fresh_registry.histogram("train_etl_ms")
        h_disp = fresh_registry.histogram("train_dispatch_ms")
        assert h_etl.count == h_disp.count == 8
        assert h_etl.sum == pytest.approx(etl / 1e6, rel=1e-9)
        assert h_disp.sum == pytest.approx(
            sum(d["end_ns"] - d["start_ns"] for d in disp) / 1e6, rel=1e-9)

    def test_spans_reach_a_span_log_when_fit_ends(self, tmp_path):
        net = _mln()
        x, y = _data(32)
        path = str(tmp_path / "spans.jsonl")
        log = observe.install_span_log(path)
        try:
            net.fit(x, y, epochs=1, batch_size=16)
            # flushed by the fit loop, before the log is closed
            assert log.events > 0
            names = [e["name"] for e in read_spans(path)]
        finally:
            observe.uninstall_span_log()
        assert names[-1] == "fit" and "fit.epoch_sync" in names


# ----------------------------------------------------------- layer scopes
def _lower_step(net, scoped=True):
    """The train step of `net`, lowered for a batch of 16; with `scoped`
    false, `jax.named_scope` is a null context while it is traced."""
    x, y = _data(16)
    step = jnp.asarray(0, jnp.int32)
    key = jax.random.PRNGKey(0)
    if hasattr(net, "layers"):
        args = (net.params_tree, net.updater_state, net.state_tree, step,
                jnp.asarray(x), jnp.asarray(y), None, None, key, None)
    else:
        args = (net.params_tree, net.updater_state, net.state_tree, step,
                {"in": jnp.asarray(x)}, {"out": jnp.asarray(y)}, None,
                None, key)
    patch = contextlib.nullcontext() if scoped else \
        pytest.MonkeyPatch.context()
    with patch as mp:
        if mp is not None:
            mp.setattr(jax, "named_scope",
                       lambda name: contextlib.nullcontext())
        return jax.jit(net.make_step_fn()).lower(*args)


@pytest.mark.parametrize("make,names,plain_add", [
    (_mln, ["hid", "mid", "head"], []),
    (_graph, ["d1", "d2", "skip", "out"], ["skip"]),
], ids=["mln", "graph"])
def test_layer_names_are_on_the_steps_ops_and_change_no_program(
        make, names, plain_add):
    net = make()
    scoped, plain = _lower_step(net), _lower_step(net, scoped=False)
    text = scoped.as_text(debug_info=True)
    for name in names + ["loss", "updater"]:
        assert re.search(rf"jvp\({name}\)|/{name}/", text), name
    for name in names:          # forward and backward split by name
        if name not in plain_add:   # an add has no backward op of its own
            assert f"transpose(jvp({name}))" in text, name
    assert "jvp(hid)" not in plain.as_text(debug_info=True)
    # debug locations only: the same program comes out of the compiler
    a, b = scoped.compile(), plain.compile()

    def instructions(compiled):
        return len(re.findall(r"^\s+(?:ROOT )?%?[\w.-]+ = ",
                              compiled.as_text(), flags=re.M))

    assert instructions(a) == instructions(b) > 0
    ma, mb = a.memory_analysis(), b.memory_analysis()
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
        assert getattr(ma, field) == getattr(mb, field), field


def test_cached_programs_are_keyed_by_their_names(monkeypatch):
    """Without debug locations in the persistent cache's key, a step with
    layer names fetched the executable of the same step compiled without
    them (chip run, PR 25): entry points turn them on."""
    from deeplearning4j_tpu.utils import compile_cache

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = (getattr(jax.config, flag), jax.config.jax_compilation_cache_dir)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent-elsewhere")
    try:
        jax.config.update(flag, False)
        compile_cache.enable_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before[0])
        jax.config.update("jax_compilation_cache_dir", before[1])


# ------------------------------------------------- spans beside a device trace
def test_profiler_listener_writes_the_spans_beside_the_trace(tmp_path):
    import glob
    import json

    from deeplearning4j_tpu.utils.profiling import BEACON, ProfilerListener

    net = _mln()
    x, y = _data(128)
    net.add_listener(ProfilerListener(str(tmp_path), start_iteration=2,
                                      num_iterations=3))
    net.fit(x, y, epochs=1, batch_size=16)
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans_path = pb[:-len(".xplane.pb")] + ".spans.jsonl"
    with open(spans_path) as fh:
        head = json.loads(fh.readline())["span_clock"]
    # three beacons when the session opened, two when it closed, each a
    # bracket [t0, t1] on the span clock
    assert head["beacon"] == BEACON and len(head["beacons_ns"]) == 5
    assert all(t0 < t1 for t0, t1 in head["beacons_ns"])
    spans = read_spans(spans_path)
    names = [s["name"] for s in spans]
    # rewritten when fit() ended: the steps after the capture are there,
    # and the still open `fit` root closes the tree
    assert names.count("fit.dispatch") == 8 - 2
    (capture,) = [s for s in spans if s["name"] == "jax.profiler.trace"]
    assert capture["attrs"]["start_iteration"] == 2
    assert capture["attrs"]["end_iteration"] == 5
    (root,) = [s for s in spans if s["name"] == "fit"]
    assert root["attrs"]["open"] is True and root["parent_id"] is None
    by_id = {s["span_id"]: s for s in spans}
    assert all(s["parent_id"] in by_id for s in spans if s is not root)
