"""Set-up is timed from inside the program (PR 38): the package's
`import.*` spans, `net.init`, `wrapper.init`, `step.build`, JAX's own
timed regions as `xla.trace` / `xla.lower` / `xla.compile` spans with the
counters beside them, and the watchdog's probe by leg. CPU: which spans
exist, under which parent, and how many; never a time."""

import ast
import glob
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.observe import (
    MetricsRegistry, get_span_store, set_registry, watchdog,
)
from deeplearning4j_tpu.optim.updaters import Adam

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XLA = ("xla.trace", "xla.lower", "xla.compile")
# a one-device program's probe: the text leg is a sharded program's alone
PROBE_LEGS = {"compile.probe.lower", "compile.probe.compile",
              "compile.probe.cost"}


def _net(n_in=8):
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=n_in, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


def _data(n=64, n_in=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, n_in), dtype=np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _under(events, root):
    """The events of `root`'s subtree, `root` left out."""
    kids = {}
    for e in events:
        kids.setdefault(e["parent_id"], []).append(e)
    out, todo = [], [root]
    while todo:
        for k in kids.get(todo.pop()["span_id"], []):
            out.append(k)
            todo.append(k)
    return out


def _names(events):
    return [e["name"] for e in events]


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


@pytest.fixture(scope="module")
def two_fits():
    """(spans of a tiny net's first `fit()`, spans of its second)."""
    store = get_span_store()
    net = _net().init()
    x, y = _data()
    n0 = store.count
    net.fit(x, y, epochs=1, batch_size=16)
    n1 = store.count
    net.fit(x, y, epochs=1, batch_size=16)
    return store.events(n0)[:n1 - n0], store.events(n1)


def _dispatches(events):
    return sorted((e for e in events if e["name"] == "fit.dispatch"),
                  key=lambda e: e["start_ns"])


def _of_the_step(events, parent):
    """`parent`'s children that are the step's own: its build, its three
    regions of JAX and its probe (a first dispatch also compiles small
    eager programs, the step counter's cast among them)."""
    return sorted((e for e in events if e["parent_id"] == parent["span_id"]
                   and (e["name"] in ("step.build", "compile.probe")
                        or e["name"] in XLA
                        and "step_fn" in e["attrs"]["fun_name"])),
                  key=lambda e: e["start_ns"])


# ------------------------------------------------------- the first fit()
@pytest.mark.parametrize("name", ("step.build", *XLA, "compile.probe"))
def test_first_dispatch_decomposes(two_fits, name):
    first, _ = two_fits
    d0 = _dispatches(first)[0]
    direct = _of_the_step(first, d0)
    assert _names(direct).count(name) == 1
    span = next(e for e in direct if e["name"] == name)
    assert d0["start_ns"] <= span["start_ns"] <= span["end_ns"] \
        <= d0["end_ns"]


def test_first_dispatch_spans_come_in_order_and_name_the_step(two_fits):
    first, _ = two_fits
    d0 = _dispatches(first)[0]
    direct = _of_the_step(first, d0)
    assert _names(direct) == ["step.build", *XLA, "compile.probe"]
    for a, b in zip(direct, direct[1:]):
        assert a["end_ns"] <= b["start_ns"]     # on one clock, in turn
    build, trace, lower, comp, _ = direct
    assert build["attrs"] == {"step": "MultiLayerNetwork._step",
                              "fused": False}
    assert trace["attrs"] == {"fun_name": "step_fn"}
    assert lower["attrs"]["fun_name"] == comp["attrs"]["fun_name"] \
        == "jit(step_fn)"
    assert comp["attrs"]["fetched"] is False    # no persistent cache here


def test_the_probe_has_a_child_a_leg_and_its_events_stay_under_it(two_fits):
    first, _ = two_fits
    probe = next(e for e in first if e["name"] == "compile.probe")
    assert probe["attrs"] == {"owner": "MultiLayerNetwork"}
    legs = [e for e in first if e["parent_id"] == probe["span_id"]]
    assert set(_names(legs)) == PROBE_LEGS and len(legs) == 3
    # what the probe's own lowering and compile fire lies under a leg,
    # never beside the dispatch's own spans
    for e in _under(first, probe):
        if e["name"] in XLA:
            parent = next(p for p in legs if p["span_id"] == e["parent_id"])
            assert parent["name"] in ("compile.probe.lower",
                                      "compile.probe.compile")


def test_later_dispatches_and_the_second_fit_leave_none(two_fits):
    first, second = two_fits
    new = {"step.build", *XLA, "compile.probe", *PROBE_LEGS,
           "compile.probe.text", "net.init"}
    for d in _dispatches(first)[1:]:
        assert not _under(first, d)
    assert not new & set(_names(second))
    assert len(_dispatches(second)) == len(_dispatches(first)) == 4
    # a steady step is what it was: etl (> data.put), dispatch, listeners
    assert set(_names(second)) == {
        "fit", "fit.epoch", "fit.etl", "data.put", "fit.dispatch",
        "fit.listeners", "fit.epoch_sync", "fit.counters"}


@jax.jit
def inner(v):
    return jnp.tanh(v) * 2.0


@jax.jit
def outer_setup_spans(v):
    return inner(v) + inner(v * 3.0)


@jax.jit
def on_another_thread(v):
    return v * 5.0 - 1.0


def test_inner_traces_are_held_by_the_outer_span():
    watchdog.listen_for_compiles()
    store = get_span_store()
    n0 = store.count
    outer_setup_spans(jnp.ones((5, 7))).block_until_ready()
    mine = [e for e in store.events(n0) if e["name"] in XLA
            and "outer_setup_spans" in e["attrs"]["fun_name"]]
    assert _names(mine) == list(XLA)
    assert not [e for e in store.events(n0) if e["name"] == "xla.trace"
                and e["attrs"]["fun_name"] == "inner"]


# ------------------------------------------------ a new shape in mid-epoch
def test_a_new_shape_in_mid_epoch_compiles_once_under_its_dispatch(
        fresh_registry):
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator

    x, y = _data(16 * 3 + 12)
    sizes = [16, 16, 12, 16]            # the third batch is a new shape
    edges = np.cumsum([0] + sizes)

    class Batches(DataSetIterator):
        def __init__(self):
            self.i = 0

        def reset(self):
            self.i = 0

        def __next__(self):
            if self.i == len(sizes):
                raise StopIteration
            lo, hi = edges[self.i], edges[self.i + 1]
            self.i += 1
            return DataSet(x[lo:hi], y[lo:hi])

        @property
        def batch_size(self):
            return 16

    net = _net().init()
    net.fit(x[:32], y[:32], epochs=1, batch_size=16)    # built, compiled
    before = fresh_registry.counter("xla_compiles_total",
                                    fetched="false").value
    store = get_span_store()
    n0 = store.count
    net.fit(Batches(), epochs=1)
    events = store.events(n0)
    compiles = [e for e in events if e["name"] == "xla.compile"
                and e["attrs"]["fun_name"] == "jit(step_fn)"]
    assert len(compiles) == 1 and compiles[0]["attrs"]["fetched"] is False
    parent = next(e for e in events
                  if e["span_id"] == compiles[0]["parent_id"])
    assert parent["name"] == "fit.dispatch"
    assert parent["attrs"]["batch"] == 2
    assert "step.build" not in _names(events)   # the same jitted step
    after = fresh_registry.counter("xla_compiles_total",
                                   fetched="false").value
    assert after - before >= 1
    assert fresh_registry.histogram("xla_compile_ms").count >= 1


def test_a_fetch_from_the_persistent_cache_is_marked(fresh_registry):
    watchdog.listen_for_compiles()
    store = get_span_store()
    n0 = store.count
    # what JAX does on a hit: the event inside the compile region
    watchdog._on_xla_start(
        "/jax/core/compile/backend_compile_duration", 0.0, fun_name="f")
    watchdog._on_xla_event("/jax/compilation_cache/cache_hits")
    watchdog._on_xla_duration(
        "/jax/core/compile/backend_compile_duration", 0.25, fun_name="f")
    (hit,) = [e for e in store.events(n0) if e["name"] == "xla.compile"]
    assert hit["attrs"] == {"fun_name": "f", "fetched": True}
    assert fresh_registry.counter("xla_compiles_total",
                                  fetched="true").value == 1
    # the flag does not leak into the next compile
    watchdog._on_xla_start(
        "/jax/core/compile/backend_compile_duration", 0.0, fun_name="g")
    watchdog._on_xla_duration(
        "/jax/core/compile/backend_compile_duration", 0.25, fun_name="g")
    assert store.events(n0)[-1]["attrs"] == {"fun_name": "g",
                                             "fetched": False}


def test_a_region_opened_before_the_listener_ends_duration_before_now():
    store = get_span_store()
    n0 = store.count
    watchdog._xla_open().clear()
    watchdog._on_xla_duration(
        "/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="late")
    (e,) = [e for e in store.events(n0) if e["name"] == "xla.trace"]
    assert e["end_ns"] - e["start_ns"] == 500_000_000


# --------------------------------------------------- init, imports, threads
def test_import_and_init_spans_exist_after_import_and_init():
    store = get_span_store()
    n0 = store.count
    net = _net().init()
    (init,) = [e for e in store.events(n0) if e["name"] == "net.init"]
    assert init["attrs"] == {"model": "MultiLayerNetwork", "layers": 2,
                             "params": 8 * 16 + 16 + 16 * 3 + 3}
    assert isinstance(init["attrs"]["params"], int)
    assert net.params_tree is not None
    # eager `init()` compiles its small programs under the span
    inside = _under(store.events(n0), init)
    assert set(_names(inside)) <= set(XLA)


def test_import_spans_in_a_fresh_process_nest_and_precede_any_fit():
    code = (
        "import json, deeplearning4j_tpu, deeplearning4j_tpu.models\n"
        "from deeplearning4j_tpu.observe import get_span_store\n"
        "print(json.dumps(get_span_store().events()))\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    events = json.loads(done.stdout.strip().splitlines()[-1])
    by_name = {e["name"]: e for e in events}
    assert {"import.observe", "import.deeplearning4j_tpu", "import.nn",
            "import.models", "import.optim"} <= set(by_name)
    assert all(e["name"].startswith("import.") for e in events)
    assert by_name["import.deeplearning4j_tpu"]["parent_id"] is None
    assert by_name["import.nn"]["parent_id"] == \
        by_name["import.deeplearning4j_tpu"]["span_id"]
    assert by_name["import.optim"]["parent_id"] is not None


def test_every_subpackage_times_its_imports():
    inits = sorted(glob.glob(os.path.join(
        ROOT, "deeplearning4j_tpu", "*", "__init__.py")))
    assert len(inits) >= 19
    for path in [os.path.join(ROOT, "deeplearning4j_tpu", "__init__.py"),
                 *inits]:
        sub = os.path.basename(os.path.dirname(path))
        with open(path, encoding="utf-8") as fh:
            assert f'with _span("import.{sub}"):' in fh.read(), path


def test_flight_off_records_nothing_and_registers_no_listener():
    code = (
        "import json, numpy as np, jax.monitoring as mon\n"
        "import tests.test_setup_spans as t\n"
        "from deeplearning4j_tpu.observe import get_span_store, watchdog\n"
        "net = t._net().init()\n"
        "net.fit(*t._data(32), epochs=1, batch_size=16)\n"
        "from jax._src.monitoring import get_event_duration_listeners\n"
        "ours = [f for f in get_event_duration_listeners()\n"
        "        if getattr(f, '__module__', '').startswith(\n"
        "            'deeplearning4j_tpu')]\n"
        "print(json.dumps([get_span_store().count, watchdog._xla_listening,\n"
        "                  len(ours)]))\n")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", DL4J_TPU_FLIGHT="0"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.strip().splitlines()[-1]) == [0, False, 0]


def test_a_compile_on_another_thread_has_no_parent():
    watchdog.listen_for_compiles()
    store = get_span_store()
    from deeplearning4j_tpu.observe import span

    ones = jnp.ones((3, 11))

    def work():
        on_another_thread(ones).block_until_ready()

    n0 = store.count
    with span("main.thread.work"):
        t = threading.Thread(target=work, name="compiler-thread")
        t.start()
        t.join()
    theirs = [e for e in store.events(n0) if e["thread"] == "compiler-thread"]
    assert _names(theirs) == list(XLA)
    assert all(e["parent_id"] is None for e in theirs)


# ----------------------------------------------------------- the wrapper
def test_the_wrapper_times_its_mesh_and_builds_its_step_once(devices8):
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

    store = get_span_store()
    net = _net().init()
    n0 = store.count
    pw = ParallelWrapper(net, mesh=make_mesh({"data": 4},
                                             devices=devices8[:4]))
    (made,) = [e for e in store.events(n0) if e["name"] == "wrapper.init"]
    assert made["attrs"] == {"wrapper": "ParallelWrapper"}
    n1 = store.count
    pw.fit(*_data(), epochs=1, batch_size=16)
    builds = [e for e in store.events(n1) if e["name"] == "step.build"]
    assert [b["attrs"]["step"] for b in builds] == ["ParallelWrapper._step"]
    fit = next(e for e in store.events(n1) if e["name"] == "fit")
    assert builds[0] in _under(store.events(n1), fit)


# ------------------------------------------------- what went, what stays
def test_performance_listener_mfu_is_over_the_wall_step_time():
    from deeplearning4j_tpu.optim.listeners import PerformanceListener

    msgs = []
    pl = PerformanceListener(frequency=2, report=msgs.append,
                             flops_per_step=1e6, peak_flops=1e12)
    net = _net().init()
    net.set_listeners(pl)
    net.fit(*_data(96), epochs=3, batch_size=16)
    assert pl.last_mfu == pytest.approx(
        1e6 / (pl.last_step_ms / 1e3) / 1e12)
    assert any("MFU" in m for m in msgs)
    assert not any("device" in m for m in msgs)
    assert not hasattr(net, "_attribution")
    assert not hasattr(pl, "last_device_step_ms")


def test_the_inferred_device_segment_is_gone(fresh_registry):
    net = _net().init()
    net.fit(*_data(32), epochs=1, batch_size=16)
    series = fresh_registry.snapshot()["series"]
    assert "train_step_attribution_ms" not in series
    assert "train_device_step_ms" not in series
    assert {"train_etl_ms", "train_dispatch_ms"} <= set(series)
    assert not os.path.exists(os.path.join(
        ROOT, "deeplearning4j_tpu", "observe", "attribution.py"))


def test_observe_imports_no_jax_at_import():
    for path in glob.glob(os.path.join(
            ROOT, "deeplearning4j_tpu", "observe", "*.py")):
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in tree.body:          # module level only: lazy is fine
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            assert not [n for n in names
                        if n == "jax" or n.startswith("jax.")], path
