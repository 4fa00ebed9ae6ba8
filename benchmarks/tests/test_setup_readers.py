"""The readers PR 38 brought (`benchmarks/layer_metrics/*.setup.py` and
`xla_compile_spans_in_window.train.py`, over `benchmarks/setup_spans.py`):
each over a recorded list of spans (`data/setup_spans.jsonl`: a warm
traced run of `resnet50_fit` on a v5e, PR 38: set-up whole, then the
window's first 12 steps and its sync, as `record_setup_table.py --out`
wrote them, with what its `setup_metrics` line read in the header), over
a ring that has wrapped, over a program without the
spans; what is left out of the `xla.*` sums; the table; and the
`tokens_tiny` rehearsal on the CPU, which reads all ten. Run by hand:

    python -m pytest benchmarks/tests/test_setup_readers.py -q
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import harness, setup_spans, span_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "setup_spans.jsonl")
METRICS = ("import_ms.setup", "init_ms.setup", "trace_ms.setup",
           "lower_ms.setup", "compile_or_fetch_ms.setup",
           "xla_compiles.setup", "compile_probe_ms.setup",
           "first_fit_ms.setup", "warmup_sync_ms.setup",
           "xla_compile_spans_in_window.train")
NEW = ("import.", "net.init", "wrapper.init", "step.build", "xla.",
       "compile.probe")


@pytest.fixture(scope="module")
def recorded():
    header, spans = span_reduce.read_span_file(DATA)
    return header, spans


def _read(metric, monkeypatch, source):
    monkeypatch.setattr(setup_spans, "held", lambda: source)
    return harness.load_module("layer_metrics", metric + ".py").read({})


def _span(sid, name, start, end, parent=None, thread="MainThread", **attrs):
    return {"span_id": sid, "parent_id": parent, "name": name,
            "start_ns": start, "end_ns": end, "thread": thread,
            "attrs": attrs}


@pytest.mark.parametrize("metric", METRICS)
def test_reader_over_the_recorded_run(recorded, monkeypatch, metric):
    header, spans = recorded
    got = _read(metric, monkeypatch, (spans, len(spans), 4096))
    assert got == pytest.approx(header["metrics"][metric])
    if metric.endswith("_ms.setup"):
        assert got > 0
    else:
        assert got == int(got) >= 0


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_once_the_ring_has_wrapped(
        recorded, monkeypatch, metric):
    _, spans = recorded
    assert _read(metric, monkeypatch, (spans, 4097, 4096)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_nothing_for_a_program_without_the_spans(
        recorded, monkeypatch, metric):
    _, spans = recorded
    # the parent of PR 38: the fit loop's spans and nothing else
    old = [s for s in spans if not s["name"].startswith(NEW)]
    assert {"fit", "fit.dispatch", "fit.epoch_sync"} <= {
        s["name"] for s in old}
    assert _read(metric, monkeypatch, (old, len(old), 4096)) is None
    # a program with no span store at all, and one that never fitted
    assert _read(metric, monkeypatch, None) is None
    assert _read(metric, monkeypatch, ([], 0, 4096)) is None


def test_setup_ends_where_the_windows_fit_begins(recorded):
    _, spans = recorded
    setup, window = setup_spans.split((spans, len(spans), 4096))
    root = window[0]
    assert root["name"] == "fit" and root["parent_id"] is None
    assert all(s["end_ns"] <= root["start_ns"] for s in setup)
    assert len(setup) + len(window) <= len(spans)
    # the warm-up's own `fit` root is set-up's, and comes first
    first = setup_spans._first_fit(setup)
    assert first[0]["name"] == "fit" and first[0] is not root
    assert "fit.epoch_sync" in {s["name"] for s in first}


def test_the_probes_and_the_beacons_regions_are_left_out():
    ms = 10 ** 6
    spans = [
        _span(1, "import.deeplearning4j_tpu", 0, 5 * ms),
        _span(2, "fit", 10 * ms, 200 * ms),
        _span(3, "fit.dispatch", 10 * ms, 150 * ms, 2),
        _span(4, "xla.trace", 11 * ms, 31 * ms, 3, fun_name="step_fn"),
        _span(5, "xla.compile", 40 * ms, 90 * ms, 3,
              fun_name="jit(step_fn)", fetched=False),
        _span(6, "compile.probe", 100 * ms, 140 * ms, 3),
        _span(7, "compile.probe.lower", 100 * ms, 120 * ms, 6),
        _span(8, "xla.trace", 101 * ms, 119 * ms, 7, fun_name="step_fn"),
        _span(9, "compile.probe.compile", 120 * ms, 130 * ms, 6),
        _span(10, "xla.compile", 121 * ms, 129 * ms, 9,
              fun_name="jit(step_fn)", fetched=False),
        _span(11, "fit.epoch_sync", 150 * ms, 190 * ms, 2),
        # the traced run's beacon, between the warm-up and the window
        _span(12, "xla.trace", 210 * ms, 211 * ms,
              fun_name="dl4j_trace_beacon"),
        _span(13, "xla.compile", 212 * ms, 218 * ms,
              fun_name="jit(dl4j_trace_beacon)", fetched=False),
        _span(14, "xla.compile", 220 * ms, 222 * ms,
              fun_name="jit(broadcast_in_dim)", fetched=True),
        _span(20, "fit", 300 * ms, 900 * ms),
        _span(21, "fit.dispatch", 300 * ms, 400 * ms, 20),
        _span(22, "xla.compile", 310 * ms, 390 * ms, 21,
              fun_name="jit(step_fn)", fetched=False),
    ]
    source = (spans, len(spans), 4096)
    assert setup_spans.read("trace_ms.setup", source) == 20.0
    assert setup_spans.read("compile_or_fetch_ms.setup", source) == 52.0
    assert setup_spans.read("xla_compiles.setup", source) == 1
    assert setup_spans.read("compile_probe_ms.setup", source) == 40.0
    assert setup_spans.read("first_fit_ms.setup", source) == 190.0
    assert setup_spans.read("warmup_sync_ms.setup", source) == 40.0
    assert setup_spans.read("import_ms.setup", source) == 5.0
    assert setup_spans.read("lower_ms.setup", source) is None   # none there
    assert setup_spans.read("init_ms.setup", source) is None
    # a recompile inside the window is the window's, with its step
    assert setup_spans.read("xla_compile_spans_in_window.train", source) == 1
    with pytest.raises(KeyError):
        setup_spans.read("no_such.setup", source)


def test_a_union_counts_nested_and_overlapping_spans_once():
    spans = [_span(1, "import.a", 0, 100), _span(2, "import.b", 10, 40, 1),
             _span(3, "import.c", 90, 130), _span(4, "import.d", 200, 210)]
    assert setup_spans.union_ms(spans) == pytest.approx(140 / 1e6)
    assert setup_spans.union_ms([]) == 0


def test_the_table_gives_each_instant_to_the_innermost_span():
    spans = [
        _span(1, "xla.trace", 100, 900, fun_name="traced"),
        _span(2, "net.init", 200, 600),             # inside it in time only
        _span(3, "import.ops", 300, 400, 2),
        _span(4, "fit", 1000, 2000),
        _span(5, "fit.dispatch", 1100, 1900, 4),
        _span(6, "compile.probe", 1500, 1800, 5),
        _span(7, "compile.probe.lower", 1500, 1700, 6),
        _span(8, "xla.trace", 1550, 1650, 7, fun_name="step_fn"),
        _span(9, "xla.compile", 1200, 1400, 5, fetched=True),
        _span(10, "data.put", 1000, 2000, thread="feeder"),  # another thread
    ]
    rows = setup_spans.table(spans, [("one", 0, 1000), ("two", 1000, 2100)])
    assert rows["one"] == {"(no span)": 0.0002, "xla.trace": 0.0004,
                           "init": 0.0003, "import": 0.0001}
    assert rows["two"] == pytest.approx({
        "fit": 0.0002, "fit.dispatch": 0.0003,
        "xla.compile (fetched)": 0.0002, "compile.probe": 0.0001,
        "compile.probe.lower": 0.0002, "(no span)": 0.0001})
    for (_, lo, hi), phase in zip([("one", 0, 1000), ("two", 1000, 2100)],
                                  rows.values()):
        assert sum(phase.values()) == pytest.approx((hi - lo) / 1e6)


def test_the_recorded_runs_table_tiles_its_setup(recorded):
    header, spans = recorded
    from benchmarks.tests.record_setup_table import phases_ns

    phases = phases_ns(header["run"], header["t_start_ns"] / 1e9)
    setup, _ = setup_spans.split((spans, len(spans), 4096))
    rows = setup_spans.table(setup, phases)
    assert list(rows) == [*header["run"]["setup_phases_s"], "to_the_window"]
    total = sum(ms for phase in rows.values() for ms in phase.values())
    assert total == pytest.approx(header["run"]["setup_s"] * 1e3, rel=1e-6)
    # the warm-up `fit()` lies in `first_steps`, the imports before it
    assert "fit.epoch_sync" in rows["first_steps"]
    assert "import" in rows["start_to_devices"]


def test_the_tokens_tiny_rehearsal_reads_all_ten():
    # a window of 1 s: on the CPU a step is a millisecond, and the ring
    # must not wrap past set-up before the readers run
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.helpers", "1",
         "fit_stream", "1", "tokens_tiny", "--seconds", "1"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=1500,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    for metric in METRICS:
        row = result["metrics"][metric]
        assert row["unit"] == ("count" if "compile" in metric
                               and "_ms" not in metric else "ms")
    assert result["metrics"]["xla_compile_spans_in_window.train"][
        "value"] == result["metrics"]["xla_compiles_in_window.train"][
        "value"] == 0
    # no persistent cache is warm in a fresh rehearsal... or one is: the
    # count is a whole number either way
    assert result["metrics"]["xla_compiles.setup"]["value"] >= 0
    assert result["metrics"]["first_fit_ms.setup"]["value"] > \
        result["metrics"]["warmup_sync_ms.setup"]["value"] > 0


def test_the_ten_are_listed_for_every_cell_under_setup_s():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    mine = [m for m in spec["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert spec["per_layer"][-10:] == mine          # appended, in order
    for m in mine:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == ("train_items_per_s"
                              if m["name"].endswith(".train") else "setup_s")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
