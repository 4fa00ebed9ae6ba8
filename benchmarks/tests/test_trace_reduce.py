"""The reduction, on interval arithmetic made by hand and on a small
trace recorded on a v5e (`data/tiny_tpu.xplane.pb`, made by
`record_trace.py`)."""

import os

import pytest

from benchmarks import trace_reduce, xplane_schema

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_tpu.xplane.pb")


def test_union_and_self_times():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    rows = trace_reduce.self_times([
        {"name": "loop", "category": "while", "start": 0, "dur": 10},
        {"name": "body", "category": "loop fusion", "start": 2, "dur": 3},
        {"name": "after", "category": "convolution fusion", "start": 12,
         "dur": 1}])
    assert {r["name"]: r["self"] for r in rows} == {
        "loop": 7, "body": 3, "after": 1}


def test_gaps_are_named_from_the_programs_around_them():
    spans = [(0, 10, "jit_a"), (20, 30, "jit_b")]
    assert trace_reduce.gap_name(3, 5, spans) == "inside jit_a"
    assert trace_reduce.gap_name(10, 20, spans) == "before jit_b"
    assert trace_reduce.gap_name(30, 40, spans) == "after the last program"


def test_kinds_come_from_the_category():
    assert trace_reduce.op_kind("convolution fusion") == "convolution"
    assert trace_reduce.op_kind("all-reduce") == "collective"
    assert trace_reduce.op_kind("loop fusion") == "other"
    assert trace_reduce.op_kind("") == "other"


def test_recorded_trace():
    space = xplane_schema.read_xspace(RECORDED)
    out = trace_reduce.reduce_space(space)
    assert out["devices"] == [0]
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share_worst"] < 1
    kinds = out["kind_s_by_device"]["0"]
    assert kinds["convolution"] > 0 and kinds["collective"] == 0
    assert abs(sum(kinds.values()) - out["busy_s"]) < 1e-9
    assert "convolution fusion" in out["category_s"]
    assert len(out["breakdown"]["device_ops"]) == trace_reduce.TOP
    names = [name for name, _ in out["breakdown"]["device_ops"]]
    assert all(" = " not in n and len(n) < 64 for n in names)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps and all(name.startswith(("before jit_", "inside jit_"))
                        for name in gaps)
    assert out["main_module"] == "jit_step_fn"
    # the trace was cut to its first ops: one run of the step starts in
    # what is left, and one run has no rate
    assert out["main_module_runs"] == 1
    assert out["main_module_runs_per_s"] is None
    # a cell's devices can be picked: none of ours is chip 3
    assert trace_reduce.reduce_space(space, devices=[3]) is None


def test_runs_per_s_is_exact_on_a_fixed_period():
    period_ps = 93_570_000_000                  # 93.57 ms
    starts = [7 + i * period_ps for i in range(43)]
    assert trace_reduce.runs_per_s(starts) == 1e12 / period_ps
    # counting the runs that start in the window over its length, as the
    # reduction did until PR 27, reads one run too many
    window_s = (starts[-1] - starts[0]) / 1e12
    assert len(starts) / window_s == \
        trace_reduce.runs_per_s(starts) * len(starts) / (len(starts) - 1)
    assert trace_reduce.runs_per_s(starts[:1]) is None
    assert trace_reduce.runs_per_s([]) is None


def test_recorded_step_rate_differs_from_the_old_count_by_one_run():
    space = xplane_schema.read_xspace(RECORDED)
    plane = next(p for p in space.planes
                 if p.name.startswith(trace_reduce.DEVICE_PLANE))
    name, starts = trace_reduce.main_module_starts(plane, 0, 10 ** 18)
    assert name == "jit_step_fn" and len(starts) > 20
    span_s = (starts[-1] - starts[0]) / 1e12
    new, old = trace_reduce.runs_per_s(starts), len(starts) / span_s
    assert (old - new) * span_s == pytest.approx(1.0)


def test_beacon_runs_are_left_out_of_the_reduction():
    """The clock link's beacons run before the first step and after the
    last: the window, the busy time and the step's runs read as in a trace
    without them."""
    scoped = xplane_schema.read_xspace(os.path.join(
        os.path.dirname(RECORDED), "tiny_tpu_scoped.xplane.pb"))
    plane = next(p for p in scoped.planes
                 if p.name.startswith(trace_reduce.DEVICE_PLANE))
    runs = trace_reduce.module_spans(plane)
    beacons = [(s, e) for s, e, n in runs if n == trace_reduce.BEACON]
    first_other = min(s for s, _, n in runs if n != trace_reduce.BEACON)
    assert len(beacons) == 3 and max(e for _, e in beacons) < first_other
    out = trace_reduce.reduce_space(scoped)
    events = list(trace_reduce.device_events(plane))
    whole = max(e["start"] + e["dur"] for e in events) \
        - min(e["start"] for e in events)
    assert out["window_s"] < whole / 1e12       # starts after the beacons
    assert out["main_module"] == "jit_step_fn"
    assert out["main_module_runs"] == 2
    assert out["main_module_runs_per_s"] == pytest.approx(
        1e12 / (runs[-1][0] - [s for s, _, n in runs
                               if n == "jit_step_fn"][0]))
