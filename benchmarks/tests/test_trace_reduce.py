"""The reduction, on interval arithmetic made by hand and on a small
trace recorded on a v5e (`data/tiny_tpu.xplane.pb`, made by
`record_trace.py`)."""

import os

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
    assert out["main_module_runs_per_s"] > 0
    # a cell's devices can be picked: none of ours is chip 3
    assert trace_reduce.reduce_space(space, devices=[3]) is None
