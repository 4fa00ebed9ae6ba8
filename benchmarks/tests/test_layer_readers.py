"""The readers of `benchmarks/layer_metrics/` that PR 27 brought: each on
the window recorded on a v5e (`data/tiny_tpu_scoped.*`) gives what
`record_scoped_trace.py` prints for the same files (`span_reduce.report`);
which cells list which metric; `span_reduce.roofline_share`."""

import json
import os

import pytest

from benchmarks import (
    harness, run as bench_run, span_reduce, trace_reduce, xplane_schema,
)
from benchmarks.tests.helpers import tiny_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = harness.load_json("peaks.json")["TPU v5 lite"]


@pytest.fixture(scope="module")
def recorded():
    """(`facts` as `runners/fit.py` hands them to a reader, the lines of
    `span_reduce.report` for the same pair of files)."""
    space = xplane_schema.read_xspace(
        os.path.join(DATA, "tiny_tpu_scoped.xplane.pb"))
    header, spans = span_reduce.read_span_file(
        os.path.join(DATA, "tiny_tpu_scoped.spans.jsonl"))
    facts = {"trace": trace_reduce.reduce_space(space), "xspace": space,
             "clock": span_reduce.clock_link(space, header["beacons_ns"]),
             "scopes": span_reduce.by_scope(space),
             "spans": span_reduce.window(spans)}
    return facts, span_reduce.report(space, spans, header, peaks=PEAKS)


@pytest.mark.parametrize("metric", [
    "fill_ms.train", "scoped_op_time_share.train",
    "updater_time_share.train"])
def test_reader_gives_what_the_hand_tool_prints(recorded, metric):
    facts, lines = recorded
    reader = harness.load_module("layer_metrics", metric + ".py")
    assert reader.read(facts) == pytest.approx(lines["metrics"][metric])
    assert reader.read(facts) > 0
    # nothing to read: no value, no raise, never 0
    empty = dict(facts, trace=None, xspace=None, clock=None, scopes=None)
    assert reader.read(empty) is None
    assert reader.read(dict(facts, clock=None, scopes={})) is None


def test_updater_share_has_no_value_without_the_scope(recorded):
    facts, _ = recorded
    reader = harness.load_module("layer_metrics",
                                 "updater_time_share.train.py")
    scopes = {k: v for k, v in facts["scopes"].items() if k != "updater"}
    assert reader.read(dict(facts, scopes=scopes)) is None


def test_collective_share_is_the_fullest_chips():
    reader = harness.load_module("layer_metrics",
                                 "collective_time_share.train.py")
    trace = {"window_s": 2.0, "kind_s_by_device": {
        "0": {"convolution": 1.0, "collective": 0.05, "other": 0.9},
        "1": {"convolution": 1.0, "collective": 0.07, "other": 0.9}}}
    assert reader.read({"trace": trace}) == pytest.approx(3.5)
    assert reader.read({"trace": None}) is None
    for kinds in trace["kind_s_by_device"].values():
        kinds["collective"] = 0.0
    assert reader.read({"trace": trace}) is None        # never 0


def _names(cell):
    return {m["name"] for m in cell["per_layer"]}


def test_a_metric_keeps_to_the_cells_it_lists():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json"),
              encoding="utf-8") as fh:
        spec = json.load(fh)
    one, four = (bench_run.load_cell(spec, name)
                 for name in ("resnet50_fit", "resnet50_dp4"))
    assert "collective_time_share.train" in _names(four)
    assert "collective_time_share.train" not in _names(one)
    assert _names(four) - _names(one) == {"collective_time_share.train"}
    assert "conv_time_share.train" in _names(one) & _names(four) \
        & _names(bench_run.load_cell(spec, "vgg16_fit"))
    # a cell that a later PR adds gets the metrics that list no cells, and
    # neither of the two that do
    spec["workloads"].append(dict(spec["workloads"][0], name="later_fit"))
    later = _names(bench_run.load_cell(spec, "later_fit"))
    assert later == _names(one) - {"conv_time_share.train"}
    assert {"fill_ms.train", "scoped_op_time_share.train",
            "updater_time_share.train", "mfu.train"} <= later


def test_the_rehearsals_list_the_collective_share_under_the_wrapper_only():
    assert "collective_time_share.train" in _names(
        tiny_cell(4, "fit_stream_dp"))
    assert "collective_time_share.train" not in _names(
        tiny_cell(1, "fit_stream"))
    assert "collective_time_share.train" not in _names(
        tiny_cell(1, "fit_stream", "tokens_tiny"))


def test_roofline_share_on_the_recorded_window(recorded):
    facts, lines = recorded
    scopes = facts["scopes"]
    # a layer's scope, from the trace's own counts: what `layers` prints
    row = next(r for r in lines["layers"] if r["scope"] != "(no scope)")
    assert span_reduce.roofline_share(scopes, row["scope"], PEAKS) == \
        pytest.approx(row["share_of_roof"])
    # the caller's own counts, per device event of the scope
    name = row["scope"]
    s, n = scopes[name]["s"], scopes[name]["n"]
    half = 0.5 * s * PEAKS["bf16_flops_per_s"] / n
    assert span_reduce.roofline_share(
        scopes, name, PEAKS, flops=half, hbm_bytes=0.0) == pytest.approx(50.0)
    assert span_reduce.roofline_share(
        scopes, name, PEAKS, flops=0.0,
        hbm_bytes=0.25 * s * PEAKS["hbm_bytes_per_s"] / n) == \
        pytest.approx(25.0)
    # over 105%: the count is too high or time was left out: refused
    with pytest.raises(ValueError, match="counted too"):
        span_reduce.roofline_share(scopes, name, PEAKS, flops=2.2 * half)
    # nothing ran under the name, or nothing was counted: None, never 0
    assert span_reduce.roofline_share(scopes, "no_such_kernel", PEAKS) is None
    assert span_reduce.roofline_share(
        scopes, name, PEAKS, flops=0.0, hbm_bytes=0.0) is None


def test_a_pallas_calls_name_is_an_inner_scope():
    inner = span_reduce.inner_scopes
    assert inner("jit(step_fn)/jvp(lstm1)/lstm_cell_fwd/pallas_call:") == \
        ["lstm_cell_fwd"]
    assert inner("jit(step_fn)/transpose(jvp(lstm1))/jvp(lstm_cell_bwd)/"
                 "pallas_call:") == ["lstm_cell_bwd"]
    assert inner("jit(step_fn)/jvp(fc)/loss/jit(log_softmax)/sub:") == []
    assert inner("jit(step_fn)/jvp(conv1)/conv_general_dilated:") == []
    # and is summed beside its layer's row
    table = {"lstm1": {"s": 2.0, "n": 4, "flops": 8.0, "hbm_bytes": 0.0,
                       "inner": {"lstm_cell_fwd": {
                           "s": 1.0, "n": 2, "flops": 0.0,
                           "hbm_bytes": 0.0}}}}
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert span_reduce.roofline_share(
        table, "lstm_cell_fwd", peaks, flops=30.0, hbm_bytes=1.0) == \
        pytest.approx(60.0)


def test_breakdown_gets_layers_and_the_windows_gaps(recorded):
    facts, lines = recorded
    fit = harness.load_module("runners", "fit.py")
    out = fit._by_layer_and_span(facts["xspace"], [0], facts, PEAKS)
    assert out["layers"] == lines["layers"] and len(out["layers"]) <= 10
    # idle time before the window's `fit` span began (the opening beacons
    # ran there) is not the window's: what is left lies under its spans
    names = [name for name, _ in out["gaps"]]
    assert names and "(no span)" not in names and len(names) <= 10
    assert set(names) <= {s["name"] for s in facts["spans"]}
    whole = sum(row["s"] for row in lines["gaps"].values())
    assert 0 < sum(s for _, s in out["gaps"]) < whole
    # without a clock link: layers alone
    assert set(fit._by_layer_and_span(
        facts["xspace"], [0], dict(facts, clock=None), PEAKS)) == {"layers"}
