"""`span_reduce` on spans and intervals made by hand, and on a window
recorded on a v5e with the program's spans beside the device trace
(`data/tiny_tpu_scoped.xplane.pb` and `.spans.jsonl`, made by
`record_scoped_trace.py`); the three `program_span` readers on those
spans."""

import os

import pytest

from benchmarks import harness, span_reduce, xplane_schema

DATA = os.path.join(os.path.dirname(__file__), "data")
PB = os.path.join(DATA, "tiny_tpu_scoped.xplane.pb")
SPANS = os.path.join(DATA, "tiny_tpu_scoped.spans.jsonl")


def _span(sid, parent, name, start, end, **attrs):
    return {"name": name, "start_ns": start, "end_ns": end, "span_id": sid,
            "parent_id": parent, "thread": "MainThread", "attrs": attrs}


HAND = [
    _span(1, None, "fit", 0, 50),                    # an earlier fit()
    _span(2, None, "fit", 100, 1100),
    _span(3, 2, "fit.epoch", 110, 1090),
    _span(4, 3, "fit.etl", 120, 220),
    _span(5, 4, "data.put", 130, 210, bytes=10),
    _span(6, 3, "fit.dispatch", 230, 530, iteration=0, steps=1, fused=False),
    _span(7, 3, "fit.listeners", 540, 560),
    _span(8, 3, "fit.etl", 570, 580, exhausted=True),
    _span(9, 3, "fit.epoch_sync", 600, 1000),
]


def test_window_is_the_last_fit_and_self_time_leaves_the_children_out():
    win = span_reduce.window(HAND)
    assert [s["span_id"] for s in win] == [2, 3, 4, 5, 6, 7, 8, 9]
    own = {s["span_id"]: s["self_ns"] for s in span_reduce.self_times(win)}
    assert own == {2: 20, 3: 980 - 100 - 300 - 20 - 10 - 400, 4: 20, 5: 80,
                   6: 300, 7: 20, 8: 10, 9: 400}
    facts = span_reduce.summary(win)
    assert facts["by_name"]["fit.etl"]["n"] == 2
    assert facts["unexplained_share"] == pytest.approx((20 + 150) / 1000)
    assert span_reduce.window([]) == [] == span_reduce.window(None)
    # an exhausted wait is no batch
    assert span_reduce.durations_ms(win, "fit.etl") == [100 / 1e6]


def test_scopes_are_parsed_from_the_ops_names():
    parse = span_reduce.parse_scope
    assert parse("jit(step_fn)/jvp(conv1)/conv_general_dilated:") == \
        ("conv1", "forward")
    assert parse("jit(step_fn)/transpose(jvp(conv1))/mul:") == \
        ("conv1", "backward")
    assert parse("jit(step_fn)/jvp(fc)/loss/jit(log_softmax)/sub:") == \
        ("fc/loss", "forward")
    assert parse("jit(step_fn)/transpose(jvp(fc))/loss/dot_general:") == \
        ("fc/loss", "backward")
    assert parse("jit(step_fn)/updater/sub:") == ("updater", "forward")
    assert parse("jit(fused)/while/body/jvp(l0)/add:") == ("l0", "forward")
    for bare in ("jit(step_fn)/transpose(jvp())/dot_general:", "x:", "",
                 "jit(step_fn)/convert_element_type:"):
        assert parse(bare) == (None, None)


def test_a_gap_goes_to_the_innermost_span_that_covers_it():
    # the device's zero at 100 ns of the span clock; gaps in picoseconds
    gaps = [[150_000, 200_000],      # 250..300 ns: inside fit.dispatch
            [420_000, 480_000],      # 520..580 ns: straddles three spans
            [600_000, 800_000],      # 700..900 ns: inside fit.epoch_sync
            [2_000_000, 2_100_000]]  # after every span
    out = span_reduce.gaps_by_span(gaps, span_reduce.window(HAND), 100.0)
    assert list(out) == ["fit.epoch_sync", "(no span)", "fit.epoch",
                         "fit.dispatch"]            # most idle time first
    assert out["fit.dispatch"]["n"] == 1
    assert out["fit.epoch"]["s"] == pytest.approx(60_000 / 1e12)
    assert out["(no span)"]["n"] == 1
    assert out["fit.epoch_sync"]["longest_ms"] == pytest.approx(2e-4)


def test_fill_runs_from_the_fit_span_to_the_first_step_on_the_device():
    runs = [(5_000, 6_000), (300_000, 400_000), (500_000, 600_000)]
    # zero at 50 ns: the first run (55 ns) lies before the window's fit
    assert span_reduce.fill_ms(span_reduce.window(HAND), runs, 50.0) == \
        pytest.approx((50 + 300 - 100) / 1e6)
    assert span_reduce.fill_ms([], runs, 50.0) is None


# ------------------------------------------------------- the recorded pair
@pytest.fixture(scope="module")
def recorded():
    header, spans = span_reduce.read_span_file(SPANS)
    return xplane_schema.read_xspace(PB), header, spans


def test_recorded_clock_link(recorded):
    space, header, _ = recorded
    assert header["clock"] == "perf_counter_ns" and header["beacons_ns"]
    link = span_reduce.clock_link(space, header["beacons_ns"])
    assert link["consistent"] and link["beacons"] >= 3
    assert link["residual_ns"] < 1e6            # under 1 ms
    # every beacon's run lies inside its bracket on the linked clock
    assert span_reduce.clock_link(space, []) is None


def test_recorded_scopes_sum_to_the_op_time(recorded):
    space, _, _ = recorded
    table = span_reduce.by_scope(space)
    scoped, updater = span_reduce.scope_shares(table)
    # at 32x32 and 8 rows the asynchronous weight copies, which carry no
    # scope, are half the op time; the real cells read 95% and 99%
    assert 40.0 < scoped <= 100.0 and 0.0 < updater < 50.0
    layers = {s for s in table if s}
    assert "updater" in layers and any(s.endswith("/loss") for s in layers)
    conv = [s for s in layers if "conv" in s]
    assert conv and all(table[s]["forward_s"] > 0 for s in conv)
    assert any(table[s]["backward_s"] > 0 for s in conv)
    assert all(abs(r["s"] - r["forward_s"] - r["backward_s"]) < 1e-12
               for s, r in table.items() if s)
    name, runs = span_reduce.main_module(space)
    assert name == "jit_step_fn" and len(runs) >= 2
    rows = span_reduce.layers(table, len(runs), 1,
                              harness.load_json("peaks.json")["TPU v5 lite"])
    assert len(rows) == span_reduce.TOP
    assert all(r["nearer_roof"] in ("flops", "hbm")
               and 0 <= r["share_of_roof"] <= 105 for r in rows)
    assert rows == sorted(rows, key=lambda r: -r["ms_per_step"])


def test_recorded_gaps_and_fill_fall_in_the_fit_loop(recorded):
    space, header, spans = recorded
    lines = span_reduce.report(space, spans, header)
    assert set(lines) == {"clock", "steps", "spans", "layers", "gaps",
                          "metrics"}
    assert lines["steps"]["n"] >= 2 and lines["steps"]["median_ms"] > 0
    assert lines["clock"]["main_module"] == "jit_step_fn"
    names = set(lines["gaps"])
    assert names and names <= {"fit", "fit.epoch", "fit.etl", "data.put",
                               "fit.dispatch", "fit.listeners",
                               "fit.epoch_sync", "(no span)"}
    assert 0 < lines["metrics"]["fill_ms.train"] < 2000
    assert {"put_ms.train", "listeners_ms.train",
            "scoped_op_time_share.train",
            "updater_time_share.train"} <= set(lines["metrics"])


@pytest.mark.parametrize("metric,want", [
    ("epoch_sync_ms.train", 400 / 1e6), ("put_ms.train", 80 / 1e6),
    ("listeners_ms.train", 20 / 1e6)])
def test_program_span_readers(monkeypatch, metric, want):
    reader = harness.load_module("layer_metrics", metric + ".py")
    # a program with no span store (the parent of PR 25): no value, no raise
    monkeypatch.setattr(span_reduce, "program_spans", lambda: None)
    assert reader.read({}) is None
    monkeypatch.setattr(span_reduce, "program_spans", lambda: HAND)
    assert reader.read({}) == pytest.approx(want)
    # and out of the program's own store, after a fit() of its own
    monkeypatch.undo()
    from deeplearning4j_tpu.observe import get_flight, span

    get_flight()
    with span("fit"):
        with span("fit.epoch"):
            with span("fit.etl"):
                with span("data.put", bytes=1):
                    pass
            with span("fit.listeners"):
                pass
            with span("fit.epoch_sync"):
                pass
    assert reader.read({}) > 0


def test_rehearsal_sees_data_put_under_parallel_wrapper():
    """Four virtual devices, `ParallelWrapper.fit()`: every batch goes out
    through `MeshContext.put_batch` under a `data.put` span, and the three
    `program_span` metrics are on the traced line."""
    from benchmarks.tests.test_rehearsal import rehearse

    result = rehearse(4, "fit_stream_dp", 1)
    assert {"epoch_sync_ms.train", "put_ms.train",
            "listeners_ms.train"} <= set(result["metrics"])
    assert result["metrics"]["put_ms.train"]["value"] > 0
    assert result["metrics"]["put_ms.train"]["unit"] == "ms"
