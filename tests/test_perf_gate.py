"""Unit tests for the slim perf gate (tools/perf_gate.py) and the
snapshot reader of the comm advisor (tools/comm_report.py).

The gate keeps its decision logic pure — compare() takes dicts in,
lists out — precisely so the gate semantics can be tested here
without running the workload or touching a device. The
workload run itself is exercised by CI via `tools/ci_check.sh --perf`.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))

import perf_gate            # noqa: E402
import comm_report          # noqa: E402


def _measured(**over):
    out = {"workload_version": perf_gate.WORKLOAD_VERSION,
           "compiles_per_owner": {"MultiLayerNetwork": 3},
           "total_compiles": 3,
           "syncs_per_step": 0.25}
    out.update(over)
    return out


def _baseline(**over):
    out = dict(_measured(), budgets=dict(perf_gate.DEFAULT_BUDGETS))
    out.update(over)
    return out


class TestCompare:
    def test_identical_passes(self):
        assert perf_gate.compare(_baseline(), _measured()) == []

    def test_within_budget_passes(self):
        base = _baseline(budgets={"extra_compiles_per_owner": 1,
                                  "extra_syncs_per_step": 0.5})
        meas = _measured(compiles_per_owner={"MultiLayerNetwork": 4},
                         syncs_per_step=0.75)
        assert perf_gate.compare(base, meas) == []

    def test_over_budget_compiles_breach(self):
        meas = _measured(compiles_per_owner={"MultiLayerNetwork": 5})
        breaches = perf_gate.compare(_baseline(), meas)
        assert len(breaches) == 1
        assert "MultiLayerNetwork" in breaches[0]
        assert "5 compiles" in breaches[0]

    def test_new_owner_breach(self):
        meas = _measured(compiles_per_owner={"MultiLayerNetwork": 3,
                                             "MysteryCache": 1})
        breaches = perf_gate.compare(_baseline(), meas)
        assert len(breaches) == 1
        assert "MysteryCache" in breaches[0]
        assert "not in baseline" in breaches[0]

    def test_sync_regression_breach(self):
        meas = _measured(syncs_per_step=1.0)   # baseline 0.25 + 0.5
        breaches = perf_gate.compare(_baseline(), meas)
        assert len(breaches) == 1
        assert "syncs/step" in breaches[0]

    def test_version_mismatch_is_single_stale_message(self):
        # a stale baseline must not cascade into per-owner noise
        meas = _measured(workload_version=perf_gate.WORKLOAD_VERSION + 1,
                         compiles_per_owner={"A": 99, "B": 99},
                         syncs_per_step=50.0)
        breaches = perf_gate.compare(_baseline(), meas)
        assert len(breaches) == 1
        assert "stale" in breaches[0]

    def test_disappeared_owner_and_improvement_pass(self):
        base = _baseline(compiles_per_owner={"MultiLayerNetwork": 3,
                                             "Gone": 2},
                         syncs_per_step=0.5)
        meas = _measured(syncs_per_step=0.125)
        assert perf_gate.compare(base, meas) == []
        # ...but diff() still reports them informationally
        d = perf_gate.diff(base, meas)
        assert any("Gone" in line for line in d)
        assert any("syncs_per_step" in line for line in d)

    def test_traced_leg_gated_when_baselined(self):
        base = _baseline(traced={"syncs_per_step": 0.25,
                                 "extra_syncs_per_step": 0.0})
        meas = _measured(traced={"syncs_per_step": 0.5,
                                 "extra_syncs_per_step": 0.25})
        breaches = perf_gate.compare(base, meas)
        assert len(breaches) == 1
        assert "traced" in breaches[0] and "sync-free" in breaches[0]
        # exactly zero extra syncs passes (the contract)
        ok = _measured(traced={"syncs_per_step": 0.25,
                               "extra_syncs_per_step": 0.0})
        assert perf_gate.compare(base, ok) == []
        # the leg is not gated until a baseline records it
        assert perf_gate.compare(_baseline(), meas) == []

    def test_checked_in_baseline_gates_traced_leg(self):
        import json
        with open(perf_gate.BASELINE_PATH) as fh:
            base = json.load(fh)
        assert base["traced"]["extra_syncs_per_step"] == 0.0
        assert base["budgets"]["extra_traced_syncs_per_step"] == 0.0

    def test_checked_in_baseline_is_current_version(self):
        import json
        with open(perf_gate.BASELINE_PATH) as fh:
            base = json.load(fh)
        assert base["workload_version"] == perf_gate.WORKLOAD_VERSION
        assert "compiles_per_owner" in base
        assert "syncs_per_step" in base


def _snapshot():
    # one memory-bound elementwise owner, one compute-bound matmul owner
    return {"threshold": 6, "total_compiles": 3, "per_owner": {
        "Elementwise@0x1": {"compiles": 1, "signatures": 1, "costs": {
            "sig_a": {"flops": 1e6, "bytes_accessed": 16e6}}},
        "Matmul@0x2": {"compiles": 2, "signatures": 2, "costs": {
            "sig_b": {"flops": 4e12, "bytes_accessed": 8e9},
            "sig_c": {"flops": 0.0, "bytes_accessed": 0.0}}},
    }}


class TestRoofline:
    def test_extract_raw_and_nested(self):
        snap = _snapshot()
        assert comm_report.extract_watchdog(snap) is snap
        assert comm_report.extract_watchdog({"watchdog": snap}) is snap
        with pytest.raises(ValueError):
            comm_report.extract_watchdog({"metric": "nope"})
