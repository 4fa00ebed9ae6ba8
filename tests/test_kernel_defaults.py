"""Kernel-default consistency guard (VERDICT r4 #5).

A hand-written kernel may only be a dispatch default where a recorded
hardware measurement says it beats its XLA alternative — the discipline
the reference applied to its cuDNN helpers
(`deeplearning4j-cuda/.../CudnnConvolutionHelper.java:54`). These tests
fail if:
  - the MEASURED table embedded in ops/kernel_defaults.py has drifted
    from tools/kernel_bench_results.json (updater not re-run), or
  - the policy would pick a kernel configuration that contradicts (or
    lacks) its measured winning row.
"""
import json
import os

import pytest

from deeplearning4j_tpu.ops import kernel_defaults as kd

HERE = os.path.dirname(os.path.abspath(__file__))
RESULTS = os.path.join(HERE, "..", "tools", "kernel_bench_results.json")


def _tpu_shapes(monkeypatch):
    """Simulate the TPU shape gate so policy decisions are testable on
    the CPU suite (same tiling/floor logic, minus the backend check)."""
    monkeypatch.setattr(
        kd, "_shape_eligible",
        lambda tq, tk, min_t=512: (tq % 128 == 0 and tk % 128 == 0
                                   and min(tq, tk) >= min_t))


def test_embedded_table_matches_results_file():
    import sys
    sys.path.insert(0, os.path.join(HERE, "..", "tools"))
    try:
        from update_kernel_defaults import build_table
    finally:
        sys.path.pop(0)
    with open(RESULTS) as fh:
        rows = json.load(fh)
    assert kd.MEASURED == build_table(rows), (
        "ops/kernel_defaults.py MEASURED table is stale — run "
        "python tools/update_kernel_defaults.py after benching")


def test_attention_policy_agrees_with_measured_winners(monkeypatch):
    _tpu_shapes(monkeypatch)
    for mode, by_t in kd.MEASURED["attention"].items():
        train = mode == "train"
        for t, row in by_t.items():
            if t >= kd.dense_max_t():
                continue   # memory necessity overrides the speed verdict
            pol = kd.attention_policy(t, train=train)
            assert pol.kind == row["winner"], (
                f"{mode}@T={t}: policy picks {pol.kind} but measured "
                f"winner is {row['winner']} ({row['flash_ms']} vs "
                f"{row['dense_ms']} ms)")
            if pol.kind == "flash":
                assert (pol.block_q, pol.block_k) == (
                    row["block_q"], row["block_k"]), (
                    f"{mode}@T={t}: policy blocks {pol.block_q}x"
                    f"{pol.block_k} != measured best "
                    f"{row['block_q']}x{row['block_k']}")


def test_flash_default_requires_winning_row(monkeypatch):
    """The sharpest r4 finding: no flash-by-default without a recorded
    win. If the policy would use flash below the memory threshold, a
    winning measured row must exist at the nearest benchmarked T."""
    _tpu_shapes(monkeypatch)
    for t in (512, 1024, 2048, 4096):
        for train in (False, True):
            pol = kd.attention_policy(t, train=train)
            if pol.kind != "flash" or t >= kd.dense_max_t():
                continue
            mode = "train" if train else "fwd"
            table = kd.MEASURED["attention"][mode]
            mt = kd._nearest_measured(table, t)
            assert mt is not None and table[mt]["winner"] == "flash", (
                f"flash default at T={t} ({mode}) has no winning "
                f"measured row backing it")


def test_pallas_backward_requires_winning_row(monkeypatch):
    _tpu_shapes(monkeypatch)
    for t in (512, 1024, 2048, 4096):
        if t >= kd.dense_max_t():
            continue
        if kd.attention_backward(t) == "pallas":
            table = kd.MEASURED["attention"]["train"]
            mt = kd._nearest_measured(table, t)
            assert (mt is not None
                    and table[mt]["winner"] == "flash"
                    and table[mt]["backward"] == "pallas"), (
                f"pallas backward default at T={t} lacks a winning "
                f"measured train row")


def test_memory_necessity_overrides_speed(monkeypatch):
    """Past DENSE_MAX_T the [T, T] dense path is a memory hazard: flash
    with the O(T) Pallas backward is mandatory regardless of verdicts."""
    _tpu_shapes(monkeypatch)
    t = kd.dense_max_t()
    pol = kd.attention_policy(t, train=True)
    assert pol.kind == "flash"
    assert pol.backward == "pallas"
    # the hazard scales with Tq*Tk, not min: a long-context
    # cross-attention with a short query side must also route to flash
    pol = kd.attention_policy(t // 4, t * 4, train=True)
    assert pol.kind == "flash"
    assert pol.backward == "pallas"
    assert kd.attention_backward(t // 4, t * 4) == "pallas"
    # ...even when the query side is below the 512 perf floor — the
    # kernel capability floor (128) governs the memory-necessity path
    pol = kd.attention_policy(256, 2 * t * t // 256, train=True)
    assert pol.kind == "flash", pol
    # but below the perf floor WITHOUT memory pressure, dense wins
    assert kd.attention_policy(256, 256, train=True).kind == "dense"


def test_env_escape_hatches(monkeypatch):
    _tpu_shapes(monkeypatch)
    monkeypatch.setenv("DL4J_TPU_ATTN", "dense")
    assert kd.attention_policy(8192, train=True).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    pol = kd.attention_policy(1024, train=True)
    assert pol.kind == "flash"
    monkeypatch.setenv("DL4J_TPU_ATTN_BACKWARD", "pallas")
    assert kd.attention_policy(1024, train=True).backward == "pallas"
    monkeypatch.setenv("DL4J_TPU_ATTN_BLOCK", "256x128")
    pol = kd.attention_policy(1024, train=True)
    assert (pol.block_q, pol.block_k) == (256, 128)
    # shape ineligibility still wins over a flash force
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    assert kd.attention_policy(1000, train=True).kind == "dense"


def test_dense_max_t_env(monkeypatch):
    _tpu_shapes(monkeypatch)
    monkeypatch.setenv("DL4J_TPU_DENSE_MAX_T", "2048")
    assert kd.attention_policy(2048, train=True).kind == "flash"


def test_lstm_policy_agrees_with_measured(monkeypatch):
    table = kd.MEASURED["lstm"]
    assert table, "no LSTM rows measured at all"
    for mode, row in table.items():
        assert kd.lstm_policy(train=(mode == "train")) == row["winner"]
    monkeypatch.setenv("DL4J_TPU_LSTM", "scan")
    assert kd.lstm_policy() == "scan"


def test_flash_attention_backward_resolution_matches_policy():
    """flash_attention(backward=None) resolves through the same
    function the policy uses, so layer dispatch and direct op calls
    can't disagree."""
    from deeplearning4j_tpu.ops.attention import _resolve_backward

    for t in (512, 1024, 2048, 8192):
        assert _resolve_backward(None, t, t) == kd.attention_backward(t)
    assert _resolve_backward("pallas", 1024, 1024) == "pallas"


def _banded_shapes(monkeypatch, value=True):
    """Simulate TPU eligibility for the banded kernel gates (the
    policies import these at call time, so patching the op module's
    attributes reaches them)."""
    # NB: ops/__init__ re-exports a function named banded_attention
    # that shadows the module attribute — go through sys.modules
    import importlib
    ba = importlib.import_module(
        "deeplearning4j_tpu.ops.banded_attention")
    monkeypatch.setattr(
        ba, "banded_eligible",
        lambda t, h, hkv, min_t=256, any_backend=False: value)
    monkeypatch.setattr(ba, "decode_eligible",
                        lambda cache_len, h, hkv: value)


def test_banded_policy_env_hatches(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_ATTN", "dense")
    assert kd.banded_policy(256, 4, 2).kind == "dense"
    # flash cannot band: a flash force on a windowed shape stays dense
    monkeypatch.setenv("DL4J_TPU_ATTN", "flash")
    assert kd.banded_policy(256, 4, 2).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_ATTN", "banded")
    pol = kd.banded_policy(256, 4, 2)
    assert pol.kind == "banded"
    assert (pol.block_q, pol.block_k) == (256, 256)
    # block override flows through the force, like flash's
    monkeypatch.setenv("DL4J_TPU_ATTN_BLOCK", "128x64")
    pol = kd.banded_policy(256, 4, 2)
    assert (pol.block_q, pol.block_k) == (128, 64)
    monkeypatch.delenv("DL4J_TPU_ATTN_BLOCK")
    # ...but tiling ineligibility still wins over the force (backend
    # does NOT: a force runs interpret-mode off-TPU by design)
    assert kd.banded_policy(100, 4, 2).kind == "dense"
    assert kd.banded_policy(256, 4, 3).kind == "dense"   # h % hkv != 0


def test_banded_policy_conservative_without_rows(monkeypatch):
    """Dispatch discipline: even on eligible shapes, banded is not the
    default until a winning MEASURED['banded'] row exists. When a real
    banded bench lands, update this pin together with the table."""
    if kd.MEASURED.get("banded"):
        pytest.skip("banded rows measured; pin no longer applies")
    _banded_shapes(monkeypatch)
    for train in (False, True):
        pol = kd.banded_policy(1024, 8, 2, train=train)
        assert pol.kind == "dense", pol
        assert "no measured rows" in pol.reason


def test_banded_policy_agrees_with_measured_winners(monkeypatch):
    _banded_shapes(monkeypatch)
    for mode, by_t in kd.MEASURED.get("banded", {}).items():
        train = mode == "train"
        for t, row in by_t.items():
            if not train and kd._mem_hazard(t, t):
                continue   # memory necessity overrides the verdict
            pol = kd.banded_policy(t, 8, 2, train=train)
            assert pol.kind == row["winner"], (
                f"banded {mode}@T={t}: policy picks {pol.kind} but "
                f"measured winner is {row['winner']}")
            if pol.kind == "banded":
                assert (pol.block_q, pol.block_k) == (
                    row["block_q"], row["block_k"])


def test_decode_policy_env_and_default(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "dense")
    assert kd.decode_attention_policy(512, 8, 2).kind == "dense"
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "banded")
    pol = kd.decode_attention_policy(512, 8, 2)
    assert pol.kind == "banded" and pol.block_l == 512
    monkeypatch.delenv("DL4J_TPU_DECODE_ATTN")
    # eligible shape, no measured rows -> conservative dense
    if kd.MEASURED.get("decode"):
        pytest.skip("decode rows measured; pin no longer applies")
    _banded_shapes(monkeypatch)
    pol = kd.decode_attention_policy(512, 8, 2)
    assert pol.kind == "dense"
    assert "no measured rows" in pol.reason


def test_decode_policy_record_flag_gates_counter(monkeypatch):
    """Observers (serving snapshots) ask what WOULD dispatch with
    record=False; kernel_dispatch_total must count only real dispatch
    sites, or snapshot polling would inflate the metric."""
    from deeplearning4j_tpu.observe import get_registry
    monkeypatch.setenv("DL4J_TPU_DECODE_ATTN", "dense")
    c = get_registry().counter("kernel_dispatch_total",
                               op="decode_attention", impl="dense")
    v0 = c.value
    kd.decode_attention_policy(512, 8, 2, record=False)
    assert c.value == v0
    kd.decode_attention_policy(512, 8, 2)
    assert c.value == v0 + 1


def test_current_data_yields_dense_defaults(monkeypatch):
    """Regression pin for the r4 ADVICE finding: with the rows recorded
    today (flash loses everywhere measured), training and inference
    attention below the memory threshold must default to XLA dense.
    When a winning 512-block sweep is persisted, this test must be
    UPDATED alongside the table — that is the point: defaults move only
    together with data."""
    _tpu_shapes(monkeypatch)
    table = kd.MEASURED["attention"]
    if any(r["winner"] == "flash"
           for by_t in table.values() for r in by_t.values()):
        pytest.skip("a winning flash row exists; pin no longer applies")
    assert kd.attention_policy(2048, train=True).kind == "dense"
    assert kd.attention_policy(2048, train=False).kind == "dense"
    assert kd.attention_backward(2048) == "dense"
