"""Keeps tools/shardmap_smoke.py runnable: the harness must stay green
on the CPU mesh (interpret mode) so chip time is never wasted on a
harness bug. The tool's real purpose is the non-interpret run on
the chip — interpret mode cannot catch Mosaic lowering errors
(VERDICT r4 #4) — so this test is necessary, not sufficient.
"""
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "tools")


@pytest.fixture(scope="module")
def smoke():
    os.environ["SMOKE_INTERPRET"] = "1"
    sys.path.insert(0, TOOLS)
    try:
        import shardmap_smoke
        yield shardmap_smoke
    finally:
        sys.path.remove(TOOLS)   # the module itself inserts repo root at 0
        os.environ.pop("SMOKE_INTERPRET", None)


def _check_names():
    # enumerate without importing jax-heavy module at collection: the
    # names mirror CHECKS; the count assertion below keeps them in sync
    return ["flash_fwd_shardmap", "flash_bwd_shardmap",
            "fused_lstm_shardmap", "conv_fused_shardmap", "ring_flash",
            "kv_decode", "kv_decode_gqa_rolling"]


def test_name_list_matches_tool(smoke):
    assert [c.__name__.replace("check_", "") for c in smoke.CHECKS] == \
        _check_names(), "update _check_names() when CHECKS changes"


@pytest.mark.parametrize("name", _check_names())
def test_check_passes_on_cpu_mesh(smoke, name, devices8):
    check = next(c for c in smoke.CHECKS
                 if c.__name__ == f"check_{name}")
    r = check()
    assert r["max_err"] <= r["tol"], (name, r)
