"""`topk_moe_rows_gathered_share.train` on gauges set by hand and on a
program without them. CPU, no device number."""

import pytest

from benchmarks import harness


def _read(facts):
    return harness.load_module(
        "layer_metrics", "topk_moe_rows_gathered_share.train.py").read(facts)


def test_the_share_is_gathered_over_tier_and_nothing_without_the_gauges():
    from deeplearning4j_tpu.observe import get_registry

    registry = get_registry()
    registry.reset()
    run = {"run": {"global_batch": 1, "chips": 1}}
    assert _read(run) is None               # an older program: no gauge
    registry.gauge("moe_rows_tier", layer="a").set(73728)
    registry.gauge("moe_rows_tier", layer="b").set(73728)
    assert _read(run) is None               # the parent of PR 44
    registry.gauge("moe_rows_gathered", layer="a").set(10240)
    registry.gauge("moe_rows_gathered", layer="b").set(26624)
    assert _read(run) is None               # no state-space layer
    registry.gauge("ssm_chunk_carry", layer="a").set(0.03)
    assert _read(run) == pytest.approx(25.0)
    registry.gauge("moe_rows_gathered", layer="a").set(73728)
    registry.gauge("moe_rows_gathered", layer="b").set(73728)
    assert _read(run) == pytest.approx(100.0)     # XLA's gather ran
    registry.reset()
