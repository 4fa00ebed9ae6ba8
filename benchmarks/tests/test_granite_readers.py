"""`granite_4_0_h_small`'s six readers on tables made by hand and on a
program without their scopes or gauges, and the configuration's arithmetic
from its file. CPU, no device number."""

import math

import pytest

from benchmarks import harness

TRACE_READERS = ("ssm_time_share", "ssm_mixer_time_share",
                 "topk_moe_time_share")
GAUGE_READERS = ("topk_moe_held_pair_share", "topk_moe_pairs_dropped",
                 "ssm_chunk_carry_share")


def _facts():
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    moe = {"router": row(0.02, 8), "dispatch": row(0.03, 8),
           "experts_held": row(0.24, 12), "combine": row(0.01, 8),
           "shared_expert": row(0.1, 8)}
    scopes = {
        # `ssm_core`, `ssm_conv` and `ssm_gate_norm` lie inside `ssm_mixer`
        "layer1_prenormblock": {"s": 1.0, "n": 10, "inner": {
            "ssm_mixer": row(0.5, 60), "ssm_conv": row(0.05, 6),
            "ssm_core": row(0.3, 40), "ssm_gate_norm": row(0.04, 6), **moe}},
        "layer6_prenormblock": {"s": 1.0, "n": 10, "inner": {
            "attention_core": row(0.2, 3), **moe}}}
    return {"trace": {}, "scopes": scopes, "run": {
        "peaks": None, "workload": "granite_4_0_h_small_fit",
        "global_batch": 1, "chips": 1, "tokens_per_item": 8192}}


def _read(name, facts):
    return harness.load_module("layer_metrics", name + ".train.py").read(facts)


def test_readers_on_a_table_made_by_hand():
    facts = _facts()
    assert _read("ssm_time_share", facts) == pytest.approx(15.0)
    # the mixer's share holds the scan's, which is not counted twice
    assert _read("ssm_mixer_time_share", facts) == pytest.approx(25.0)
    assert _read("topk_moe_time_share", facts) == pytest.approx(40.0)


def test_readers_find_nothing_where_there_is_nothing():
    """An older program (no such scope, no such gauge), an untraced run, a
    model with experts and no state-space layer: no value, nothing
    raised."""
    from deeplearning4j_tpu.observe import get_registry

    untraced = {"trace": None, "scopes": None, "run": {"peaks": None}}
    other = _facts()
    del other["scopes"]["layer1_prenormblock"]
    for name in TRACE_READERS:
        assert _read(name, untraced) is None
        assert _read(name, other) is None
    registry = get_registry()
    registry.reset()
    run = {"run": {"global_batch": 1, "chips": 1}}
    for name in GAUGE_READERS:
        assert _read(name, run) is None
    # another model's routing gauges alone are not these metrics'
    registry.gauge("moe_pairs_held", layer="a").set(10240)
    registry.gauge("moe_pairs_routed", layer="a").set(81920)
    registry.gauge("moe_pairs_dropped", layer="a").set(0)
    for name in GAUGE_READERS:
        assert _read(name, run) is None
    registry.gauge("ssm_chunk_carry", layer="a").set(0.03)
    registry.gauge("ssm_chunk_carry", layer="b").set(0.05)
    assert _read("topk_moe_held_pair_share", run) == pytest.approx(12.5)
    assert _read("topk_moe_pairs_dropped", run) == 0
    assert _read("ssm_chunk_carry_share", run) == pytest.approx(4.0)
    registry.reset()


def test_granite_arithmetic_from_its_configuration():
    """The cut's parameters as ISSUE 42's table has them, what a token
    costs, and what a held expert sees."""
    cfg = harness.load_json("configs", "granite_4_0_h_small.json")
    ref = harness.load_module("reference", "granite_4_0_h_small.py")
    size = lambda index, prefix: sum(
        math.prod(s) for k, s in ref.layer_shapes(cfg, index).items()
        if k.startswith(prefix))
    assert size(0, "mixer_") == 26_359_136
    assert size(5, "mixer_") == 10_485_760
    assert size(0, "moe_") == 104_103_936
    assert size(0, "") == 130_471_264 and size(5, "") == 114_597_888
    total = sum(size(i, "") for i in range(10)) + 4096 + 12_544 * 4096
    assert total == 1_340_223_584
    assert total * 6 / 2 ** 30 == pytest.approx(7.489, abs=1e-3)
    assert ref.forward_macs(cfg) / 8192 == pytest.approx(621.84e6, rel=1e-4)
    assert 6 * 2 * ref.forward_macs(cfg) / 2 == pytest.approx(30.56e12,
                                                               rel=1e-3)
    held = cfg["experts_held"][1]
    assert 8192 * cfg["num_experts_per_tok"] // cfg["num_local_experts"] \
        == 1137
    assert 8192 * cfg["num_experts_per_tok"] * held \
        // cfg["num_local_experts"] == 10_240
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in spec["configs"]
                 if c["name"] == "granite_4_0_h_small")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source_url"]
