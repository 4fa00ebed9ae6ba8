"""The counts `latent_attention_roofline.train` multiplies the trace's
calls by, against a hand count at a small shape; `deepseek_v2`'s new
readers on tables made by hand and on a program without their scopes or
gauges; and the configuration's arithmetic from its file. CPU, no device
number."""

import json
import os

import pytest

from benchmarks import harness, latent_counts

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TRACE_READERS = ("latent_attention_time_share",
                 "latent_projection_time_share", "latent_attention_roofline",
                 "grouped_moe_time_share")
GAUGE_READERS = ("grouped_moe_held_pair_share", "grouped_moe_pairs_dropped",
                 "grouped_moe_token_reach_share")
CELL = dict(batch=1, t=8192, heads=32, nope=128, rope=64, value=128)


def test_calls_against_a_hand_count_at_a_small_shape():
    # 4 tokens, 2 heads, a query of 3 + 2 lanes against values of 5: 10
    # causal pairs a head, 20 in all
    calls = latent_counts.latent_attention_calls(
        batch=1, t=4, heads=2, nope=3, rope=2, value=5, itemsize=2)
    assert calls["latent_attention_fwd"][0] == 2 * (5 + 5) * 20
    assert calls["latent_attention_bwd_dq"][0] == 2 * (5 + 5 + 5) * 20
    assert calls["latent_attention_bwd_dkdv"][0] == 2 * (5 + 5 + 5 + 5) * 20
    q, k_nope, v, k_rope = 4 * 2 * 5 * 2, 4 * 2 * 3 * 2, 4 * 2 * 5 * 2, 4 * 2 * 2
    stats = 4 * 2 * 128 * 4
    # q, k_nope, the rope key ONCE, v in; o out
    assert calls["latent_attention_fwd"][1] == q + k_nope + k_rope + 2 * v
    # and do in, dq out, both statistics
    assert calls["latent_attention_bwd_dq"][1] \
        == 2 * q + k_nope + k_rope + 2 * v + 2 * stats
    # q, k_nope, rope key, v, do in; dk_nope, the rope key's gradient, dv out
    assert calls["latent_attention_bwd_dkdv"][1] \
        == q + 2 * k_nope + 2 * k_rope + 3 * v + 2 * stats


def test_calls_at_the_cell_s_shape():
    calls = latent_counts.latent_attention_calls(**CELL)
    pairs = 32 * (8192 * 8193 // 2)
    assert calls["latent_attention_fwd"][0] == 2 * (192 + 128) * pairs
    assert calls["latent_attention_bwd_dq"][0] == 2 * (192 + 128 + 192) * pairs
    assert calls["latent_attention_bwd_dkdv"][0] \
        == 2 * (192 + 128 + 128 + 192) * pairs
    # the forward's multiply-adds a token: the 41.9M a layer that make the
    # core 23% of the configuration's 911.4M
    core = calls["latent_attention_fwd"][0] / 2 / 8192
    assert core == pytest.approx(41.95e6, rel=1e-3)
    cfg = harness.load_json("configs", "deepseek_v2.json")
    ref = harness.load_module("reference", "deepseek_v2.py")
    assert 5 * core / (ref.forward_macs(cfg) / 8192) == pytest.approx(
        0.23, abs=0.005)


def _facts(seconds, workload="deepseek_v2_fit"):
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    scopes = {"layer2_prenormblock": {"s": 1.0, "n": 10, "inner": {
        "latent_projections": row(0.2, 40),
        "latent_attention_core": row(3.5 * seconds, 9),
        "latent_attention_fwd": row(seconds, 1),
        "latent_attention_bwd_dq": row(seconds, 1),
        "latent_attention_bwd_dkdv": row(1.5 * seconds, 1),
        "router": row(0.02, 8), "group_select": row(0.01, 4),
        "dispatch": row(0.03, 8), "experts_held": row(0.04, 8),
        "combine": row(0.01, 8), "shared_expert": row(0.1, 8)}},
        "layer1_prenormblock": {"s": 1.0, "n": 3, "inner": {
            "latent_projections": row(0.2, 40), "ffn": row(0.6, 9)}}}
    return {"trace": {}, "scopes": scopes, "run": {
        "peaks": PEAKS, "workload": workload, "global_batch": 1, "chips": 1,
        "tokens_per_item": 8192}}


def _read(name, facts):
    return harness.load_module("layer_metrics", name + ".train.py").read(facts)


def test_readers_on_a_table_made_by_hand():
    facts = _facts(0.02)
    assert _read("latent_attention_time_share", facts) == pytest.approx(3.5)
    assert _read("latent_projection_time_share", facts) == pytest.approx(20.0)
    # `group_select` lies inside `router` and is not counted twice
    assert _read("grouped_moe_time_share", facts) == pytest.approx(10.0)
    calls = latent_counts.latent_attention_calls(**CELL)
    want = 100 * sum(ops for ops, _ in calls.values()) / 0.07 / 197e12
    assert 0 < want < 100
    assert _read("latent_attention_roofline", facts) == pytest.approx(want)


def test_a_share_over_105_raises_and_is_not_clipped():
    # all three kernels in 7 ms: 2.5 PFLOP/s
    with pytest.raises(ValueError, match="of the roof"):
        _read("latent_attention_roofline", _facts(0.002))


def test_readers_find_nothing_where_there_is_nothing():
    """An older program (no such scope, no such gauge), an untraced run, a
    cell that is not in `BENCHMARK.json`, a cell without latent attention
    or without group routing: no value, and nothing raised."""
    from deeplearning4j_tpu.observe import get_registry

    untraced = {"trace": None, "scopes": None,
                "run": {"peaks": None, "workload": "deepseek_v2_fit"}}
    bare = {"trace": {}, "scopes": {"layer1": {"s": 1.0, "n": 1, "inner": {
        "router": {"s": 0.1, "n": 1, "flops": 0.0, "hbm_bytes": 0.0}}}},
        "run": {"peaks": PEAKS, "workload": "deepseek_v2_fit",
                "global_batch": 1, "chips": 1}}
    for name in TRACE_READERS:
        assert _read(name, untraced) is None
        assert _read(name, bare) is None     # flat routing: no group_select
    assert _read("latent_attention_roofline", _facts(0.02, "tiny_1")) is None
    assert _read("latent_attention_roofline",
                 _facts(0.02, "trinity_large_fit")) is None
    run = {"run": {"global_batch": 1, "chips": 1, "tokens_per_item": 8192}}
    registry = get_registry()
    registry.reset()
    for name in GAUGE_READERS:
        assert _read(name, run) is None
    # a flat router's gauges alone (`trinity_large`'s) are not these
    # metrics'
    registry.gauge("moe_pairs_held", layer="a").set(2458)
    registry.gauge("moe_pairs_routed", layer="a").set(49152)
    registry.gauge("moe_pairs_dropped", layer="a").set(0)
    for name in GAUGE_READERS:
        assert _read(name, run) is None
    registry.gauge("moe_tokens_held", layer="a").set(2048)
    assert _read("grouped_moe_held_pair_share", run) == pytest.approx(
        100 * 2458 / 49152)
    assert _read("grouped_moe_pairs_dropped", run) == 0
    assert _read("grouped_moe_token_reach_share", run) == pytest.approx(25.0)
    registry.reset()


def test_deepseek_v2_arithmetic_from_its_configuration():
    cfg = harness.load_json("configs", "deepseek_v2.json")
    ref = harness.load_module("reference", "deepseek_v2.py")
    assert ref.forward_macs(cfg) / 8192 == pytest.approx(911.4e6, rel=1e-4)
    # every number of the catalog's row is in the file under its key, but
    # for the keys `reduced`
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "DeepSeek-V2")
    assert cfg["source_url"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "heads_held",
                              "experts_held", "vocabulary_held"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 5
    assert cfg["heads_held"] == [0, 32] and cfg["experts_held"] == [0, 8]
    assert cfg["vocabulary_held"] * 8 == cfg["vocab_size"]
    assert cfg["deployment"]["chips_sharing_each_layer"] == 20
