"""The counts `sparse_attention_roofline.train` multiplies the trace's
calls by, `minicpm_sala`'s new readers on tables made by hand and on a
program without their scopes or gauges, and the configuration's arithmetic
from its file. CPU, no device number."""

import json
import os

import pytest

from benchmarks import harness, sparse_counts

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("linear_attention_time_share", "sparse_select_time_share",
           "sparse_attention_time_share", "sparse_attention_roofline")


def test_pairs_of_the_blocks_kept():
    # under topk blocks everything reachable is kept: the causal pairs
    assert sparse_counts.kept_pairs(256, 64, 64) == 256 * 257 // 2
    # 3 blocks of 4 keys, 2 kept: tokens 0 to 7 read all they reach, tokens
    # 8 to 11 one whole block and their own up to themselves
    assert sparse_counts.kept_pairs(12, 4, 2) == 36 + (5 + 6 + 7 + 8)
    assert sparse_counts.kept_pairs(16384, 64, 64) == 16384 * 3560.5


def test_calls_count_products_over_the_kept_pairs():
    calls = sparse_counts.sparse_attention_calls(
        batch=1, t=16384, heads=32, kv_heads=2, head_dim=128, block_size=64,
        topk=64)
    product = 2 * 32 * 128 * 58_335_232
    assert calls["sparse_attention_fwd"][0] == 2 * product
    assert calls["sparse_attention_bwd_dq"][0] == 3 * product
    assert calls["sparse_attention_bwd_dkdv"][0] == 4 * product
    wide, narrow = 16384 * 32 * 128 * 2, 16384 * 2 * 128 * 2
    mask, stats = 2 * 16384 * 256 * 2, 16384 * 32 * 128 * 4
    assert calls["sparse_attention_fwd"][1] \
        == 2 * wide + 2 * narrow + mask + stats


def _facts(seconds, workload="minicpm_sala_fit"):
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    scopes = {"layer1_prenormblock": {"s": 1.0, "n": 10, "inner": {
        "sparse_select": row(0.05, 40),
        "sparse_attention_core": row(3.5 * seconds, 9),
        "sparse_attention_fwd": row(seconds, 1),
        "sparse_attention_bwd_dq": row(seconds, 1),
        "sparse_attention_bwd_dkdv": row(1.5 * seconds, 1)}},
        "layer2_prenormblock": {"s": 1.0, "n": 3, "inner": {
            "linear_attention_core": row(0.3, 30)}}}
    return {"trace": {}, "scopes": scopes, "run": {
        "peaks": PEAKS, "workload": workload, "global_batch": 1, "chips": 1}}


def _read(name, facts):
    return harness.load_module("layer_metrics", name + ".train.py").read(facts)


def test_readers_on_a_table_made_by_hand():
    facts = _facts(0.1)
    assert _read("linear_attention_time_share", facts) == pytest.approx(15.0)
    assert _read("sparse_select_time_share", facts) == pytest.approx(2.5)
    assert _read("sparse_attention_time_share", facts) == pytest.approx(17.5)
    calls = sparse_counts.sparse_attention_calls(
        batch=1, t=16384, heads=32, kv_heads=2, head_dim=128, block_size=64,
        topk=64)
    want = 100 * sum(ops for ops, _ in calls.values()) / 0.35 / 197e12
    assert _read("sparse_attention_roofline", facts) == pytest.approx(want)
    # a kernel that cannot have run that fast is refused, not clipped
    with pytest.raises(ValueError):
        _read("sparse_attention_roofline", _facts(0.001))


def test_readers_find_nothing_where_there_is_nothing():
    """An older program (no such scope, no such gauge), an untraced run, a
    cell that is not in `BENCHMARK.json`: no value, and nothing raised."""
    from deeplearning4j_tpu.observe import get_registry

    untraced = {"trace": None, "scopes": None,
                "run": {"peaks": None, "workload": "minicpm_sala_fit"}}
    bare = {"trace": {}, "scopes": {"layer1": {"s": 1.0, "n": 1}},
            "run": {"peaks": PEAKS, "workload": "minicpm_sala_fit",
                    "global_batch": 1, "chips": 1}}
    for name in READERS:
        assert _read(name, untraced) is None
        assert _read(name, bare) is None
    assert _read("sparse_attention_roofline", _facts(0.1, "tiny_1")) is None
    assert _read("sparse_attention_roofline",
                 _facts(0.1, "trinity_large_fit")) is None
    get_registry().reset()
    assert _read("sparse_kept_block_share", {}) is None
    get_registry().gauge("sparse_blocks_kept", layer="a").set(437)
    get_registry().gauge("sparse_blocks_causal", layer="a").set(1000)
    assert _read("sparse_kept_block_share", {}) == pytest.approx(43.7)
    get_registry().reset()


def test_minicpm_sala_arithmetic_from_its_configuration():
    cfg = harness.load_json("configs", "minicpm_sala.json")
    ref = harness.load_module("reference", "minicpm_sala.py")
    per_token = ref.forward_macs(cfg) / cfg["input_shape"][0]
    assert per_token == pytest.approx(1219.0e6, rel=1e-3)
    assert ref.kept_pairs(16384, ref.sparse_sizes(cfg)) \
        == sparse_counts.kept_pairs(16384, 64, 64)
    # every number of the catalog's row is in the file under its key, but
    # for the keys `reduced`
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "MiniCPM-SALA")
    assert cfg["source_url"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types",
                              "vocabulary_held"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["mixer_types"] == row["config"]["mixer_types"][:4]
    assert cfg["vocabulary_held"] * 4 == cfg["vocab_size"]
