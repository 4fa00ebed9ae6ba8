"""The counts a `<kernel>_roofline` reader multiplies the trace's calls
by, the new readers on tables made by hand, and `trinity_large`'s
arithmetic from its configuration. CPU, no device number."""

import pytest

from benchmarks import harness, kernel_counts

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_pairs_of_the_causal_band():
    assert kernel_counts.causal_pairs(8192, 4096) == 25_167_872
    assert kernel_counts.causal_pairs(8192) == 33_558_528
    assert kernel_counts.causal_pairs(8, 100) == 36
    assert kernel_counts.causal_pairs(4, 1) == 4


def test_attention_calls_count_products_over_the_band():
    calls = kernel_counts.attention_calls(
        "banded_attention", batch=1, t=8192, heads=48, kv_heads=8,
        head_dim=128, window=4096)
    product = 2 * 48 * 128 * 25_167_872
    assert calls["banded_attention_fwd"][0] == 2 * product
    assert calls["banded_attention_bwd_dq"][0] == 3 * product
    assert calls["banded_attention_bwd_dkdv"][0] == 4 * product
    wide, narrow = 8192 * 48 * 128 * 2, 8192 * 8 * 128 * 2
    assert calls["banded_attention_fwd"][1] == 2 * wide + 2 * narrow


def _scopes(seconds):
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    return {"layer1": {"s": 1.0, "n": 10, "inner": {
        "k_fwd": row(seconds, 2), "k_bwd_dq": row(2 * seconds, 1),
        "attention_core": row(0.25, 5)}},
        "layer2": {"s": 1.0, "n": 3, "inner": {"router": row(0.1, 1),
                                               "combine": row(0.1, 1)}}}


def test_family_share_sums_calls_and_seconds():
    calls = {"k_fwd": (1e12, 1e6), "k_bwd_dq": (3e12, 1e6),
             "k_bwd_dkdv": (4e12, 1e6)}           # the last never ran
    share = kernel_counts.family_share(_scopes(0.02), calls, PEAKS)
    assert share == pytest.approx(100 * (2e12 + 3e12) / 0.06 / 197e12)
    assert kernel_counts.family_share({"layer1": {"s": 1.0, "n": 1}},
                                      calls, PEAKS) is None
    with pytest.raises(ValueError):
        kernel_counts.family_share(_scopes(0.001), calls, PEAKS)


def test_inner_share_and_the_readers_without_a_trace():
    assert kernel_counts.inner_share(_scopes(0.02), ["attention_core"]) \
        == pytest.approx(12.5)
    assert kernel_counts.inner_share(_scopes(0.02), ["router", "combine",
                                                     "dispatch"]) \
        == pytest.approx(10.0)
    assert kernel_counts.inner_share(_scopes(0.02), ["absent"]) is None
    facts = {"trace": None, "scopes": None,
             "run": {"peaks": None, "workload": "trinity_large_fit"}}
    for name in ("attention_time_share", "moe_time_share",
                 "flash_attention_roofline", "banded_attention_roofline"):
        reader = harness.load_module("layer_metrics", name + ".train.py")
        assert reader.read(facts) is None


def test_program_counters_read_nothing_from_a_program_without_them():
    from deeplearning4j_tpu.observe import get_registry

    get_registry().reset()
    for name in ("moe_pairs_dropped", "moe_held_pair_share"):
        reader = harness.load_module("layer_metrics", name + ".train.py")
        assert reader.read({}) is None
    for layer, held in (("a", 30), ("b", 34)):
        get_registry().gauge("moe_pairs_held", layer=layer).set(held)
        get_registry().gauge("moe_pairs_routed", layer=layer).set(1024)
        get_registry().gauge("moe_pairs_dropped", layer=layer).set(0)
    assert harness.load_module(
        "layer_metrics", "moe_pairs_dropped.train.py").read({}) == 0
    assert harness.load_module(
        "layer_metrics", "moe_held_pair_share.train.py").read({}) \
        == pytest.approx(3.125)
    get_registry().reset()


def test_trinity_large_arithmetic_from_its_configuration():
    cfg = harness.load_json("configs", "trinity_large.json")
    ref = harness.load_module("reference", "trinity_large.py")
    per_token = ref.forward_macs(cfg) / cfg["input_shape"][0]
    assert per_token == pytest.approx(836.5e6, rel=1e-3)
    assert kernel_counts.cell_config({"workload": "trinity_large_fit"})[
        "sliding_window"] == 4096
    assert kernel_counts.cell_config({"workload": "no_such_cell"}) is None
    # every published number is the catalog's, but for the keys `reduced`
    for key, value in {"hidden_size": 3072, "num_attention_heads": 48,
                       "num_key_value_heads": 8, "head_dim": 128,
                       "sliding_window": 4096, "moe_intermediate_size": 3072,
                       "intermediate_size": 12288, "num_experts": 256,
                       "num_experts_per_tok": 4, "route_scale": 2.448,
                       "vocab_size": 200192}.items():
        assert cfg[key] == value
