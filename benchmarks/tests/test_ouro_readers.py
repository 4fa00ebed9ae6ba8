"""`ouro_2_6b`'s four readers on tables made by hand and on a program
without their scopes or gauges, and the configuration's arithmetic from
its file. CPU, no device number."""

import math

import pytest

from benchmarks import harness, kernel_counts

TRACE_READERS = ("looped_attention_time_share", "exit_loss_time_share",
                 "looped_attention_roofline")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _facts(share=0.5):
    """One step's table: the loop layer's scope with its blocks, the
    attention core and the three flash kernels inside (24 calls each, at
    `share` of the bf16 peak), and the head's `loss` scope with the exits'
    and the gate's."""
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    calls = kernel_counts.attention_calls(
        "flash_attention", batch=1, t=8192, heads=16, kv_heads=16,
        head_dim=128)
    kernels = {name: row(24 * ops / (share * PEAKS["bf16_flops_per_s"]), 24)
               for name, (ops, _) in calls.items()}
    core = sum(r["s"] for r in kernels.values())
    scopes = {
        "layer1_loopedstack": {"s": 1.0, "n": 900, "inner": {
            "block0": row(0.12, 100), "attention_core": row(core, 72),
            "pass_norm": row(0.004, 8), **kernels}},
        "layer2_exitgatedoutputlayer/loss": {"s": 0.2, "n": 60, "inner": {
            "exit_loss": row(0.17, 40), "exit_gate": row(0.01, 12)}},
        "updater": {"s": 0.05, "n": 30}}
    return {"trace": {}, "scopes": scopes, "run": {
        "peaks": PEAKS, "workload": "ouro_2_6b_fit", "global_batch": 1,
        "chips": 1, "tokens_per_item": 8192}}, core


def _read(name, facts):
    return harness.load_module("layer_metrics", name + ".train.py").read(facts)


def test_readers_on_a_table_made_by_hand():
    facts, core = _facts()
    assert _read("looped_attention_time_share", facts) == pytest.approx(
        100.0 * core / 1.25)
    assert _read("exit_loss_time_share", facts) == pytest.approx(
        100.0 * 0.18 / 1.25)
    assert _read("looped_attention_roofline", facts) == pytest.approx(50.0)


def test_a_roofline_over_105_is_refused():
    facts, _ = _facts(share=1.2)
    with pytest.raises(ValueError, match="roof"):
        _read("looped_attention_roofline", facts)


def test_readers_find_nothing_where_there_is_nothing():
    """An older program (no such scope, no such gauge), an untraced run, a
    run of another cell's flash kernels: no value, nothing raised."""
    from deeplearning4j_tpu.observe import get_registry

    untraced = {"trace": None, "scopes": None,
                "run": {"peaks": None, "workload": "ouro_2_6b_fit"}}
    other, _ = _facts()
    other["scopes"] = {"layer3_dense": {"s": 1.0, "n": 4, "inner": {}}}
    for name in TRACE_READERS:
        assert _read(name, untraced) is None
        assert _read(name, other) is None
    registry = get_registry()
    registry.reset()
    assert _read("exit_entropy_share", {}) is None
    # a loop without a gate, and a gate's gauge without the loop's
    registry.gauge("loop_passes", model="MultiLayerNetwork").set(4)
    assert _read("exit_entropy_share", {}) is None
    registry.reset()
    registry.gauge("exit_entropy", layer="head").set(1.0)
    assert _read("exit_entropy_share", {}) is None
    registry.gauge("loop_passes", model="MultiLayerNetwork").set(4)
    assert _read("exit_entropy_share", {}) == pytest.approx(
        100.0 / math.log(4.0))
    # the assumed init: (1/2, 1/4, 1/8, 1/8)
    start = -sum(p * math.log(p) for p in (0.5, 0.25, 0.125, 0.125))
    registry.gauge("exit_entropy", layer="head").set(start)
    assert _read("exit_entropy_share", {}) == pytest.approx(87.5)
    registry.gauge("exit_entropy", layer="head").set(0.0)
    assert _read("exit_entropy_share", {}) == 0.0
    registry.reset()


def test_ouro_arithmetic_from_its_configuration():
    """The cut's parameters as ISSUE 46 has them, what a sequence costs,
    and the benchmark's entries."""
    cfg = harness.load_json("configs", "ouro_2_6b.json")
    ref = harness.load_module("reference", "ouro_2_6b.py")
    block = sum(math.prod(s) for s in ref._block_shapes(cfg).values())
    assert block == 51_388_416
    total = 8 * block + 2 * 49_152 * 2048 + 2048 + 2048 + 1
    assert total == 612_438_017 == cfg["parameters"]["held_here"]
    assert total * 6 / 2 ** 30 == pytest.approx(3.422, abs=1e-3)
    assert 48 * block + 2 * 49_152 * 2048 + 4097 \
        == cfg["published"]["parameters"] == 2_667_974_657
    assert 6 * ref.forward_macs(cfg) == pytest.approx(127.0e12, rel=1e-3)
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    assert entry["source"] == cfg["source_url"]
    cell = next(c for c in spec["workloads"] if c["name"] == "ouro_2_6b_fit")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b", "fit_stream", 1)
    mine = [m for m in spec["per_layer"]
            if m.get("workloads") == ["ouro_2_6b_fit"]]
    assert sorted(m["name"] for m in mine) == sorted(
        name + ".train" for name in TRACE_READERS + ("exit_entropy_share",))
    assert all(m["moves"] == "train_items_per_s"
               and m["layer"] == "layers and kernels" for m in mine)


def test_ouro_forward_macs_count_the_passes():
    # 21.166 T a sequence of 8,192: 8 blocks' products 13.469 T and their
    # attention 4.399 T over four passes, the head 3.299 T over four exits
    # (blocks 63.6%, attention 20.8%, the exits 15.6%), the gate 50 M.
    ref = harness.load_module("reference", "ouro_2_6b.py")
    cfg = harness.load_json("configs", "ouro_2_6b.json")
    blocks = 4 * 8 * 8192 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    attention = 4 * 8 * 2 * 16 * 128 * (8192 * 8193 // 2)
    head = 4 * 8192 * 2048 * 49152
    assert (blocks, attention, head) == (
        13_469_017_440_256, 4_398_583_382_016, 3_298_534_883_328)
    total = ref.forward_macs(cfg)
    assert total == blocks + attention + head + 3 * 8192 * 2048
    assert abs(total / 1e12 - 21.166) < 0.001
    # one pass: a quarter of the block, attention and head terms, no gate
    once = ref.forward_macs({**cfg, "total_ut_steps": 1})
    assert once == (blocks + attention + head) // 4
