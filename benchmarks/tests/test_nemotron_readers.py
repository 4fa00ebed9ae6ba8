"""`nemotron_3_super`'s thirteen readers on tables made by hand and on a
program without their scopes or gauges, and the configuration's arithmetic
from its file against a count by hand. CPU, no device number."""

import math

import pytest

from benchmarks import harness, kernel_counts

TRACE_READERS = ("latent_moe_time_share", "latent_moe_routed_time_share",
                 "latent_moe_projection_time_share", "grouped_ssm_time_share",
                 "grouped_ssm_mixer_time_share", "mtp_time_share",
                 "gqa_attention_roofline", "trunk_embedding_time_share")
GAUGE_READERS = ("latent_moe_held_pair_share", "latent_moe_pairs_dropped",
                 "latent_moe_rows_visited_share",
                 "latent_moe_rows_gathered_share",
                 "grouped_ssm_chunk_carry_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _facts(share=0.4):
    """One step's table: an `M`, an `E` and a `*` sublayer and the head's
    `loss` scope with the prediction module inside it, whose attention and
    expert sublayers open the same inner scopes as the trunk's. The flash
    kernels run twice a step (trunk and module), at `share` of the bf16
    peak for 4 heads of 128 over one KV head."""
    row = lambda s, n: {"s": s, "n": n, "flops": 0.0, "hbm_bytes": 0.0}
    moe = lambda k: {"router": row(0.02 * k, 8), "latent_down": row(0.03 * k, 3),
                     "dispatch": row(0.05 * k, 8),
                     "experts_held": row(0.04 * k, 12),
                     "combine": row(0.01 * k, 8),
                     "latent_up": row(0.03 * k, 3),
                     "shared_expert": row(0.22 * k, 8)}
    calls = kernel_counts.attention_calls(
        "flash_attention", batch=1, t=8192, heads=4, kv_heads=1,
        head_dim=128)
    kernels = {name: row(ops / (share * PEAKS["bf16_flops_per_s"]), 1)
               for name, (ops, _) in calls.items()}
    scopes = {
        "layer0_embeddingsequencelayer": {"s": 0.05, "n": 4},
        # `ssm_core`, `ssm_conv` and `ssm_gate_norm` lie inside `ssm_mixer`
        "layer1_prenormsublayer": {"s": 0.5, "n": 10, "inner": {
            "ssm_mixer": row(0.45, 60), "ssm_conv": row(0.05, 6),
            "ssm_core": row(0.25, 40), "ssm_gate_norm": row(0.04, 6)}},
        "layer2_prenormsublayer": {"s": 1.0, "n": 10, "inner": moe(2)},
        "layer8_prenormsublayer": {"s": 0.2, "n": 10, "inner": {
            "attention_core": row(0.1, 3), **kernels}},
        "layer12_multitokenoutputlayer/loss": {"s": 0.8, "n": 90, "inner": {
            "mtp": row(0.6, 70), "mtp_layer0": row(0.1, 10),
            "mtp_layer1": row(0.4, 50), "attention_core": row(0.1, 3),
            **kernels, **moe(1)}},
        "updater": {"s": 0.0, "n": 30}}
    return {"trace": {}, "scopes": scopes, "run": {
        "peaks": PEAKS, "workload": "nemotron_3_super_fit",
        "global_batch": 1, "chips": 1, "tokens_per_item": 8192}}


def _read(name, facts):
    return harness.load_module("layer_metrics", name + ".train.py").read(facts)


def test_readers_on_a_table_made_by_hand():
    facts = _facts()
    total = 2.55
    assert _read("latent_moe_time_share", facts) == pytest.approx(
        100.0 * 0.40 * 3 / total)
    assert _read("latent_moe_routed_time_share", facts) == pytest.approx(
        100.0 * 0.10 * 3 / total)
    assert _read("latent_moe_projection_time_share", facts) \
        == pytest.approx(100.0 * 0.06 * 3 / total)
    assert _read("grouped_ssm_time_share", facts) == pytest.approx(
        100.0 * 0.25 / total)
    # the mixer's share holds the scan's, which is not counted twice
    assert _read("grouped_ssm_mixer_time_share", facts) == pytest.approx(
        100.0 * 0.45 / total)
    assert _read("mtp_time_share", facts) == pytest.approx(
        100.0 * 0.6 / total)
    # the trunk's lookup alone: the labels' lies under `mtp`
    assert _read("trunk_embedding_time_share", facts) == pytest.approx(
        100.0 * 0.05 / total)
    # both layers' calls, at the heads HELD (4 over 1), not the published 32
    assert _read("gqa_attention_roofline", facts) == pytest.approx(40.0)


def test_a_roofline_over_105_is_refused():
    with pytest.raises(ValueError, match="roof"):
        _read("gqa_attention_roofline", _facts(share=1.2))


def test_readers_find_nothing_where_there_is_nothing():
    """An older program (no such scope, no such gauge), an untraced run, a
    model with experts at the model's width and no latent: no value,
    nothing raised."""
    from deeplearning4j_tpu.observe import get_registry

    untraced = {"trace": None, "scopes": None, "run": {"peaks": None}}
    other = _facts()
    for name in ("layer0_embeddingsequencelayer", "layer1_prenormsublayer",
                 "layer8_prenormsublayer"):
        del other["scopes"][name]
    head = other["scopes"]["layer12_multitokenoutputlayer/loss"]["inner"]
    for scopes in (other["scopes"]["layer2_prenormsublayer"]["inner"], head):
        del scopes["latent_down"], scopes["latent_up"]
    for name in ("mtp", "flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkdv"):
        del head[name]
    for name in TRACE_READERS:
        assert _read(name, untraced) is None
        assert _read(name, other) is None
    # a cell that is not in BENCHMARK.json (a test's) has no heads held
    elsewhere = _facts()
    elsewhere["run"]["workload"] = "tiny_1"
    assert _read("gqa_attention_roofline", elsewhere) is None
    registry = get_registry()
    registry.reset()
    run = {"run": {"global_batch": 1, "chips": 1}}
    for name in GAUGE_READERS:
        assert _read(name, run) is None
    # the gauges as the program publishes them, a label set a layer
    for layer, held in (("e1", 5632), ("head", 11264)):
        registry.gauge("moe_pairs_held", layer=layer).set(held)
        registry.gauge("moe_pairs_routed", layer=layer).set(180224)
        registry.gauge("moe_pairs_dropped", layer=layer).set(0)
        registry.gauge("moe_rows_visited", layer=layer).set(8192)
        registry.gauge("moe_rows_gathered", layer=layer).set(4096)
        registry.gauge("moe_rows_tier", layer=layer).set(131072)
    registry.gauge("ssm_chunk_carry", layer="m1").set(0.25)
    registry.gauge("ssm_chunk_carry", layer="m2").set(0.75)
    assert _read("latent_moe_held_pair_share", run) == pytest.approx(
        1.5 * 3.125)
    assert _read("latent_moe_pairs_dropped", run) == 0
    assert _read("latent_moe_rows_visited_share", run) == pytest.approx(6.25)
    assert _read("latent_moe_rows_gathered_share", run) == pytest.approx(
        3.125)
    assert _read("grouped_ssm_chunk_carry_share", run) == pytest.approx(50.0)
    registry.reset()


def test_nemotron_arithmetic_from_its_configuration():
    """The cut's parameters as ISSUE 50's table has them, what a token
    costs by a count made by hand, and what a held expert sees."""
    cfg = harness.load_json("configs", "nemotron_3_super.json")
    ref = harness.load_module("reference", "nemotron_3_super.py")
    size = lambda shapes: sum(math.prod(s) for s in shapes.values())
    d = 4096
    mamba = size(ref.sublayer_shapes(cfg, "M"))
    # in_proj 4096 x (1024 z + 1024 x + 128 B + 128 C + 16 dt), out_proj,
    # conv 5 x 1280, dt_bias, A_log, D, the gated norm, the layer's norm
    assert mamba == d * 2320 + 1024 * d + 5 * 1280 + 3 * 16 + 1024 + d \
        == 13_708_592
    attention = size(ref.sublayer_shapes(cfg, "*"))
    assert attention == d * 512 * 2 + d * 128 * 2 + d == 5_246_976
    experts = size(ref.sublayer_shapes(cfg, "E"))
    assert experts == (d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376
                       + 16 * 2 * 1024 * 2688 + d) == 142_610_944
    head = size(ref.head_shapes(cfg))
    assert head == (16_384 * d + 2 * d * d + 4 * d
                    + attention + experts) == 248_537_600
    total = 5 * mamba + attention + 5 * experts + 16_384 * d + head
    assert total == 1_102_491_120
    assert total * 6 / 2 ** 30 == pytest.approx(6.161, abs=1e-3)
    # forward multiply-adds a token, by hand
    t = 8192
    m = d * 2320 + 1024 * d + 4 * 1280 + 2 * 1024 * 128 + 1024
    a = d * 128 * (2 * 4 + 2 * 1) + 2 * 4 * 128 * (t + 1) / 2
    e = (d * 512 + 2 * d * 1024 + 2 * d * 5376
         + 22 * 16 / 512 * 2 * 1024 * 2688)
    per_token = 5 * m + a + 5 * e + d * 16_384 \
        + 2 * d * d + a + e + d * 16_384
    assert ref.forward_macs(cfg) / t == pytest.approx(per_token, rel=1e-6)
    assert per_token == pytest.approx(606.34e6, rel=1e-4)
    assert 6 * ref.forward_macs(cfg) == pytest.approx(29.80e12, rel=1e-3)
    # 22 of 512 a token: a held expert's load here and deployed
    held = cfg["experts_held"][1]
    assert t * cfg["num_experts_per_tok"] // cfg["n_routed_experts"] == 352
    assert t * cfg["num_experts_per_tok"] * held \
        // cfg["n_routed_experts"] == 5632
    assert t * min(cfg["num_experts_per_tok"], held) == 131_072
    spec = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == "nemotron_3_super")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source_url"]
    catalog = {k: v for k, v in cfg.items() if k in (
        "hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
        "n_groups", "moe_latent_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "n_routed_experts", "vocab_size", "chunk_size", "conv_kernel")}
    assert catalog == {
        "hidden_size": 4096, "head_dim": 128, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "moe_latent_size": 1024,
        "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376,
        "num_experts_per_tok": 22, "n_routed_experts": 512,
        "vocab_size": 131072, "chunk_size": 128, "conv_kernel": 4}
