"""The layers' counters are summed over an epoch's steps on the device.

A layer writes a step's counters into its state (`parallel/moe.COUNTERS`,
`attention.SPARSE_COUNTERS`, `ssm_chunk_carry`, `exit_entropy` /
`exit_mass`); `optim/step.make_train_step` adds each to a `<counter>_sum`
leaf the net's `init()` put beside it, and `optim/executor` reads both
once an epoch, as the span `fit.counters`: the last step's gauge as
before, `<counter>_total`, and `<counter>_epoch_mean`, the mean over the
epoch's steps. Held here against K one-step epochs of a twin net, against
the K-step scan, across epochs and checkpoints, and the new readers of
`benchmarks/layer_metrics/` against a registry filled by hand.
"""

import io
import json
import math
import os
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.models.serialize import load_model, save_model
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.layers.attention import (
    MultiHeadAttention, PreNormBlock, PreNormSublayer, SelectiveStateSpace,
)
from deeplearning4j_tpu.observe import (
    MetricsRegistry, get_span_store, set_registry,
)
from deeplearning4j_tpu.ops.sparse_attention import BlockSelection
from deeplearning4j_tpu.optim.step import COUNTER_STEPS, counters_of
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, WIDTH, CLASSES, K = 64, 32, 8, 4
SELECTION = BlockSelection(block_size=8, topk=3, init_blocks=1,
                           window_size=12, kernel_size=4, kernel_stride=2,
                           dense_len=16)
# kind -> the net's two layers in front of its head: a pre-norm block with
# an expert half, a selecting attention alone, a Mamba-2 sublayer
LAYERS = {
    "expert": lambda: PreNormBlock(
        mixer=MultiHeadAttention(causal=True, num_heads=4, num_kv_heads=2),
        ffn=ExpertFeedForward(width=16, held=(0, 4), k=2, n_experts=16,
                              score="sigmoid", n_shared=1)),
    "selecting": lambda: MultiHeadAttention(
        n_out=WIDTH, causal=True, num_heads=4, num_kv_heads=2,
        sparse=SELECTION),
    "state_space": lambda: PreNormSublayer(layer=SelectiveStateSpace(
        num_heads=4, head_dim=8, state_size=8, chunk=16)),
}


def _net(kind):
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(3e-2))
            .list(*[LAYERS[kind]() for _ in range(2)],
                  RnnOutputLayer(n_out=CLASSES, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.recurrent(WIDTH, T)).build())
    return MultiLayerNetwork(conf).init()


def _batches(n=K, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.standard_normal((1, T, WIDTH)).astype(np.float32),
                    np.eye(CLASSES, dtype=np.float32)[
                        rng.integers(0, CLASSES, (1, T))])
            for _ in range(n)]


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


def _series(reg, name, kind="gauge"):
    """{labels' values: value} of the registry's series called `name`."""
    return {tuple(v for _, v in s.labels): s.value for s in reg.series()
            if s.name == name and s.kind == kind}


def _counter_layers(net):
    return {name: counters_of(st) for name, st in net.state_tree.items()
            if counters_of(st)}


# ------------------------------------------------------------ the epoch mean
@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_epoch_mean_is_the_mean_of_the_steps_last_step_gauges(kind, registry):
    """One epoch of K steps against K one-step epochs of a twin net over
    the same batches: every `<c>_epoch_mean` is the mean of the twin's K
    last-step gauges, to float32, and `<c>_total` is their sum."""
    batches = _batches()
    twin, steps = _net(kind), {}
    for ds in batches:
        twin.fit([ds], epochs=1)
        for name, own in _counter_layers(twin).items():
            for c in own:
                steps.setdefault((name, c), []).append(
                    _series(registry, c)[(name,)])
    # a one-step epoch's mean is its step
    for (name, c), values in steps.items():
        assert _series(registry, c + "_epoch_mean")[(name,)] == \
            pytest.approx(values[-1], rel=1e-6)
    net = _net(kind)
    registry.reset()
    net.fit(batches, epochs=1)
    assert steps and len(next(iter(steps.values()))) == K
    for (name, c), values in steps.items():
        assert _series(registry, c)[(name,)] == pytest.approx(
            values[-1], rel=1e-6)
        assert _series(registry, c + "_epoch_mean")[(name,)] == \
            pytest.approx(np.mean(values), rel=1e-6), (name, c)
        assert _series(registry, c + "_total", "counter")[(name,)] == \
            pytest.approx(np.sum(values), rel=1e-6)
        assert _series(registry, "counter_steps_total",
                       "counter")[(name,)] == K


def test_the_counters_move_between_steps():
    """What the twin's K steps read is not one number K times: the means
    above are not held against constants alone."""
    net, seen = _net("state_space"), []
    for ds in _batches():
        net.fit([ds], epochs=1)
        seen.append(float(
            net.state_tree["layer0_prenormsublayer"]["ssm_chunk_carry"]))
    assert len(set(seen)) == K


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_the_fused_scan_sums_what_k_dispatches_sum(kind):
    batches = _batches()
    one, fused = _net(kind), _net(kind)
    one.fit(batches, epochs=1)
    fused.fit(batches, epochs=1, steps_per_dispatch=K)
    assert fused.iteration == one.iteration == K
    for name, own in _counter_layers(one).items():
        a, b = one.state_tree[name], fused.state_tree[name]
        assert int(a[COUNTER_STEPS]) == int(b[COUNTER_STEPS]) == K
        for c in own:
            assert a[c + "_sum"].dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(b[c + "_sum"]),
                                       np.asarray(a[c + "_sum"]), rtol=1e-6)
            assert float(a[c + "_sum"]) > 0.0 or c == "moe_pairs_dropped" \
                or c == "moe_load_min"


def test_two_epochs_read_two_means_and_the_total_is_their_weighted_sum(
        registry):
    net, name = _net("state_space"), "layer0_prenormsublayer"
    net.fit(_batches(K), epochs=1)
    first = _series(registry, "ssm_chunk_carry_epoch_mean")[(name,)]
    net.fit(_batches(2, seed=5), epochs=1)
    second = _series(registry, "ssm_chunk_carry_epoch_mean")[(name,)]
    assert first != second and 0.0 < first < 1.0 and 0.0 < second < 1.0
    total = _series(registry, "ssm_chunk_carry_total", "counter")[(name,)]
    assert total == pytest.approx(K * first + 2 * second, rel=1e-6)
    assert _series(registry, "counter_steps_total",
                   "counter")[(name,)] == K + 2
    st = net.state_tree[name]
    assert int(st[COUNTER_STEPS]) == K + 2
    assert float(st["ssm_chunk_carry_sum"]) == pytest.approx(total, rel=1e-6)


def test_a_vector_counter_keeps_its_shape_and_its_pass_labels(registry):
    """`exit_mass` is a value a pass: so are its sum, its total and its
    epoch mean, under `pass=`; the means over the passes sum to 1 as every
    step's masses do."""
    from deeplearning4j_tpu.zoo import LoopedSandwichTransformer

    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "ouro_2_6b_tiny.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    passes, t = cfg["total_ut_steps"], cfg["input_shape"][0]
    net = MultiLayerNetwork(LoopedSandwichTransformer(
        cfg, timesteps=t).conf()).init()
    head = "layer2_exitgatedoutputlayer"
    assert net.state_tree[head]["exit_mass_sum"].shape == (passes,)
    rng = np.random.default_rng(1)
    net.fit([DataSet(*(rng.integers(0, cfg["vocab_size"], (2, t)).astype(
        np.int32) for _ in range(2))) for _ in range(3)], epochs=1)
    st = net.state_tree[head]
    assert st["exit_mass_sum"].shape == (passes,) and \
        int(st[COUNTER_STEPS]) == 3
    means = _series(registry, "exit_mass_epoch_mean")
    assert set(means) == {(head, str(i + 1)) for i in range(passes)}
    assert sum(means.values()) == pytest.approx(1.0, rel=1e-5)
    np.testing.assert_allclose(
        [means[(head, str(i + 1))] * 3 for i in range(passes)],
        np.asarray(st["exit_mass_sum"]), rtol=1e-6)
    assert _series(registry, "exit_entropy_epoch_mean")[(head,)] == \
        pytest.approx(float(st["exit_entropy_sum"]) / 3, rel=1e-6)


def test_a_graphs_counter_vertex_is_summed_too(registry):
    from deeplearning4j_tpu.models import ComputationGraph

    conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(3e-2))
            .graph_builder().add_inputs("in")
            .add_layer("attn", LAYERS["selecting"](), "in")
            .add_layer("out", RnnOutputLayer(
                n_out=CLASSES, activation="softmax", loss="mcxent"), "attn")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(WIDTH, T)).build())
    net = ComputationGraph(conf).init()
    net.fit(_batches(3), epochs=1)
    st = net.state_tree["attn"]
    assert int(st[COUNTER_STEPS]) == 3
    assert float(st["sparse_blocks_causal_sum"]) == \
        3 * int(st["sparse_blocks_causal"])
    assert _series(registry, "sparse_blocks_kept_epoch_mean")[("attn",)] \
        == pytest.approx(float(st["sparse_blocks_kept_sum"]) / 3, rel=1e-6)


def test_output_and_score_leave_the_sums_alone():
    net, (ds, *_) = _net("expert"), _batches(1)
    net.fit([ds], epochs=1)
    before = jax.tree_util.tree_map(np.asarray, net.state_tree)
    net.output(ds.features)
    net.score(ds)
    after = jax.tree_util.tree_map(np.asarray, net.state_tree)
    assert jax.tree_util.tree_structure(before) == \
        jax.tree_util.tree_structure(after)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert int(after["layer0_prenormblock"][COUNTER_STEPS]) == 1


# --------------------------------------------------------------- checkpoints
def test_a_checkpoint_with_sums_round_trips(tmp_path):
    net = _net("expert")
    net.fit(_batches(), epochs=1)
    save_model(net, tmp_path / "net.zip")
    back = load_model(tmp_path / "net.zip")
    for name, own in _counter_layers(net).items():
        assert int(back.state_tree[name][COUNTER_STEPS]) == K
        for c in own:
            assert float(back.state_tree[name][c + "_sum"]) == \
                float(net.state_tree[name][c + "_sum"])
    back.fit(_batches(1), epochs=1)      # and the step takes the tree
    assert int(back.state_tree["layer0_prenormblock"][COUNTER_STEPS]) == K + 1


def test_a_checkpoint_written_without_sums_starts_them_at_zero(tmp_path):
    """A state tree saved before the sums existed: the file lacks their
    keys and the net's template fills them."""
    net = _net("expert")
    net.fit(_batches(), epochs=1)
    save_model(net, tmp_path / "new.zip")
    with zipfile.ZipFile(tmp_path / "new.zip") as src, \
            zipfile.ZipFile(tmp_path / "old.zip", "w") as dst:
        for item in src.namelist():
            data = src.read(item)
            if item == "netState.npz":
                flat = np.load(io.BytesIO(data))
                kept = {k: flat[k] for k in flat.files
                        if not k.endswith(("_sum", COUNTER_STEPS))}
                assert len(kept) < len(flat.files)
                buf = io.BytesIO()
                np.savez(buf, **kept)
                data = buf.getvalue()
            dst.writestr(item, data)
    back = load_model(tmp_path / "old.zip")
    st = back.state_tree["layer0_prenormblock"]
    assert int(st["moe_pairs_routed"]) == 2 * T       # the file's
    assert int(st[COUNTER_STEPS]) == 0 and \
        float(st["moe_pairs_routed_sum"]) == 0.0
    back.fit(_batches(1), epochs=1)
    st = back.state_tree["layer0_prenormblock"]
    assert float(st["moe_pairs_routed_sum"]) == 2 * T


# ------------------------------------------------------- a net with no counter
def test_a_net_with_no_counter_layer_steps_with_no_sum_leaf():
    conf = (NeuralNetConfiguration.builder().seed(3).list(
        DenseLayer(n_in=WIDTH, n_out=WIDTH, activation="relu"),
        RnnOutputLayer(n_out=CLASSES, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(WIDTH, T)).build())
    net = MultiLayerNetwork(conf).init()
    ds = _batches(1)[0]
    out = jax.eval_shape(
        net.make_step_fn(), net.params_tree, net.updater_state,
        net.state_tree, jnp.int32(0), jnp.asarray(ds.features),
        jnp.asarray(ds.labels), None, None, jax.random.PRNGKey(0))
    keys = {k for st in out[2].values() for k in st}
    assert not {k for k in keys if k.endswith("_sum")} \
        and COUNTER_STEPS not in keys
    assert jax.tree_util.tree_structure(out[2]) == \
        jax.tree_util.tree_structure(net.state_tree)


def test_a_counter_layers_own_state_is_what_it_was():
    """The sums are the net's: a layer's `init_params` and `apply` hand
    out the step's counters alone."""
    from deeplearning4j_tpu.parallel import moe

    layer = ExpertFeedForward(n_in=WIDTH, width=16, held=(0, 4), k=2,
                              n_experts=16, weight_init="xavier")
    params, state = layer.init_params(jax.random.PRNGKey(0), None)
    assert set(state) == set(moe.COUNTERS)
    _, new = layer.apply(params, jnp.ones((1, T, WIDTH)), state=state)
    assert set(new) == set(moe.COUNTERS)


# -------------------------------------------------------------------- the span
def test_fit_counters_is_one_span_an_epoch_after_the_sync():
    net, store = _net("selecting"), get_span_store()
    n0 = store.count
    net.fit(_batches(2), epochs=3)
    events = store.events(n0)
    epochs = [e for e in events if e["name"] == "fit.epoch"]
    assert len(epochs) == 3
    for epoch in epochs:
        kids = [e for e in events if e["parent_id"] == epoch["span_id"]]
        assert [e["name"] for e in kids[-2:]] == ["fit.epoch_sync",
                                                  "fit.counters"]
        assert [e["name"] for e in kids].count("fit.counters") == 1
        assert kids[-2]["end_ns"] <= kids[-1]["start_ns"]
        # two selecting layers: a counter, its sum and the steps of each
        assert kids[-1]["attrs"] == {"layers": 2, "values": 2 * 5}


# ----------------------------------------------------------------- the readers
def _reader(name):
    from benchmarks import harness

    return harness.load_module("layer_metrics", name + ".py")


RUN = {"tokens_per_item": 100, "global_batch": 2, "chips": 1, "steps": 7}
# reader -> (the gauges it reads, two layers' values each; its value)
READERS = {
    "moe_held_pair_share_mean.train": (
        {"moe_pairs_held_epoch_mean": (10.0, 30.0),
         "moe_pairs_routed_epoch_mean": (100.0, 100.0)}, 20.0),
    "moe_pairs_dropped_total.train": (
        {"moe_pairs_dropped_epoch_mean": (0.5, 1.5)}, 14.0),
    "moe_rows_visited_share_mean.train": (
        {"moe_rows_visited_epoch_mean": (64.0, 32.0),
         "moe_rows_tier_epoch_mean": (128.0, 256.0)}, 25.0),
    "moe_rows_gathered_share_mean.train": (
        {"moe_rows_gathered_epoch_mean": (96.0, 96.0),
         "moe_rows_tier_epoch_mean": (128.0, 256.0)}, 50.0),
    "moe_token_reach_share_mean.train": (
        {"moe_tokens_held_epoch_mean": (50.0, 30.0)}, 20.0),
    "sparse_kept_block_share_mean.train": (
        {"sparse_blocks_kept_epoch_mean": (30.0, 50.0),
         "sparse_blocks_causal_epoch_mean": (100.0, 100.0)}, 40.0),
    "ssm_chunk_carry_share_mean.train": (
        {"ssm_chunk_carry_epoch_mean": (0.25, 0.75)}, 50.0),
    "exit_entropy_share_mean.train": (
        {"exit_entropy_epoch_mean": (math.log(4.0) / 2, math.log(4.0) / 2),
         "loop_passes": (4.0,)}, 50.0),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_nothing_from_a_bare_program_and_the_mean_from_ours(
        name, registry):
    gauges, want = READERS[name]
    facts = {"run": RUN, "spans": []}
    assert _reader(name).read(facts) is None
    # a program before the sums: the last-step gauges alone
    for gauge in gauges:
        if gauge.endswith("_epoch_mean"):
            registry.gauge(gauge[:-len("_epoch_mean")], layer="a").set(1.0)
    assert _reader(name).read(facts) is None
    for gauge, values in gauges.items():
        for i, value in enumerate(values):
            registry.gauge(gauge, layer=f"layer{i}").set(value)
    assert _reader(name).read(facts) == pytest.approx(want)


def test_counters_publish_ms_reads_the_windows_span():
    reader = _reader("counters_publish_ms.train")
    span = lambda i, name, a, b: {
        "span_id": i, "parent_id": 1, "name": name, "start_ns": a,
        "end_ns": b, "attrs": {}}
    spans = [span(2, "fit.epoch_sync", 0, 5_000_000)]
    assert reader.read({"spans": spans}) is None
    assert reader.read({"spans": None}) is None
    spans.append(span(3, "fit.counters", 5_000_000, 7_500_000))
    assert reader.read({"spans": spans}) == pytest.approx(2.5)


def test_every_new_metric_is_listed_with_its_cells_and_has_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        spec = json.load(fh)
    listed = {m["name"]: m for m in spec["per_layer"]}
    cells = {c["name"] for c in spec["workloads"]}
    for name in [*READERS, "counters_publish_ms.train"]:
        assert set(listed[name]["workloads"]) <= cells
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert len(listed["counters_publish_ms.train"]["workloads"]) == 6
