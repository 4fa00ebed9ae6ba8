"""A checkpointed block keeps what only a norm's backward reads.

`SandwichTransformerBlock`, `PreNormBlock`, a sparse `MultiHeadAttention`
and an `ExpertFeedForward` name (`ops/attention.name_block_residual`) the
values their recomputation would make again only to read: a sub-layer's
output in front of a norm, the stream between a pre-norm block's halves, a
block selection, an expert layer's choice and schedule
(`tests/test_expert_schedule.py` has the layer alone). A checkpointed
layer's policy (`models/multilayer._checkpointed`, `KEPT_NAMES`) keeps
them, and the product that made each is dead in the recomputed forward.
Counted in the gradient's jaxpr on the CPU at small widths: nothing here is
a time. `tests/test_remat_attention.py` holds the kernels' pair and the
layers that name nothing.
"""

import importlib
import json
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import RnnOutputLayer
from deeplearning4j_tpu.nn.layers.attention import (
    MultiHeadAttention, PreNormBlock, SandwichTransformerBlock,
)
from deeplearning4j_tpu.observe.registry import get_registry
from deeplearning4j_tpu.ops.sparse_attention import BlockSelection
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward, _row_tiers

# by module path: `ops/__init__` re-exports a function under this name
names = importlib.import_module("deeplearning4j_tpu.ops.attention")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 256 tokens x 2 a token over 2 of 32 experts: three tiers, so the routed
# products sit under a `lax.switch` as they do in the cells with a ladder
T, WIDTH, CLASSES, LAYERS = 256, 32, 8, 2
EXPERTS = dict(n_experts=32, score="sigmoid", n_shared=1)
HEADS = dict(num_heads=4, num_kv_heads=2)
SELECTION = BlockSelection(block_size=8, topk=5, init_blocks=1,
                           window_size=12, kernel_size=4, kernel_stride=2,
                           dense_len=16)


def _mixer(**kw):
    return MultiHeadAttention(causal=True, **HEADS, **kw)


def _sandwich(**kw):
    return SandwichTransformerBlock(**HEADS, **kw)


# name -> (a block, what leaves a block's recomputation once the names are
# kept: grouped products a tier's path, plain products, `top_k` calls,
# sorts)
BLOCKS = {
    # the tier's forward (three grouped products); `Wo`, the shared w2;
    # the router's choice; the schedule's sort and the weights' into row
    # order
    "sandwich_experts": (lambda: _sandwich(
        experts_held=(0, 2), moe_k=2, expert_width=16, **EXPERTS),
                         3, 2, 1, 2),
    # `Wo`, the SwiGLU's w2
    "sandwich_dense": (lambda: _sandwich(ffn_width=64), 0, 2, 0, 0),
    # the mixer's `Wo`; the second half's products were dead already, its
    # choice and its sorts were not
    "prenorm_experts": (lambda: PreNormBlock(
        mixer=_mixer(), ffn=ExpertFeedForward(
            width=16, held=(0, 2), k=2, **EXPERTS)), 0, 1, 1, 2),
    "prenorm_dense": (lambda: PreNormBlock(mixer=_mixer(), ffn_width=64),
                      0, 1, 0, 0),
    # the choice of blocks: its score product and its `top_k`
    "sparse_attention": (lambda: _mixer(n_out=WIDTH, sparse=SELECTION),
                         0, 1, 1, 0),
}
# grouped products a tier's path and a block under the layer's checkpoint:
# three forward, three the tier's own checkpoint remakes, six backward
GROUPED = {"sandwich_experts": 12, "prenorm_experts": 12}
PRIMITIVES = ("ragged_dot", "dot_general", "top_k", "sort")


def _net(kind, checkpointing):
    conf = (NeuralNetConfiguration.builder().seed(3)
            .gradient_checkpointing(checkpointing)
            .list(*[BLOCKS[kind][0]() for _ in range(LAYERS)],
                  RnnOutputLayer(n_out=CLASSES, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.recurrent(WIDTH, T)).build())
    return MultiLayerNetwork(conf).init()


def _batch():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, T, WIDTH), jnp.float32)
    y = jax.nn.one_hot(rng.randint(0, CLASSES, (1, T)), CLASSES,
                       dtype=jnp.float32)
    return x, y


def _loss(net):
    """params -> (loss, the layers' new state), on one seeded batch."""
    x, y = _batch()
    return lambda p: net._loss(p, net.state_tree, x, y, None, None, None)


def _jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _jaxprs(item)


def _count(jaxpr, primitive):
    """Equations of `primitive` (by the start of its name) in `jaxpr` and
    every jaxpr below it, each as often as it stands there; of a `cond`
    (the tiers' `lax.switch`) one branch, all branches alike."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name.startswith(primitive)
        if eqn.primitive.name == "cond":
            paths = {_count(b.jaxpr, primitive)
                     for b in eqn.params["branches"]}
            assert len(paths) == 1, paths
            n += paths.pop()
            continue
        for value in eqn.params.values():
            n += sum(_count(j, primitive) for j in _jaxprs(value))
    return n


def _gradient_counts(net, *primitives, loss=None):
    loss = loss or _loss(net)
    # a new function each time: JAX caches a trace
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss(p)[0]))(
        net.params_tree).jaxpr
    return [_count(jaxpr, p) for p in primitives]


def _without_the_names(monkeypatch):
    """The parent's policy: the attention kernels' pair alone."""
    monkeypatch.setattr(names, "KEPT_NAMES", names.RESIDUAL_NAMES)


def _gauge(net, name="block_residuals_kept"):
    return get_registry().gauge(name, model=type(net).__name__).value


def test_the_tiers_sit_under_a_switch_here():
    assert len(_row_tiers(T * 2, 2 / 32, T * 2)) == 3


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_the_recomputed_forward_leaves_out_what_was_kept(monkeypatch, kind):
    gone = np.array(BLOCKS[kind][1:])
    net = _net(kind, True)
    now = np.array(_gradient_counts(net, *PRIMITIVES))
    assert now[0] == LAYERS * GROUPED.get(kind, 0)
    with monkeypatch.context() as parent:
        _without_the_names(parent)
        was = np.array(_gradient_counts(net, *PRIMITIVES))
    np.testing.assert_array_equal(was - now, LAYERS * gone)
    assert now[2] == LAYERS * gone[2]   # a choice: forward, and never again
    # the schedule's sort and the weights' forward; their cotangent's
    assert now[3] == LAYERS * 3 * kind.endswith("experts")


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_checkpointing_changes_no_number(monkeypatch, kind):
    """Gradients and the layers' new state, float32: those of the net
    without `gradient_checkpointing`, and those of the parent's policy,
    bit for bit. A kept value is the value the recomputation made."""
    net, plain = _net(kind, True), _net(kind, False)
    params = net.params_tree
    got = jax.grad(_loss(net), has_aux=True)(params)
    want = jax.grad(_loss(plain), has_aux=True)(params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    with monkeypatch.context() as parent:
        _without_the_names(parent)
        was = jax.grad(_loss(net), has_aux=True)(params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, was)
    moved = [float(jnp.max(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(got[0])]
    assert max(moved) > 0 and all(np.isfinite(moved))


@pytest.mark.parametrize("kind", sorted(BLOCKS))
@pytest.mark.parametrize("checkpointing", [True, False])
def test_the_gauge_counts_the_values_named_under_a_checkpoint(
        kind, checkpointing):
    net = _net(kind, checkpointing)
    jax.make_jaxpr(jax.grad(lambda p: _loss(net)(p)[0]))(net.params_tree)
    # an expert layer's choice, order, places, sizes and sorted weights
    a_block = (2 if kind.startswith("sandwich") else 1) + 5 * kind.endswith(
        "experts")
    assert _gauge(net) == checkpointing * LAYERS * a_block
    assert _gauge(net, "attention_residuals_kept") == 0


# the benchmark's token models at their CPU rehearsals' sizes, built by
# the benchmark's own model files: two values a sandwich block and five
# (choice, order, places, sizes, sorted weights) each of two expert
# layers; the stream of each pre-norm block and five each of two expert
# layers; three streams and one selection; the stream and the five of
# each of three blocks; the five and the routed sum of each of two
# LatentMoE layers (the prediction module's own checkpoint keeps its
# layer's, outside the gauge)
MODELS = {"trinity_tiny": 6 + 10, "deepseek_v2_tiny": 3 + 10,
          "minicpm_sala_tiny": 4, "granite_4_0_h_small_tiny": 3 * 6,
          "nemotron_3_super_tiny": 2 * 6}


@pytest.mark.parametrize("config", sorted(MODELS))
@pytest.mark.parametrize("checkpointing", [True, False])
def test_a_model_of_each_kind_reads_its_count(config, checkpointing):
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           config + ".json"), encoding="utf-8") as fh:
        cfg = {**json.load(fh), "gradient_checkpointing": checkpointing}
    net = harness.load_module("models", cfg["model"] + ".py").build(
        cfg, 0).init()
    ids = jnp.zeros((1, cfg["input_shape"][0]), jnp.int32)
    jax.make_jaxpr(jax.grad(lambda p: net._loss(
        p, net.state_tree, ids, ids, None, None, None, train=True)[0]))(
            net.params_tree)
    assert _gauge(net) == checkpointing * MODELS[config]


@pytest.mark.parametrize("model", ["MultiLayerNetwork", "ComputationGraph"])
def test_a_block_that_names_nothing_keeps_nothing(monkeypatch, model):
    """`TransformerEncoderBlock`'s norms read a sub-layer's output too, and
    it names none: nothing is named in its gradient, nothing is kept (JAX
    marks a kept value with `reduce_precision`), and its products are
    those of the parent's policy."""
    from deeplearning4j_tpu.models import ComputationGraph
    from deeplearning4j_tpu.nn.layers.attention import (
        TransformerEncoderBlock,
    )

    base = NeuralNetConfiguration.builder().seed(3).gradient_checkpointing()
    blocks = [TransformerEncoderBlock(num_heads=4) for _ in range(LAYERS)]
    head = RnnOutputLayer(n_out=CLASSES, activation="softmax", loss="mcxent")
    if model == "MultiLayerNetwork":
        net = MultiLayerNetwork(
            base.list(*blocks, head)
            .set_input_type(InputType.recurrent(WIDTH, T)).build()).init()
        loss = _loss(net)
    else:
        graph, last = base.graph_builder().add_inputs("in"), "in"
        for i, block in enumerate(blocks):
            graph, last = graph.add_layer(f"b{i}", block, last), f"b{i}"
        net = ComputationGraph(
            graph.add_layer("out", head, last).set_outputs("out")
            .set_input_types(InputType.recurrent(WIDTH, T)).build()).init()
        x, y = _batch()
        loss = lambda p: net._loss(p, net.state_tree, {"in": x}, {"out": y},
                                   None, None, None)

    def counts():
        return _gradient_counts(net, "name", "reduce_precision",
                                "dot_general", "remat", "exp", loss=loss)

    now = counts()
    assert now[:2] == [0, 0] and now[3] >= LAYERS
    assert _gauge(net) == 0
    with monkeypatch.context() as parent:
        _without_the_names(parent)
        assert counts() == now


def test_a_name_outside_the_list_is_refused():
    with pytest.raises(ValueError, match="not in"):
        names.name_block_residual(jnp.zeros(2), "attention_out")
