"""A convolution's bias and activation run AFTER the max-pool that follows
it (`nn/layers/convolution.defers_to_pool`), on a quarter of the elements.

`maxpool(act(conv + b)) == act(maxpool(conv) + b)` exactly for the
piecewise-linear non-decreasing activations, so the two orders must give
the same outputs to the bit in float32 and in bfloat16 and the same
float32 gradients up to the bias gradient's summation order. Where the rule
does not engage the traced program must be the text it was without the
rule. The plain order is the same net traced with the rule switched off.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.models import (
    ComputationGraph, MultiLayerNetwork, computation_graph, multilayer,
)
from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.layers import (
    BatchNormalization, ConvolutionLayer, Deconvolution2DLayer,
    DepthwiseConvolution2DLayer, GlobalPoolingLayer, OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.layers.convolution import defers_to_pool
from deeplearning4j_tpu.nn.preprocessors import Preprocessor
from deeplearning4j_tpu.observe.registry import get_registry

MODELS = ("MultiLayerNetwork", "ComputationGraph")
ACTIVATIONS = ("relu", "relu6", "leakyrelu", "leakyrelu:0.3", "identity")
POOLS = {
    "2x2s2": dict(kernel=(2, 2), stride=(2, 2)),
    "3x3s2_padded": dict(kernel=(3, 3), stride=(2, 2), padding=(1, 1)),
    "3x3s2_same": dict(kernel=(3, 3), stride=(2, 2), convolution_mode="same"),
}
SHAPE = (4, 12, 12, 3)


@dataclasses.dataclass(frozen=True)
class _Flip(Preprocessor):
    """A preprocessor that keeps the shape: something BETWEEN the two."""

    def output_type(self, input_type):
        return input_type

    def apply(self, x, mask=None):
        return x[:, ::-1]


def _conv(act="relu", **kw):
    kw.setdefault("convolution_mode", "same")
    return ConvolutionLayer(n_out=5, kernel=(3, 3), activation=act, **kw)


def _pool(**kw):
    return SubsamplingLayer(**{"pooling": "max", **POOLS["2x2s2"], **kw})


def _net(model, layers, *, dtype="float32", preprocessor_at=None,
         checkpointing=False, shape=SHAPE):
    """`layers` then global average pooling and a softmax output, as a
    stack or as the same chain in a graph (vertices `v0`, `v1`, ...)."""
    layers = list(layers) + [
        GlobalPoolingLayer(pooling="avg"),
        OutputLayer(n_out=3, activation="softmax", loss="mcxent")]
    base = (NeuralNetConfiguration.builder().seed(3).dtype(dtype)
            .gradient_checkpointing(checkpointing))
    shape = InputType.convolutional(*shape[1:])
    if model == "MultiLayerNetwork":
        b = base.list(*layers).set_input_type(shape)
        if preprocessor_at is not None:
            b.input_preprocessor(preprocessor_at, _Flip())
        return _randomized(MultiLayerNetwork(b.build()).init())
    g = base.graph_builder().add_inputs("in").set_input_types(shape)
    for i, layer in enumerate(layers):
        g.add_layer(f"v{i}", layer, f"v{i - 1}" if i else "in",
                    preprocessor=_Flip() if i == preprocessor_at else None)
    g.set_outputs(f"v{len(layers) - 1}")
    return _randomized(ComputationGraph(g.build()).init())


def _randomized(net, seed=0):
    """Every parameter off its initial value, the biases above all."""
    rng = np.random.default_rng(seed)
    net.params_tree = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(0.0, 0.5, a.shape), a.dtype),
        net.params_tree)
    return net


def _batch(dtype=jnp.float32, seed=1, shape=SHAPE):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, shape[0])])
    return x, y


def _never(producer, consumer):
    return False


@contextlib.contextmanager
def _plain_order(net):
    """The same net with the rule switched off: the order it ran in before
    there was a rule."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(multilayer, "defers_to_pool", _never)
        mp.setattr(computation_graph, "defers_to_pool", _never)
        net.__dict__.pop("_pool_after_cache", None)
        try:
            yield
        finally:
            net.__dict__.pop("_pool_after_cache", None)


def _output_fn(net):
    if isinstance(net, ComputationGraph):
        out = net.conf.network_outputs[0]
        return lambda p, x: net._forward(
            p, net.state_tree, {"in": x}, train=False, rng=None)[0][out]
    return lambda p, x: net._forward(
        p, net.state_tree, x, train=False, rng=None)[0]


def _loss_fn(net):
    if isinstance(net, ComputationGraph):
        out = net.conf.network_outputs[0]
        return lambda p, x, y: net._loss(
            p, net.state_tree, {"in": x}, {out: y}, None, None, None,
            train=True)[0]
    return lambda p, x, y: net._loss(
        p, net.state_tree, x, y, None, None, None, train=True)[0]


def _both_orders(net, make_fn, *args):
    """`make_fn(net)` jitted and run in the deferred and the plain order."""
    fn = jax.jit(make_fn(net))
    deferred = fn(net.params_tree, *args)
    with _plain_order(net):
        fn = jax.jit(make_fn(net))
        plain = fn(net.params_tree, *args)
    return deferred, plain


def _texts(net, make_fn, *args):
    live = jax.jit(make_fn(net)).lower(net.params_tree, *args).as_text()
    with _plain_order(net):
        off = jax.jit(make_fn(net)).lower(net.params_tree, *args).as_text()
    return live, off


def _pairs(net):
    return get_registry().gauge("conv_pool_pairs_deferred",
                                model=type(net).__name__).value


def _assert_gradients_agree(deferred, plain, rel=1e-6):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(deferred),
                            jax.tree_util.tree_leaves(plain)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        gap = np.linalg.norm(a - b)
        assert gap <= rel * np.linalg.norm(b) + 1e-12, (
            jax.tree_util.keystr(path), gap, np.linalg.norm(b))


# ------------------------------------------------------------- the rule
@pytest.mark.parametrize("name, commutes", [
    (None, True), ("identity", True), ("linear", True), ("relu", True),
    ("RELU", True), ("relu6", True), ("leakyrelu", True),
    ("leakyrelu:0.2", True), ("leakyrelu:0", True), ("leakyrelu:-0.1", False),
    ("tanh", False), ("sigmoid", False), ("gelu", False), ("swish", False),
    ("mish", False), ("softmax", False), ("elu", False),
    ("clippedrelu", False), (jax.nn.relu, False),
])
def test_which_activations_commute_with_a_max_pool(name, commutes):
    assert Activation.commutes_with_max_pool(name) is commutes


def test_a_name_registered_over_does_not_commute():
    from deeplearning4j_tpu.nn import activations
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(activations._REGISTRY, "relu", jnp.tanh)
        assert not Activation.commutes_with_max_pool("relu")
    assert Activation.commutes_with_max_pool("relu")


@pytest.mark.parametrize("producer, consumer, defers", [
    (_conv("relu"), _pool(), True),
    (_conv("identity"), _pool(), True),                   # a bias alone
    (_conv("relu", has_bias=False), _pool(), True),       # a ReLU alone
    (_conv("identity", has_bias=False), _pool(), False),  # nothing to defer
    (Deconvolution2DLayer(n_out=5, activation="relu"), _pool(), True),
    (_conv("tanh"), _pool(), False),
    (_conv(jax.nn.relu), _pool(), False),
    (_conv("relu"), _pool(pooling="avg"), False),
    (_conv("relu"), _pool(pooling="MAX"), True),
    (_conv("relu"), _pool(dropout=0.5), False),
    (_conv("relu"), _conv("relu"), False),
    (BatchNormalization(activation="relu"), _pool(), False),
    (DepthwiseConvolution2DLayer(activation="relu"), _pool(), False),
])
def test_defers_to_pool(producer, consumer, defers):
    assert defers_to_pool(producer, consumer) is defers


# ------------------------------------------------- the two orders agree
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("act", ACTIVATIONS)
@pytest.mark.parametrize("model", MODELS)
def test_outputs_equal_the_plain_order_to_the_bit(model, act, pool, dtype):
    net = _net(model, [_conv(act), SubsamplingLayer(**POOLS[pool]),
                       _conv("identity")], dtype=dtype)
    x, _ = _batch(jnp.dtype(dtype))
    deferred, plain = _both_orders(net, _output_fn, x)
    assert _pairs(net) == 0          # the plain order was traced last
    assert deferred.dtype == plain.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(deferred, np.float32),
                                  np.asarray(plain, np.float32))
    live, off = _texts(net, _output_fn, x)
    assert live != off               # and the rule did engage


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("act", ACTIVATIONS)
@pytest.mark.parametrize("model", MODELS)
def test_float32_gradients_of_every_leaf_agree(model, act, pool):
    net = _net(model, [_conv(act), SubsamplingLayer(**POOLS[pool]),
                       _conv("relu"), _pool()])
    deferred, plain = _both_orders(
        net, lambda n: jax.grad(_loss_fn(n)), *_batch())
    assert all(np.abs(np.asarray(g)).max() > 0
               for g in jax.tree_util.tree_leaves(plain))
    _assert_gradients_agree(deferred, plain)


def _integer_valued(net, bias):
    """Small integers in every convolution, so that windows hold exact ties
    before and after the bias."""
    rng = np.random.default_rng(5)
    for name, p in net.params_tree.items():
        if p.get("W") is not None and p["W"].ndim == 4:
            p["W"] = jnp.asarray(rng.integers(-1, 2, p["W"].shape) * 0.125,
                                 p["W"].dtype)
            p["b"] = jnp.full(p["b"].shape, bias, p["b"].dtype)
    return net


@pytest.mark.parametrize("case, bias", [("exact_ties", 0.25),
                                        ("all_windows_negative", -50.0)])
@pytest.mark.parametrize("act", ["relu", "relu6", "leakyrelu", "identity"])
@pytest.mark.parametrize("model", MODELS)
def test_ties_and_negative_windows(model, act, case, bias):
    net = _integer_valued(
        _net(model, [_conv(act), _pool(), _conv("identity")]), bias)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 2, SHAPE), jnp.float32)
    y = _batch()[1]
    first = "layer0_convolutionlayer" if model == MODELS[0] else "v0"
    raw = net.conf.layers[0] if model == MODELS[0] else \
        net.conf.vertices["v0"].layer
    pre = np.asarray(raw.pre_output(net.params_tree[first], x))
    if case == "exact_ties":
        windows = (pre.reshape(SHAPE[0], 6, 2, 6, 2, 5)
                   .transpose(0, 1, 3, 5, 2, 4).reshape(-1, 4))
        tied = (windows == windows.max(1, keepdims=True)).sum(1) > 1
        assert tied.mean() > 0.2     # the maximum is shared in many windows
    else:
        assert pre.max() < 0
    out_d, out_p = _both_orders(net, _output_fn, x)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    g_d, g_p = _both_orders(net, lambda n: jax.grad(_loss_fn(n)), x, y)
    _assert_gradients_agree(g_d, g_p)


# --------------------------------------------- where it does not engage
def _branching(second_pool):
    """conv -> pool -> output, and besides either a second pool on the
    convolution or the convolution as a network output."""
    g = (NeuralNetConfiguration.builder().seed(3).graph_builder()
         .add_inputs("in")
         .set_input_types(InputType.convolutional(*SHAPE[1:])))
    g.add_layer("conv", _conv("relu"), "in")
    g.add_layer("pool", _pool(), "conv")
    last = "pool"
    if second_pool:
        g.add_layer("pool_too", _pool(), "conv")
        g.add_vertex("add", ElementWiseVertex(op="add"), "pool", "pool_too")
        last = "add"
    g.add_layer("gap", GlobalPoolingLayer(pooling="avg"), last)
    g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "gap")
    g.set_outputs(*(("out",) if second_pool else ("out", "conv")))
    return _randomized(ComputationGraph(g.build()).init())


def _stem(model, **kw):
    """ResNet-50's stem in small: the pool follows a batch norm."""
    return _net(model, **kw, layers=[
        ConvolutionLayer(n_out=5, kernel=(3, 3), stride=(2, 2),
                         convolution_mode="same", activation="identity",
                         has_bias=False),
        BatchNormalization(activation="relu"),
        SubsamplingLayer(**POOLS["3x3s2_same"]),
        ConvolutionLayer(n_out=5, kernel=(1, 1), activation="identity",
                         has_bias=False)])


BYPASSES = {
    **{f"{p}_pool": (lambda m, p=p: _net(m, [_conv("relu"), _pool(pooling=p)]))
       for p in ("avg", "sum", "pnorm")},
    **{a: (lambda m, a=a: _net(m, [_conv(a), _pool()]))
       for a in ("tanh", "sigmoid", "gelu", "softmax")},
    "a_callable": lambda m: _net(m, [_conv(jax.nn.relu), _pool()]),
    "pool_with_dropout": lambda m: _net(m, [_conv("relu"),
                                            _pool(dropout=0.5)]),
    "preprocessor_between": lambda m: _net(m, [_conv("relu"), _pool()],
                                           preprocessor_at=1),
    "nothing_to_defer": lambda m: _net(m, [
        _conv("identity", has_bias=False), _pool()]),
    "batch_norm_before_the_pool": _stem,
}


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("case", sorted(BYPASSES))
def test_bypass_lowers_to_the_text_it_was(case, model):
    net = BYPASSES[case](model)
    x, y = _batch()
    for make_fn, args in ((_output_fn, (x,)),
                          (lambda n: jax.grad(_loss_fn(n)), (x, y))):
        live, off = _texts(net, make_fn, *args)
        assert live == off
    assert _pairs(net) == 0


@pytest.mark.parametrize("case", ["two_consumers", "conv_is_an_output"])
def test_graph_bypass_lowers_to_the_text_it_was(case):
    net = _branching(second_pool=case == "two_consumers")
    x, _ = _batch()

    def outputs(n):
        return lambda p, x: [
            n._forward(p, n.state_tree, {"in": x}, train=False,
                       rng=None)[0][o] for o in n.conf.network_outputs]

    live, off = _texts(net, outputs, x)
    assert live == off
    assert _pairs(net) == 0


@pytest.mark.parametrize("model", MODELS)
def test_gradient_checkpointing_leaves_the_pair_as_it_is(model):
    """Its unit is one layer, so the train step is the text it was; the
    same net's inference function, which checkpoints nothing, defers."""
    net = _net(model, [_conv("relu"), _pool(), _conv("relu"), _pool()],
               checkpointing=True)
    x, y = _batch()
    live, off = _texts(net, lambda n: jax.grad(_loss_fn(n)), x, y)
    assert live == off
    jax.jit(jax.grad(_loss_fn(net))).lower(net.params_tree, x, y)
    assert _pairs(net) == 0
    deferred, plain = _both_orders(net, _output_fn, x)
    np.testing.assert_array_equal(np.asarray(deferred), np.asarray(plain))
    jax.jit(_output_fn(net)).lower(net.params_tree, x)
    assert _pairs(net) == 2


# ------------------------------ callers that ask for every activation
def test_feed_forward_gives_every_layers_own_activation():
    net = _net(MODELS[0], [_conv("relu"), _pool(), _conv("relu"), _pool()])
    x, _ = _batch()
    get_registry().gauge("conv_pool_pairs_deferred",
                         model=MODELS[0]).set(-1)
    acts = net.feed_forward(x)
    assert _pairs(net) == -1         # and does not speak for the step
    with _plain_order(net):
        net._jit_cache.clear()
        plain = net.feed_forward(x)
    net._jit_cache.clear()
    assert len(acts) == len(plain) == 6
    for a, b in zip(acts, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(acts[0].min()) == 0.0 and acts[0].shape[1:3] == SHAPE[1:3]
    np.testing.assert_array_equal(np.asarray(acts[-1]),
                                  np.asarray(net.output(x)))


def test_graph_values_with_and_without_collect():
    net = _net(MODELS[1], [_conv("relu"), _pool(), _conv("relu"), _pool()])
    x, _ = _batch()
    args = (net.params_tree, net.state_tree, {"in": x})
    values, _, _ = net._forward(*args, train=False, rng=None)
    assert _pairs(net) == 2
    assert "v0" not in values and "v2" not in values
    collected, _, _ = net._forward(*args, train=False, rng=None, collect=True)
    assert _pairs(net) == 2
    with _plain_order(net):
        plain, _, _ = net._forward(*args, train=False, rng=None)
    assert sorted(collected) == sorted(plain)
    for name in plain:
        np.testing.assert_array_equal(np.asarray(collected[name]),
                                      np.asarray(plain[name]))
    for name in values:
        np.testing.assert_array_equal(np.asarray(values[name]),
                                      np.asarray(plain[name]))


def test_stop_before_the_pool_leaves_its_convolution_whole():
    net = _net(MODELS[1], [_conv("relu"), _pool()])
    x, _ = _batch()
    values, _, _ = net._forward(net.params_tree, net.state_tree, {"in": x},
                                train=False, rng=None, stop_before="v1")
    assert float(values["v0"].min()) == 0.0


# ------------------------------------------------------------ the gauge
def _vgg_shaped():
    layers = []
    for block, convs in enumerate((2, 2, 3, 3, 3)):
        layers += [ConvolutionLayer(n_out=4 + block, kernel=(3, 3),
                                    convolution_mode="same",
                                    activation="relu")] * convs
        layers.append(_pool())
    return layers


@pytest.mark.parametrize("model", MODELS)
def test_gauge_counts_the_deferred_pairs(model):
    shape = (2, 32, 32, 3)
    vgg = _net(model, _vgg_shaped(), shape=shape)
    stem = _stem(model, shape=shape)
    x, y = _batch(shape=shape)

    def pairs_in_the_step(net):
        batch = (({"in": x}, {net.conf.network_outputs[0]: y})
                 if model == MODELS[1] else (x, y))
        jax.jit(net.make_step_fn()).lower(
            net.params_tree, net.updater_state, net.state_tree,
            jnp.asarray(0, jnp.int32), *batch, None, None,
            jax.random.PRNGKey(0))
        return _pairs(net)

    assert pairs_in_the_step(vgg) == 5
    assert pairs_in_the_step(stem) == 0
    assert pairs_in_the_step(vgg) == 5
