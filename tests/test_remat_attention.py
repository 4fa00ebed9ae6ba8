"""Gradient checkpointing keeps what an attention kernel's backward reads.

A checkpointed layer (`models/multilayer._checkpointed`) keeps the two
arrays the forward rules of `ops/attention.py` and `ops/banded_attention.py`
name (`RESIDUAL_NAMES`: the kernel's output and its rows' log-sum-exp), so
the backward pass recomputes the layer round the kernel and does not run the
kernel again. Counted in the gradient's jaxpr, on the CPU with the kernels
in interpret mode: nothing here is a time.
"""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
from deeplearning4j_tpu.observe.registry import get_registry

# by module path: `ops/__init__` re-exports a function under this name
flash = importlib.import_module("deeplearning4j_tpu.ops.attention")
kernel_defaults = importlib.import_module(
    "deeplearning4j_tpu.ops.kernel_defaults")

T, WIDTH, CLASSES, LAYERS = 128, 32, 8, 2


def _route(monkeypatch, kernel):
    """Send the layers' attention to `kernel` on the CPU, interpreted."""
    if kernel == "banded":
        monkeypatch.setenv("DL4J_TPU_ATTN", "banded")
        return
    real = flash.flash_attention
    monkeypatch.setattr(
        kernel_defaults, "attention_policy",
        lambda tq, tk=None, train=False: kernel_defaults.AttentionPolicy(
            "flash", 64, 64, "pallas", "the test's"))
    monkeypatch.setattr(
        flash, "flash_attention",
        lambda q, k, v, causal, scale, bq, bk, interpret, backward:
        real(q, k, v, causal, scale, bq, bk, True, backward))


def _layers(kernel):
    if kernel == "dense":
        return [DenseLayer(n_out=WIDTH, activation="tanh")
                for _ in range(LAYERS)]
    return [MultiHeadAttention(n_out=WIDTH, num_heads=4, num_kv_heads=2,
                               causal=True,
                               window=64 if kernel == "banded" else None)
            for _ in range(LAYERS)]


def _net(model, kernel, checkpointing):
    base = (NeuralNetConfiguration.builder().seed(3)
            .gradient_checkpointing(checkpointing))
    head = RnnOutputLayer(n_out=CLASSES, activation="softmax",
                          loss="mcxent")
    if model is MultiLayerNetwork:
        conf = (base.list(*_layers(kernel), head)
                .set_input_type(InputType.recurrent(WIDTH, T)).build())
        return MultiLayerNetwork(conf).init()
    graph, last = base.graph_builder().add_inputs("in"), "in"
    for i, layer in enumerate(_layers(kernel)):
        graph, last = graph.add_layer(f"a{i}", layer, last), f"a{i}"
    conf = (graph.add_layer("out", head, last).set_outputs("out")
            .set_input_types(InputType.recurrent(WIDTH, T)).build())
    return ComputationGraph(conf).init()


def _batch():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, T, WIDTH), jnp.float32)
    y = jax.nn.one_hot(rng.randint(0, CLASSES, (2, T)), CLASSES,
                       dtype=jnp.float32)
    return x, y


def _loss(net):
    x, y = _batch()
    if isinstance(net, MultiLayerNetwork):
        return lambda p: net._loss(p, net.state_tree, x, y, None, None,
                                   None)[0]
    return lambda p: net._loss(p, net.state_tree, {"in": x}, {"out": y},
                               None, None, None)[0]


def _gauge(net):
    return get_registry().gauge("attention_residuals_kept",
                                model=type(net).__name__).value


def _bare_checkpoint(monkeypatch):
    """The parent's form: `jax.checkpoint` with no policy."""
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


def _same(a, b, **tolerance):
    jax.tree_util.tree_map(
        functools.partial(np.testing.assert_allclose, **tolerance)
        if tolerance else np.testing.assert_array_equal, a, b)


@pytest.mark.parametrize("model", [MultiLayerNetwork, ComputationGraph],
                         ids=lambda m: m.__name__)
@pytest.mark.parametrize("kernel", ["banded", "flash"])
def test_the_recomputed_forward_does_not_run_the_kernel_again(
        monkeypatch, model, kernel):
    _route(monkeypatch, kernel)
    forward = re.compile(rf"name={kernel}_attention_fwd\b")
    net = _net(model, kernel, True)
    params = net.params_tree

    def calls():        # a new function each time: JAX caches a trace
        return len(forward.findall(
            str(jax.make_jaxpr(jax.grad(_loss(net)))(params))))

    assert calls() == LAYERS
    assert _gauge(net) == LAYERS
    gradient = jax.grad(_loss(net))(params)

    with monkeypatch.context() as parent:
        _bare_checkpoint(parent)
        assert calls() == 2 * LAYERS
        # the names are there and nothing keeps them
        assert _gauge(net) == LAYERS
        _same(gradient, jax.grad(_loss(net))(params))   # bit for bit

    plain = _net(model, kernel, False)
    _same(gradient, jax.grad(_loss(plain))(params), rtol=2e-5, atol=1e-6)
    assert _gauge(plain) == 0


@pytest.mark.parametrize("model", [MultiLayerNetwork, ComputationGraph],
                         ids=lambda m: m.__name__)
def test_a_layer_that_names_nothing_keeps_nothing(monkeypatch, model):
    """Dense layers under the policy: the parent's program. The printed
    jaxprs differ in the policy's own address and in nothing else."""
    net = _net(model, "dense", True)

    def printed():
        return re.sub(r"policy=[^\n\]]*", "policy=", str(jax.make_jaxpr(
            jax.grad(_loss(net)))(net.params_tree)))

    now = printed()
    assert _gauge(net) == 0
    assert "remat" in now and "name[" not in now
    with monkeypatch.context() as parent:
        _bare_checkpoint(parent)
        assert printed() == now


def test_only_the_pallas_backward_names_its_residuals():
    """The `"dense"` backward recomputes from q, k and v alone: nothing is
    named for it, and `flash_attention_with_lse` (the ring's) names
    nothing either."""
    q = jnp.ones((2, 128, 16), jnp.float32)

    def names(fn):
        jaxpr = str(jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(fn(q))))(q))
        return [n for n in flash.RESIDUAL_NAMES if f"name={n}" in jaxpr]

    assert names(lambda q: flash.flash_attention(
        q, q, q, True, None, 64, 64, True, "pallas")) \
        == list(flash.RESIDUAL_NAMES)
    assert names(lambda q: flash.flash_attention(
        q, q, q, True, None, 64, 64, True, "dense")) == []
    assert names(lambda q: flash.flash_attention_with_lse(
        q, q, q, True, None, 64, 64, True)[0]) == []
