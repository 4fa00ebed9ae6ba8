"""In-tree perf regression guard that works without TPU hardware.

Absolute rates come only from the chip (`benchmarks/`, the ledger);
what CAN be guarded in CI is the RATIO of the framework's jitted
train step to an equivalent hand-written jax step on the same device —
machine speed divides out. A ratio blow-up means a compile-path
regression: accidental per-step recompiles, host syncs inside the loop,
a de-donated buffer, Python in the hot path. Reference precedent:
`datasets/iterator/impl/BenchmarkDataSetIterator.java` (synthetic
throughput fixtures); VERDICT r3 next-step #7.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optim.updaters import Sgd

B, F, H, C = 256, 128, 256, 10
LR = 0.01


def _median_step_seconds(fn, n=30, trials=3):
    best = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / n)
    return min(best)


@pytest.fixture(scope="module")
def data():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.standard_normal((B, F)), jnp.float32)
    y = jnp.asarray(np.eye(C, dtype=np.float32)[r.integers(0, C, B)])
    return x, y


def test_jitted_step_within_2x_of_raw_jax(data):
    x, y = data
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=H, activation="relu"))
            .layer(OutputLayer(n_out=C, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(F)).build())
    net = MultiLayerNetwork(conf).init()
    step = jax.jit(net.make_step_fn())
    params, opt = net.params_tree, net.updater_state
    states = net.state_tree
    itn = jnp.asarray(0, jnp.int32)
    rng = jax.random.PRNGKey(0)

    def framework_step():
        nonlocal params, opt
        out = step(params, opt, states, itn, x, y, None, None, rng, None)
        params, opt = out[0], out[1]
        return out[3]

    framework_step()  # compile

    # equivalent raw jax: same arch, loss, and SGD update
    raw_params = jax.tree_util.tree_map(jnp.array, net.params_tree)

    def raw_loss(p, x, y):
        h = jax.nn.relu(x @ p["layer0_denselayer"]["W"]
                        + p["layer0_denselayer"]["b"])
        logits = (h @ p["layer1_outputlayer"]["W"]
                  + p["layer1_outputlayer"]["b"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    @jax.jit
    def raw_step(p, x, y):
        loss, g = jax.value_and_grad(raw_loss)(p, x, y)
        p = jax.tree_util.tree_map(lambda w, gw: w - LR * gw, p, g)
        return p, loss

    def raw():
        nonlocal raw_params
        raw_params, loss = raw_step(raw_params, x, y)
        return loss

    raw()  # compile

    t_fw = _median_step_seconds(framework_step)
    t_raw = _median_step_seconds(raw)
    ratio = t_fw / t_raw
    # generous bound: the framework step legitimately does a little more
    # (listener outputs, iteration counter, score) but 2x means a
    # compile-path regression (recompiles / host syncs / de-donation)
    assert ratio < 2.0, (
        f"framework jitted step {t_fw * 1e6:.0f}us vs raw jax "
        f"{t_raw * 1e6:.0f}us — ratio {ratio:.2f} >= 2.0; the train-step "
        "compile path has regressed")


def test_no_recompile_across_steps(data):
    """Each additional fit step must NOT trigger a new trace — recompiles
    are the classic silent 10x (dynamic shapes / unhashable statics)."""
    x, y = data
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=C, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(F)).build())
    net = MultiLayerNetwork(conf).init()
    step = jax.jit(net.make_step_fn())
    params, opt = net.params_tree, net.updater_state
    itn = jnp.asarray(0, jnp.int32)
    rng = jax.random.PRNGKey(0)
    with jax.log_compiles(True):
        import io
        import logging

        buf = io.StringIO()
        handler = logging.StreamHandler(buf)
        logging.getLogger("jax").addHandler(handler)
        try:
            for i in range(4):
                out = step(params, opt, net.state_tree, itn + i, x, y,
                           None, None, rng, None)
                params, opt = out[0], out[1]
            jax.block_until_ready(out[3])
        finally:
            logging.getLogger("jax").removeHandler(handler)
        logs = buf.getvalue()
    # exactly one compilation of step_fn is allowed (the first call);
    # one compile emits several log lines (trace/lower/compile), so count
    # only the final XLA-compilation line
    n = logs.count("Finished XLA compilation of jit(step_fn)")
    # n == 1 exactly: the first call MUST compile, which also proves the
    # log probe still matches (n == 0 would mean the probe went stale)
    assert n == 1, f"{n} compilations of step_fn — recompiles:\n{logs}"


def test_decode_steps_do_not_recompile():
    """KV-cache stepping promises fixed shapes — after the first
    one-token step compiles, every further token must reuse it (a
    recompile per token is the classic silent 100x in generation)."""
    from deeplearning4j_tpu.zoo.transformer import TextGenerationTransformer

    net = TextGenerationTransformer(num_classes=9, input_shape=(16, 1),
                                    d_model=16, num_heads=2,
                                    num_blocks=1).init()
    x = np.random.default_rng(0).integers(
        0, 9, (1, 16, 1)).astype(np.float32)
    net.rnn_clear_previous_state()
    net.rnn_time_step(x[:, :4, :])       # prefix (its own shape, compiles)
    net.rnn_time_step(x[:, 4:5, :])      # first 1-token step compiles
    with jax.log_compiles(True):
        import io
        import logging

        buf = io.StringIO()
        handler = logging.StreamHandler(buf)
        logging.getLogger("jax").addHandler(handler)
        try:
            for t in range(5, 12):
                out = net.rnn_time_step(x[:, t:t + 1, :])
            jax.block_until_ready(out)
        finally:
            logging.getLogger("jax").removeHandler(handler)
        logs = buf.getvalue()
    n = logs.count("Finished XLA compilation")
    assert n == 0, f"{n} recompiles during steady-state decode:\n{logs}"


# --------------------------------------------------------- dispatch depth
class TestDispatchDepthGuard:
    """Async-dispatch contract: the default fit() hot loop must not sync
    the host more than once per epoch. Patches the device→host
    materialization seams (`ArrayImpl.__float__` / `block_until_ready`) so
    any per-step `float(loss)` regression in multilayer.py /
    computation_graph.py / data_parallel.py fails loudly here."""

    def _counting_patches(self, monkeypatch, counts):
        from jax._src import array as _jarray

        orig_float = _jarray.ArrayImpl.__float__
        orig_block = _jarray.ArrayImpl.block_until_ready

        def counting_float(a):
            counts["float"] += 1
            return orig_float(a)

        def counting_block(a):
            counts["block"] += 1
            return orig_block(a)

        monkeypatch.setattr(_jarray.ArrayImpl, "__float__", counting_float)
        monkeypatch.setattr(_jarray.ArrayImpl, "block_until_ready",
                            counting_block)

    def test_multilayer_fit_syncs_at_most_once_per_epoch(self, monkeypatch):
        r = np.random.default_rng(1)
        x = r.standard_normal((64, F)).astype(np.float32)
        y = np.eye(C, dtype=np.float32)[r.integers(0, C, 64)]
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
                .list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=C, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(F)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(x, y, epochs=1, batch_size=16)      # compile outside guard

        counts = {"float": 0, "block": 0}
        self._counting_patches(monkeypatch, counts)
        epochs = 3
        net.fit(x, y, epochs=epochs, batch_size=16)
        assert net._loss_tracker.updates >= 4 * epochs + 4
        assert counts["float"] + counts["block"] <= epochs, counts

    def test_computation_graph_fit_syncs_at_most_once_per_epoch(
            self, monkeypatch):
        from deeplearning4j_tpu.models import ComputationGraph

        r = np.random.default_rng(2)
        x = r.standard_normal((64, F)).astype(np.float32)
        y = np.eye(C, dtype=np.float32)[r.integers(0, C, 64)]
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=F, n_out=32,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=32, n_out=C,
                                              activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out")
                .build())
        net = ComputationGraph(conf).init()
        net.fit(x, y, epochs=1, batch_size=16)

        counts = {"float": 0, "block": 0}
        self._counting_patches(monkeypatch, counts)
        epochs = 3
        net.fit(x, y, epochs=epochs, batch_size=16)
        assert counts["float"] + counts["block"] <= epochs, counts

    def test_parallel_wrapper_fit_syncs_at_most_once_per_epoch(
            self, monkeypatch):
        from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

        r = np.random.default_rng(3)
        x = r.standard_normal((64, F)).astype(np.float32)
        y = np.eye(C, dtype=np.float32)[r.integers(0, C, 64)]
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
                .list()
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=C, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(F)).build())
        net = MultiLayerNetwork(conf).init()
        pw = ParallelWrapper(net)
        pw.fit(x, y, epochs=1, batch_size=32)

        counts = {"float": 0, "block": 0}
        self._counting_patches(monkeypatch, counts)
        epochs = 2
        pw.fit(x, y, epochs=epochs, batch_size=32)
        assert counts["float"] + counts["block"] <= epochs, counts

    def test_score_access_is_the_sync_point(self, monkeypatch):
        r = np.random.default_rng(4)
        x = r.standard_normal((32, F)).astype(np.float32)
        y = np.eye(C, dtype=np.float32)[r.integers(0, C, 32)]
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(LR))
                .list()
                .layer(OutputLayer(n_out=C, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(F)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(x, y, epochs=1, batch_size=16)
        before = net._loss_tracker.host_syncs
        assert np.isfinite(net.score_)      # epoch-end already materialized
        assert net._loss_tracker.host_syncs == before   # cache hit, no sync
