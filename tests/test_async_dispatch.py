"""Async-dispatch training loop: deferred loss sync, device prefetch,
fused multi-step execution, and the iterator plumbing underneath.

Covers the pipelined-executor contract (PERF_NOTES): the steady-state fit
hot loop performs no per-step host syncs, `steps_per_dispatch=K` is
bit-identical to K sequential steps, and AsyncDataSetIterator surfaces
worker failures / joins its thread deterministically.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import (
    ArrayDataSetIterator, AsyncDataSetIterator, DataSetIterator,
    DevicePrefetchIterator, IterableDataSetIterator, as_iterator,
)
from deeplearning4j_tpu.models.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optim.executor import LossTracker, TrainingExecutor
from deeplearning4j_tpu.optim.listeners import (
    CollectScoresIterationListener, TrainingListener,
)


def _mlp(seed=7, updater="sgd", **conf_kw):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(updater))
    for k, v in conf_kw.items():
        b = getattr(b, k)(*v) if isinstance(v, tuple) else getattr(b, k)(v)
    return MultiLayerNetwork(
        b.list(DenseLayer(n_in=8, n_out=16, activation="relu"),
               OutputLayer(n_in=16, n_out=3, activation="softmax",
                           loss="mcxent"))
        .build()).init()


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def _max_param_diff(a, b):
    return max(float(jnp.abs(x - y).max()) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------- tracker
class TestLossTracker:
    def test_defers_until_read(self):
        t = LossTracker()
        t.update(jnp.float32(1.5))
        assert t.host_syncs == 0
        assert isinstance(t.peek(), jax.Array)
        assert t.value == 1.5
        assert t.host_syncs == 1
        # cached: second read is free
        assert t.value == 1.5
        assert t.host_syncs == 1

    def test_sync_every_cadence(self):
        t = LossTracker(sync_every=3)
        for i in range(7):
            t.update(jnp.float32(i))
        # materialized at updates 3 and 6 only
        assert t.host_syncs == 2

    def test_plain_floats_never_count_as_syncs(self):
        t = LossTracker()
        t.update(2.0)
        assert t.value == 2.0
        assert t.host_syncs == 0

    def test_set_does_not_count_update(self):
        t = LossTracker()
        t.set(4.0)
        assert t.updates == 0 and t.value == 4.0


# --------------------------------------------------------- deferred sync
class TestDeferredLossSync:
    def test_fit_keeps_loss_on_device(self):
        net = _mlp()
        x, y = _data()
        net.fit(x, y, epochs=2, batch_size=16)
        # raw loss is a device array; score_ reads materialize lazily
        assert net._loss_tracker.updates == 8
        # exactly one mandatory materialization per epoch
        assert net._loss_tracker.host_syncs == 2
        assert np.isfinite(net.score_)

    def test_sync_every_knob(self):
        net = _mlp()
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16, sync_every=2)
        # 4 steps / sync_every=2 → 2 cadence syncs; epoch end hits cache
        assert net._loss_tracker.host_syncs == 2

    def test_listener_receives_device_score_and_can_materialize(self):
        seen = []

        class Probe(TrainingListener):
            def iteration_done(self, model, iteration, epoch, score):
                seen.append(score)

        net = _mlp()
        net.set_listeners(Probe())
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        assert len(seen) == 4
        assert all(isinstance(s, jax.Array) for s in seen)
        assert all(np.isfinite(float(s)) for s in seen)

    def test_collect_scores_listener_still_works(self):
        net = _mlp()
        col = CollectScoresIterationListener(frequency=2)
        net.set_listeners(col)
        x, y = _data()
        net.fit(x, y, epochs=1, batch_size=16)
        assert len(col.scores) == 2
        assert all(isinstance(s, float) for _, s in col.scores)


# ---------------------------------------------------------- fused steps
class TestFusedDispatch:
    def test_fused_matches_sequential_exactly(self):
        x, y = _data()
        a = _mlp()
        a.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=1)
        b = _mlp()
        b.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=4)
        assert _max_param_diff(a.params_tree, b.params_tree) < 1e-6
        assert abs(a.score_ - b.score_) < 1e-6
        assert b.iteration == 4

    def test_partial_buffer_drains_as_singles(self):
        # 6 batches with K=4 → one fused dispatch + 2 single steps
        x, y = _data(96)
        a = _mlp()
        a.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=4)
        b = _mlp()
        b.fit(x, y, epochs=1, batch_size=16)
        assert a.iteration == 6 == b.iteration
        assert _max_param_diff(a.params_tree, b.params_tree) < 1e-6

    def test_shape_change_flushes_buffer(self):
        x, y = _data(80)
        # 4 batches of 16 + 1 ragged batch of 16? use batch 24: 24,24,24,8
        a = _mlp()
        a.fit(x, y, epochs=1, batch_size=24, steps_per_dispatch=4)
        b = _mlp()
        b.fit(x, y, epochs=1, batch_size=24)
        assert a.iteration == 4 == b.iteration
        assert _max_param_diff(a.params_tree, b.params_tree) < 1e-6

    def test_non_sgd_solver_falls_back_to_per_step(self):
        x, y = _data(32)
        net = _mlp(updater="sgd",
                   optimization_algo=("lbfgs",))
        # must not raise: solver path is not fusible and runs per-step
        net.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=4)
        assert net.iteration == 2

    def test_tbptt_falls_back_to_per_step(self):
        from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer

        conf = (NeuralNetConfiguration.builder().seed(1).updater("sgd")
                .list(LSTM(n_in=5, n_out=7),
                      RnnOutputLayer(n_in=7, n_out=2, activation="softmax",
                                     loss="mcxent"))
                .tbptt(4)
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.standard_normal((8, 8, 5)).astype(np.float32)
        y = np.zeros((8, 8, 2), np.float32)
        y[..., 0] = 1.0
        net.fit(x, y, epochs=1, batch_size=4, steps_per_dispatch=4)
        assert net.iteration == 2
        assert np.isfinite(net.score_)


# -------------------------------------------------------- device prefetch
class TestDevicePrefetch:
    def test_batches_arrive_on_device(self):
        x, y = _data(32)
        it = DevicePrefetchIterator(ArrayDataSetIterator(x, y, 8))
        out = list(it)
        assert len(out) == 4
        assert all(isinstance(d.features, jax.Array) for d in out)
        np.testing.assert_array_equal(np.asarray(out[0].features), x[:8])

    def test_multi_epoch_reiteration(self):
        x, y = _data(32)
        it = DevicePrefetchIterator(ArrayDataSetIterator(x, y, 8))
        assert sum(1 for _ in it) == 4
        assert sum(1 for _ in it) == 4

    def test_transform_and_put_fn_hooks(self):
        x, y = _data(16)
        calls = []

        def transform(ds):
            calls.append("t")
            return ds

        def put(a):
            calls.append("p")
            return jax.device_put(a)

        it = DevicePrefetchIterator(
            ArrayDataSetIterator(x, y, 8), put_fn=put, transform=transform)
        list(it)
        assert calls.count("t") == 2
        assert calls.count("p") == 4  # features + labels per batch

    def test_runs_ahead_double_buffered(self):
        x, y = _data(64)
        consumed = []

        class Tracking(ArrayDataSetIterator):
            def __next__(self):
                d = super().__next__()
                consumed.append(1)
                return d

        it = DevicePrefetchIterator(Tracking(x, y, 8), depth=2)
        i = iter(it)
        next(i)
        # after ONE consumer next(), the prefetcher has pulled ≥2 more
        assert sum(consumed) >= 3

    @pytest.mark.parametrize("k", [1, 2], ids=["per_step", "fused"])
    @pytest.mark.parametrize("feed", ["float32", "uint8", "bfloat16"])
    def test_features_travel_in_the_iterators_dtype(self, feed, k):
        """A bf16 net's features reach the dispatch as the iterator made
        them (nothing is cast on the host) and train exactly as features
        pre-cast to bf16 with numpy do."""
        from deeplearning4j_tpu import observe
        from deeplearning4j_tpu.observe.trace import get_span_store

        rng = np.random.default_rng(3)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 48)]
        x = (rng.integers(0, 256, (48, 8)) if feed == "uint8"
             else rng.standard_normal((48, 8))).astype(jnp.dtype(feed))

        net = _mlp(dtype="bfloat16")
        seen = []
        step, fused = net._dispatch_batch, net._fused_dispatch

        def spy_step(ds):
            seen.append(ds.features)
            return step(ds)

        def spy_fused(batches):
            seen.extend(b.features for b in batches)
            return fused(batches)

        net._dispatch_batch, net._fused_dispatch = spy_step, spy_fused
        observe.get_flight()        # span recording on (the default)
        store = get_span_store()
        n0 = store.count
        net.fit(x, y, epochs=1, batch_size=16, steps_per_dispatch=k)

        assert len(seen) == 3 and net.iteration == 3
        assert all(isinstance(f, jax.Array) and f.dtype == x.dtype
                   for f in seen)
        puts = [e for e in store.events(n0) if e["name"] == "data.put"]
        assert [p["attrs"]["bytes"] for p in puts] == \
            [x[:16].nbytes + y[:16].nbytes] * 3

        ref = _mlp(dtype="bfloat16")
        ref.fit(np.asarray(x, jnp.bfloat16), y, epochs=1, batch_size=16,
                steps_per_dispatch=k)
        assert all(p.dtype == jnp.bfloat16 for p in
                   jax.tree_util.tree_leaves(net.params_tree))
        assert _max_param_diff(net.params_tree, ref.params_tree) == 0.0


# ------------------------------------------------- async iterator hygiene
class _ExplodingIterator(DataSetIterator):
    def __init__(self, good_batches=2):
        self._good = good_batches
        self._i = 0

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= self._good:
            raise RuntimeError("etl exploded")
        self._i += 1
        x = np.zeros((4, 8), np.float32)
        y = np.zeros((4, 3), np.float32)
        return DataSet(x, y)


class TestAsyncIterator:
    def test_worker_exception_reraised_on_next(self):
        it = AsyncDataSetIterator(_ExplodingIterator(2), prefetch=1)
        got = []
        with pytest.raises(RuntimeError, match="etl exploded"):
            for ds in it:
                got.append(ds)
        assert len(got) <= 2

    def test_error_fails_fast_before_buffered_batches(self):
        # With a big prefetch buffer the error must still surface promptly
        # on the NEXT next() call after the pump dies, not after the
        # consumer drains every buffered batch.
        it = AsyncDataSetIterator(_ExplodingIterator(4), prefetch=8)
        i = iter(it)
        time.sleep(0.3)     # let the pump hit the error with batches queued
        with pytest.raises(RuntimeError, match="etl exploded"):
            for _ in range(8):
                next(i)

    def test_close_joins_worker_thread(self):
        x, y = _data(64)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, 8), prefetch=2)
        i = iter(it)
        next(i)
        t = it._thread
        assert t is not None and t.is_alive()
        it.close()
        assert not t.is_alive()
        assert it._thread is None

    def test_context_manager_closes(self):
        x, y = _data(32)
        with AsyncDataSetIterator(ArrayDataSetIterator(x, y, 8)) as it:
            n = sum(1 for _ in it)
        assert n == 4
        assert it._thread is None

    def test_exhaustion_then_reuse(self):
        x, y = _data(32)
        it = AsyncDataSetIterator(ArrayDataSetIterator(x, y, 8))
        assert sum(1 for _ in it) == 4
        assert sum(1 for _ in it) == 4


# ------------------------------------------------ iterable fit regression
class TestIterableFit:
    def test_fit_list_of_datasets_multi_epoch(self):
        x, y = _data(32)
        batches = [DataSet(x[:16], y[:16]), DataSet(x[16:], y[16:])]
        net = _mlp()
        net.fit(batches, epochs=3)
        assert net.iteration == 6

    def test_fit_generator_replays_across_epochs(self):
        x, y = _data(32)

        def gen():
            yield DataSet(x[:16], y[:16])
            yield DataSet(x[16:], y[16:])

        net = _mlp()
        net.fit(gen(), epochs=2)
        assert net.iteration == 4

    def test_as_iterator_coercions(self):
        x, y = _data(16)
        assert isinstance(as_iterator([DataSet(x, y)]),
                          IterableDataSetIterator)
        assert isinstance(as_iterator(iter([DataSet(x, y)])),
                          IterableDataSetIterator)
        assert isinstance(as_iterator(x, y, 8), ArrayDataSetIterator)


# ------------------------------------------------------ executor plumbing
class TestExecutorHooks:
    def test_skip_and_stop_sentinels(self):
        from deeplearning4j_tpu.optim.executor import SKIP, STOP

        net = _mlp()
        x, y = _data(64)
        it = ArrayDataSetIterator(x, y, 16)
        seen = []

        def before(bi, ds):
            seen.append(bi)
            if bi == 0:
                return SKIP
            if bi == 3:
                return STOP
            return ds

        ex = TrainingExecutor(net, step=net._dispatch_batch,
                              before_batch=before)
        ex.run(it, 1)
        assert ex.stopped
        assert net.iteration == 2      # batches 1 and 2 only
        assert seen == [0, 1, 2, 3]
