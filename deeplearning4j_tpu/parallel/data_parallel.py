"""ParallelWrapper — sharded-jit multi-device trainer.

Reference parity: `parallelism/ParallelWrapper.java` (SURVEY §3.3): the
reference round-robins minibatches to N replica threads and averages
params/updater state every `averagingFrequency` iterations (AVERAGING mode)
or exchanges threshold-quantized gradients (SHARED_GRADIENTS mode). On TPU
the whole construct is ONE jitted train step over a mesh: the global batch
is sharded over the `data` axis, params are replicated (or FSDP-sharded via
rules), and XLA emits a single fused allreduce over ICI for the gradients —
mathematically the reference's averaging with frequency 1, without
quantization (ICI bandwidth makes 1-bit compression pointless — SURVEY §5).

Works over MultiLayerNetwork and ComputationGraph. Same API shape as the
reference: wrap a model, call fit(iterator).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import (
    DevicePrefetchIterator, as_iterator,
)
from deeplearning4j_tpu.optim.executor import TrainingExecutor
from deeplearning4j_tpu.optim.recovery import RecoveryPlan, run_with_recovery
from deeplearning4j_tpu.observe.trace import span
from deeplearning4j_tpu.optim.step import build_step
from deeplearning4j_tpu.parallel.distributed import (
    put_global, put_global_batch,
)
from deeplearning4j_tpu.parallel.mesh import (
    AXIS_DATA, MeshContext, make_mesh,
)
from deeplearning4j_tpu.parallel.ring_attention import SeqCtxJitCache
from deeplearning4j_tpu.parallel.sharding import ShardingRules


class ParallelWrapper(SeqCtxJitCache):
    """Data-parallel trainer over a mesh.

    Kwargs mirror the reference Builder (`ParallelWrapper.java:562-715`)
    where meaningful: `prefetch_buffer` maps to async-iterator depth;
    `workers` is implied by the mesh's data-axis size. Gradient averaging is
    exact and per-step (allreduce), i.e. averagingFrequency=1 semantics.
    `param_rules` opts into FSDP/ZeRO-style parameter+optimizer sharding
    (reference precedent: none — extension).

    Placement comes from ONE `parallel.mesh.MeshContext` (the sharding
    spine): pass a prebuilt `spine`, or let the wrapper assemble one from
    `mesh`/`param_rules`/`batch_axis`. By contract the spine shards the
    optimizer moments across the replica axis even when params replicate
    (weight-update sharding, ~data_size× less optimizer HBM per device);
    `shard_opt_state=False` is the escape hatch back to replicated
    moments (see PERF_NOTES — replicating them is a regression)."""

    def __init__(self, net, *, mesh: Optional[Mesh] = None,
                 param_rules: Optional[ShardingRules] = None,
                 prefetch_buffer: int = 2,
                 batch_axis: str = AXIS_DATA,
                 spine: Optional[MeshContext] = None,
                 shard_opt_state: bool = True):
        if net.params_tree is None:
            raise RuntimeError("Model must be init()ed before wrapping")
        if getattr(net.conf, "optimization_algo",
                   "stochastic_gradient_descent") != \
                "stochastic_gradient_descent":
            raise ValueError(
                "ParallelWrapper trains with the sharded SGD step; "
                f"optimization_algo={net.conf.optimization_algo!r} is a "
                "full-batch single-device solver — fit the model directly")
        self.net = net
        # mesh, shardings and the placement of the net's state on them
        with span("wrapper.init", wrapper=type(self).__name__):
            if spine is None:
                spine = MeshContext(
                    mesh if mesh is not None else make_mesh(),
                    param_rules, batch_axis=batch_axis,
                    shard_opt_state=shard_opt_state)
            self.spine = spine
            self.mesh = spine.mesh
            self.batch_axis = spine.batch_axis
            self.param_rules = spine.rules
            self.prefetch = prefetch_buffer
            self.last_batch_index = -1  # in-epoch position (elastic resume)
            self.stopped_early = False  # did the last fit() stop via stop_fn?

            self.data_size = spine.data_size
            # Multi-controller: each process feeds a host-LOCAL slice of
            # every batch; padding must make the local slice divide the
            # local devices.
            self._nproc = jax.process_count()
            self._local_divisor = max(1, self.data_size // self._nproc)

            self._rep = spine.replicated
            self._params_sh = spine.param_shardings(net.params_tree)
            self._opt_sh = spine.opt_shardings(
                net.updater_state, self._moment_keys())
            net.params_tree = jax.tree_util.tree_map(
                put_global, net.params_tree, self._params_sh)
            net.updater_state = jax.tree_util.tree_map(
                put_global, net.updater_state, self._opt_sh)
            if net.state_tree:
                net.state_tree = jax.tree_util.tree_map(
                    lambda x: put_global(x, self._rep), net.state_tree)

    # ------------------------------------------------------- shardings
    def _moment_keys(self):
        """State keys the spine may replica-shard: what this net's actual
        updaters declare, or every built-in moment key as the fallback."""
        ups = getattr(self.net, "_layer_updaters", None)
        if not ups:
            return None
        return frozenset(k for u in ups.values()
                         for k in getattr(u, "sharded_state", ()))

    def _param_tree_sharding(self, tree):
        """NamedSharding tree matching `tree`'s structure (spine rules at
        the leaf key). Kept as the wrapper-level seam; placement itself
        lives in `MeshContext`."""
        return self.spine.param_shardings(tree)

    def _batch_sharding_like(self, x):
        return self.spine.batch_sharding_like(x)

    # ------------------------------------------------------- step build
    def _get_step(self, key, example_args):
        if key in self._jit_cache:
            return self._jit_cache[key]
        # (params, opt, states, step, features, labels, fmask, lmask, rng)
        in_sh = (self._params_sh, self._opt_sh, self._rep, self._rep,
                 *map(self._batch_sharding_like, example_args[4:8]),
                 self._rep)
        # (params, opt, states, loss): out_shardings pin the donated
        # params/opt buffers to their input placement — the moments stay
        # replica-sharded through the update instead of silently
        # re-replicating (the regression the perf gate's
        # opt_state_shard_factor budget exists to catch).
        out_sh = (self._params_sh, self._opt_sh, self._rep, self._rep)
        return build_step(self.net.make_step_fn, cache=self._jit_cache,
                          key=key, name="ParallelWrapper._step",
                          in_shardings=in_sh, out_shardings=out_sh)

    # -------------------------------------------------------------- fit
    def _pad_to_divisible(self, ds):
        div = self._local_divisor if self._nproc > 1 else self.data_size
        b = ds.num_examples()
        if b % div == 0:
            return ds
        pad = div - (b % div)
        idx = np.concatenate([np.arange(b), np.zeros(pad, np.int64)])
        if isinstance(ds, MultiDataSet):
            return MultiDataSet(
                [f[idx] for f in ds.features], [l[idx] for l in ds.labels],
                None if not ds.features_masks else
                [None if m is None else m[idx] for m in ds.features_masks],
                None if not ds.labels_masks else
                [None if m is None else m[idx] for m in ds.labels_masks])
        sl = lambda a: None if a is None else a[idx]
        return DataSet(ds.features[idx], sl(ds.labels),
                       sl(ds.features_mask), sl(ds.labels_mask))

    def fit(self, data, labels=None, *, epochs: int = 1,
            batch_size: int = 128, checkpointer=None,
            checkpoint_every: int = 1, resume=None,
            stop_fn=None, preemption=None, steps_per_dispatch: int = 1,
            device_prefetch: bool = True, sync_every: int = 0):
        """Reference: `ParallelWrapper.fit(DataSetIterator):409`. Partial
        final batches are padded by repetition to keep XLA shapes static.

        Multi-controller (jax.process_count() > 1): `data` and
        `batch_size` are PER-PROCESS — each controller feeds its host-local
        slice and the global batch is their concatenation in process order
        (global batch = batch_size * process_count). Pass GLOBAL sizes to
        DistributedTrainingMaster.execute_training instead, which shards
        and divides for you.

        Recovery (shared `optim/recovery.RecoveryPlan` — same semantics as
        `MultiLayerNetwork.fit`): `checkpointer` (a ShardedCheckpointer)
        saves sharded snapshots every `checkpoint_every` iterations, async.
        `resume` takes the position dict returned by
        `ShardedCheckpointer.restore_into_wrapper`, or `"auto"` to restore
        the newest committed step with this wrapper's shardings — training
        continues mid-epoch from the exact batch/rng/step, and `epochs`
        counts TOTAL epochs over the whole (resumed) run so an interrupted
        fit(epochs=N) is finished by the same call. `stop_fn` /
        `preemption=True` end training cleanly at a batch boundary — the
        preemption seam used by ElasticTrainer.

        Async-dispatch knobs (see MultiLayerNetwork.fit / PERF_NOTES):
        `device_prefetch` pre-shards batch N+1 across the mesh while batch
        N computes (single-controller only — multi-controller feeding goes
        through `put_global_batch`); `steps_per_dispatch=K` fuses K batches
        into one `lax.scan` dispatch. Fusion now COMPOSES with recovery:
        checkpoints land at scan-window boundaries (where params are
        consistent) and a resume replays into a partial window per-step."""
        net = self.net

        def prepare(ds):
            ds = self._pad_to_divisible(ds)
            net.last_batch_size = ds.num_examples()
            return ds

        # PW always runs under a plan: padding needs before_batch anyway,
        # and last_batch_index must track even checkpointer-less fits
        # (ElasticTrainer reads it after a stop)
        plan = RecoveryPlan(
            net, checkpointer=checkpointer, checkpoint_every=checkpoint_every,
            resume=resume, stop_fn=stop_fn, preemption=preemption,
            prepare=prepare,
            restore_fn=(lambda: checkpointer.restore_into_wrapper(self))
            if checkpointer is not None else None)

        if isinstance(data, MultiDataSet):
            iterable: Any = [data]
        else:
            iterable = as_iterator(data, labels, batch_size)
            if self.prefetch:
                iterable = iterable.async_(self.prefetch)
        if device_prefetch and self._nproc == 1:
            # Pad on host, then land every leaf pre-sharded across the
            # mesh one batch ahead of compute — the spine's batch
            # placement in ONE device_put per leaf.
            iterable = DevicePrefetchIterator(
                iterable, depth=max(2, int(steps_per_dispatch)),
                put_fn=self.spine.put_batch,
                transform=self._pad_to_divisible)

        def epoch_start():
            plan.epoch_start()
            self.last_batch_index = plan.last_batch_index

        def after_dispatch(bi):
            plan.after_dispatch(bi)
            self.last_batch_index = plan.last_batch_index

        net._loss_tracker.sync_every = int(sync_every)
        from deeplearning4j_tpu.observe import get_flight, get_registry

        reg = get_registry()
        reg.gauge("train_replicas").set(self.mesh.devices.size)
        reg.gauge("train_steps_per_dispatch").set(steps_per_dispatch)
        # multi-replica fits are where HBM headroom actually bites
        # (replicated params + updater state per device): breadcrumb the
        # topology so a flight dump names the mesh it died on
        get_flight().record("parallel_fit", replicas=int(self.mesh.devices.size),
                            steps_per_dispatch=int(steps_per_dispatch),
                            processes=int(self._nproc),
                            mesh_axes={str(a): int(self.mesh.shape[a])
                                       for a in self.mesh.axis_names},
                            opt_state_sharded=bool(
                                self.spine.shard_opt_state))
        execu = TrainingExecutor(
            net, step=self._step, fused_step=self._fused_step,
            can_fuse=self._can_fuse, steps_per_dispatch=steps_per_dispatch,
            before_batch=plan.before_batch, after_dispatch=after_dispatch,
            epoch_start=epoch_start, epoch_end=plan.epoch_end,
            mesh_ctx=self.spine)
        run_with_recovery(execu, plan, iterable, epochs)
        self.last_batch_index = plan.last_batch_index
        self.stopped_early = execu.stopped  # authoritative for ElasticTrainer
        return net

    def _put_batch(self, batch):
        """Multi-controller feed: lift this process's local slice of every
        leaf into the global batch array (concatenation over processes)."""
        return jax.tree_util.tree_map(
            lambda x: put_global_batch(x, self.spine.batch_sharding(x.ndim)),
            batch)

    @staticmethod
    def _shape_key(kind, batch):
        """Cache key of a step over `batch`: names and ranks, which the
        in_shardings are built from."""
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        return (kind, treedef, tuple(x.ndim for x in leaves))

    def _step(self, ds):
        net = self.net
        net._rng, k = jax.random.split(net._rng)
        # Multi-controller: keep the local slice on host (numpy) so
        # put_global_batch uploads once — no device round-trip.
        batch = net._batch_args(ds, host=self._nproc > 1)
        if self._nproc > 1:
            step = put_global(np.int32(net.iteration), self._rep)
            k = put_global(k, self._rep)
            batch = self._put_batch(batch)
        else:
            step = jnp.asarray(net.iteration, jnp.int32)
        args = (net.params_tree, net.updater_state, net.state_tree, step,
                *batch, k)
        fn = self._get_step(self._shape_key("step", batch), args)
        (net.params_tree, net.updater_state, net.state_tree, loss, *_
         ) = fn(*args)
        # Deferred sync: replicated device scalar; LossTracker materializes.
        return loss

    # --------------------------------------------------- fused dispatch
    def _can_fuse(self, ds) -> bool:
        """Multi-controller feeding goes through put_global_batch with
        per-step host staging — fusion is single-controller only."""
        return self._nproc == 1

    def _stacked_sharding(self, ndim: int):
        """(K, batch, ...) stack: scan axis replicated, batch sharded."""
        return NamedSharding(
            self.mesh, P(None, self.batch_axis, *([None] * (ndim - 2))))

    def _get_fused_step(self, key, example_args):
        if key in self._jit_cache:
            return self._jit_cache[key]
        # Both ends of the K-step scan are pinned: the partitioner must
        # carry the replica-sharded moments through the whole window and
        # hand them back in place — without the explicit in_shardings it
        # re-replicates the carry and the donated moment buffers become
        # unusable (a reshard + 2x moment HBM per dispatch window).
        # (params, opt, states, step0, rng, feats, labs, fms, lms)
        in_sh = (self._params_sh, self._opt_sh, self._rep, self._rep,
                 self._rep,
                 *jax.tree_util.tree_map(
                     lambda x: self._stacked_sharding(x.ndim),
                     example_args[5:9]))
        # (params, opt, states, rng, losses)
        out_sh = (self._params_sh, self._opt_sh, self._rep, self._rep,
                  self._rep)
        return build_step(self.net.make_step_fn, fused=True,
                          cache=self._jit_cache, key=key,
                          name="ParallelWrapper._fused_step",
                          in_shardings=in_sh, out_shardings=out_sh)

    def _fused_step(self, batches):
        """K pre-sharded batches → one sharded `lax.scan` dispatch."""
        net = self.net
        # host batches come stacked as numpy, so this device_put is the
        # single host→device hop per tensor
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._stacked_sharding(x.ndim)),
            net._stacked_batch_args(batches))
        args = (net.params_tree, net.updater_state, net.state_tree,
                np.int32(net.iteration), net._rng, *stacked)
        fn = self._get_fused_step(
            self._shape_key(("fused", len(batches)), stacked), args)
        (net.params_tree, net.updater_state, net.state_tree, net._rng,
         losses) = fn(*args)
        return losses
