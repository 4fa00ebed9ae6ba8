"""Async-dispatch training executor: the shared fit-loop engine.

Reference parity: the reference's fit loops (`MultiLayerNetwork.fit:1046`,
`ComputationGraph.fit:778`, `ParallelWrapper.fit:409`) each re-implement the
same epoch/listener/score plumbing AND block the dispatch pipeline every
step reading the scalar score off-device. Here that plumbing lives in ONE
executor with TPU-native dispatch semantics (PyGraph, arXiv:2503.19779, is
the GPU analogue — keep the accelerator queue full, stop paying host
round-trips per step):

- **Deferred loss sync** (`LossTracker`): the step functions return the
  loss as a DEVICE array; the tracker only materializes a Python float on
  demand (``score_`` access, a listener calling ``float(score)``, an
  every-N ``sync_every`` cadence, or epoch end). The steady-state hot loop
  performs ZERO mandatory host syncs — JAX's async dispatch keeps N steps
  in flight while the host runs ahead enqueueing more.
- **Fused multi-step execution** (`steps_per_dispatch=K`): K same-shape
  batches are stacked and the donated train step runs under `lax.scan` in
  a single dispatch — the TPU analogue of CUDA-graph capture. The executor
  transparently falls back to per-step dispatch for batches that need
  per-step visibility (tBPTT chunking, non-SGD solvers, shape changes,
  resume/stop/checkpoint seams).
- **Listener contract**: ``iteration_done`` receives the *device* loss;
  listeners that read it (``float(score)``) pay the sync they ask for,
  listeners that don't are free. Epoch end always materializes once so
  ``score_`` is a float at every epoch boundary (≤1 sync/epoch).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.observe import get_registry, span
from deeplearning4j_tpu.observe.trace import flush_span_log
from deeplearning4j_tpu.observe.commsmon import get_reshard_witness
from deeplearning4j_tpu.observe.devicemon import maybe_start_monitor
from deeplearning4j_tpu.observe.flight import get_flight
from deeplearning4j_tpu.optim.step import (
    COUNTER_PREFIXES, COUNTER_STEPS, counters_of,
)

__all__ = ["LossTracker", "TrainingExecutor", "SKIP", "STOP"]

# before_batch sentinels: skip this batch (resume replay) / stop cleanly
SKIP = object()
STOP = object()
_END = object()     # the iterator is exhausted


def _is_device_array(x) -> bool:
    return isinstance(x, jax.Array)


class LossTracker:
    """Deferred-sync score holder.

    Stores the most recent loss as whatever the step returned (device
    array or float) and converts to a Python float lazily, caching the
    result. ``host_syncs`` counts actual device→host materializations —
    the instrumentation seam the perf guard asserts on.

    ``sync_every=N`` forces a materialization every N updates (the
    listener-cadence knob); 0 (default) defers until ``value`` is read or
    ``materialize()`` is called (the executor calls it once per epoch).
    """

    def __init__(self, sync_every: int = 0):
        self.sync_every = int(sync_every)
        self._raw: Any = None
        self._cached: Optional[float] = None
        self._since_sync = 0
        self.host_syncs = 0     # device materializations (perf-guard seam)
        self.updates = 0

    def set(self, loss) -> None:
        """Overwrite the tracked loss without counting an update (the
        ``score_`` setter seam — solvers/earlystopping assign floats)."""
        self._raw = loss
        self._cached = None

    def update(self, loss) -> None:
        self.set(loss)
        self.updates += 1
        self._since_sync += 1
        if self.sync_every and self._since_sync >= self.sync_every:
            self.materialize()

    @property
    def value(self) -> Optional[float]:
        """The tracked loss as a float — THIS is the sync point."""
        if self._raw is None:
            return None
        if self._cached is None:
            if _is_device_array(self._raw):
                self.host_syncs += 1
            self._cached = float(self._raw)
            self._since_sync = 0
        return self._cached

    def peek(self):
        """The loss without forcing a sync (device array if never read)."""
        return self._raw if self._cached is None else self._cached

    def materialize(self) -> Optional[float]:
        return self.value


def _arr_sig(a):
    return None if a is None else (tuple(a.shape), str(getattr(a, "dtype", "")))


def batch_signature(ds):
    """Structural signature of a DataSet/MultiDataSet — two batches fuse
    into one `lax.scan` dispatch only when their signatures match (same
    shapes, dtypes, and mask presence ⇒ same compiled program)."""
    if hasattr(ds, "features_masks"):   # MultiDataSet
        return ("m",
                tuple(_arr_sig(f) for f in ds.features),
                tuple(_arr_sig(l) for l in ds.labels),
                tuple(_arr_sig(x) for x in (ds.features_masks or ())),
                tuple(_arr_sig(x) for x in (ds.labels_masks or ())))
    return ("d", _arr_sig(ds.features), _arr_sig(ds.labels),
            _arr_sig(ds.features_mask), _arr_sig(ds.labels_mask))


def _labelled(value, layer: str):
    """(value, labels) of one counter of one layer: a scalar under
    `layer=`, a vector (`exit_mass`: a value a pass) under `pass=` too."""
    if value.ndim == 0:
        return [(value.item(), {"layer": layer})]
    return [(one, {"layer": layer, "pass": str(i)})
            for i, one in enumerate(value.tolist(), start=1)]


@jax.jit
def _pack(leaves):
    """A list of small arrays as one vector a dtype, on the device."""
    by = {}
    for leaf in leaves:
        by.setdefault(leaf.dtype.name, []).append(leaf.ravel())
    return {kind: jnp.concatenate(v) for kind, v in by.items()}


def _read_packed(tree):
    """`jax.device_get(tree)` of a tree of small leaves in one transfer a
    dtype: `_pack` on the device (one small program, compiled at the
    first epoch's end, the warm-up's), cut apart again here. A scalar a
    transfer read 88 us each on the chip: 16.7 ms for
    `granite_4_0_h_small_fit`'s 188 (chip run, PR 52)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    # graft: allow-sync(the epoch has just synchronised; one read)
    packed, at, out = jax.device_get(_pack(leaves)), {}, []
    for leaf in leaves:
        kind, start = leaf.dtype.name, at.get(leaf.dtype.name, 0)
        at[kind] = start + leaf.size
        out.append(packed[kind][start:at[kind]].reshape(leaf.shape))
    return treedef.unflatten(out)


def _publish_routing_counters(net) -> None:
    """The counters of every layer that keeps some in its state
    (`optim/step.COUNTER_PREFIXES`: an expert layer's routing, a
    selecting attention's blocks, `ssm_chunk_carry`, `exit_entropy` and
    `exit_mass`, `main_loss` and `mtp_loss`), read once where the epoch
    has just synchronised (`_read_packed`), as the span `fit.counters`.
    For a counter `c` of a layer: the gauge `c{layer=}`, the LAST step's
    value; and from the sums the step keeps beside it
    (`optim/step.with_counter_sums`) the registry counters
    `c_total{layer=}` and `counter_steps_total{layer=}` and the gauge
    `c_epoch_mean{layer=}`, the sum's growth since the last publication
    over the steps': the mean over the epoch that just ended.
    The sums at the last publication stay with the net (`_counters_seen`:
    no second read), so a net restored from a checkpoint reads, in its
    first epoch, the mean since `init()`. A net without such a layer pays
    a walk over its state's keys."""
    timed = span("fit.counters")
    with timed:
        counters = {}
        for name, st in (getattr(net, "state_tree", None) or {}).items():
            if counters_of(st):     # the counters, their sums, the steps
                counters[name] = {
                    k: v for k, v in st.items()
                    if k == COUNTER_STEPS or k.startswith(COUNTER_PREFIXES)}
        timed.attrs.update(layers=len(counters),
                           values=sum(map(len, counters.values())))
        if not counters:
            return
        reg = get_registry()
        seen = vars(net).setdefault("_counters_seen", {})
        for name, values in _read_packed(counters).items():
            then = seen.get(name, {})
            steps = max(0, int(values.get(COUNTER_STEPS, 0))
                        - int(then.get(COUNTER_STEPS, 0)))
            if steps:
                reg.counter("counter_steps_total", layer=name).inc(steps)
            for c in counters_of(values):
                for one, labels in _labelled(values[c], name):
                    reg.gauge(c, **labels).set(one)
                if c + "_sum" not in values:     # a state born without sums
                    continue
                grown = values[c + "_sum"] - then.get(c + "_sum", 0.0)
                for one, labels in _labelled(grown, name):
                    reg.counter(c + "_total", **labels).inc(max(one, 0.0))
                    if steps:
                        reg.gauge(c + "_epoch_mean",
                                  **labels).set(one / steps)
            seen[name] = values


class TrainingExecutor:
    """The shared epoch/batch/listener loop with async-dispatch semantics.

    The model (or parallel trainer) supplies the step callables; the
    executor owns iteration bookkeeping, the fused-dispatch buffer,
    listener fan-out, and the epoch-end materialization. Each segment of
    a step is timed once, by its span (`observe/trace.py`): `fit.etl`
    (the wait for the next batch; the prefetch iterator's `data.put`
    spans are its children), `fit.dispatch`, `fit.listeners`, and once an
    epoch `fit.epoch_sync` and, after it, `fit.counters` (the read and
    the publication of the layers' counters and their sums over the
    epoch's steps). The spans are `fit()`'s one recorder: the
    `train_etl_ms` / `train_dispatch_ms` histograms take their values
    from those spans' own clock reads.

    Hooks:
      step(ds) -> loss                one training step (device loss)
      fused_step(batches) -> (K,)    K stacked steps in one dispatch
      can_fuse(ds) -> bool           batch eligible for fusion
      before_batch(bi, ds) -> ds | SKIP | STOP
      after_step(bi)                 post-iteration seam (per _finish)
      after_dispatch(bi)             post-DISPATCH seam: fires once per
                                     device dispatch (per step unfused,
                                     per K-step scan window fused), at a
                                     point where params/updater/rng are a
                                     consistent snapshot — the
                                     checkpointing seam (RecoveryPlan)
      epoch_start() / epoch_end()    per-epoch trainer state

    `mesh_ctx` (a `parallel.mesh.MeshContext`) scopes the sharding spine
    over the whole loop: step-fn tracing, batch placement (the prefetch
    iterator's default put), and trace-time kernel policies all see ONE
    mesh while the executor runs.
    """

    def __init__(self, net, *, step: Callable,
                 fused_step: Optional[Callable] = None,
                 can_fuse: Optional[Callable] = None,
                 steps_per_dispatch: int = 1,
                 before_batch: Optional[Callable] = None,
                 after_step: Optional[Callable] = None,
                 after_dispatch: Optional[Callable] = None,
                 epoch_start: Optional[Callable] = None,
                 epoch_end: Optional[Callable] = None,
                 mesh_ctx=None):
        self.net = net
        self.mesh_ctx = mesh_ctx
        self.step = step
        self.fused_step = fused_step
        self.can_fuse = can_fuse or (lambda ds: False)
        self.k = max(1, int(steps_per_dispatch or 1))
        self.before_batch = before_batch
        self.after_step = after_step
        self.after_dispatch = after_dispatch
        self.epoch_start = epoch_start
        self.epoch_end = epoch_end
        self.stopped = False
        # commsmon reshard witness — None when DL4J_TPU_COMMSMON is off,
        # so the disabled hot loop pays one attribute read per dispatch
        self._reshard = get_reshard_witness()

    # ------------------------------------------------------------- loop
    def run(self, iterable, epochs: int, *, start_epoch: int = 0):
        if self.mesh_ctx is not None:
            # lazy import: parallel.mesh pulls no optim modules, but the
            # parallel package __init__ imports this one
            from deeplearning4j_tpu.parallel.mesh import use_mesh_context
            with use_mesh_context(self.mesh_ctx):
                return self._run(iterable, epochs, start_epoch=start_epoch)
        return self._run(iterable, epochs, start_epoch=start_epoch)

    def _run(self, iterable, epochs: int, *, start_epoch: int = 0):
        net = self.net
        listeners = net.listeners
        # registry handles bound once per run, to the registry active at
        # fit start; _finish only bumps them
        reg = get_registry()
        self._iter_counter = reg.counter("train_iterations")
        self._etl_hist = reg.histogram("train_etl_ms")
        self._dispatch_hist = reg.histogram("train_dispatch_ms")
        # black box + device telemetry: the flight recorder turns span
        # recording on, so a crash dump carries this run from the start
        flight = get_flight()
        maybe_start_monitor()
        try:
            with span("fit", epochs=epochs, start_epoch=start_epoch,
                      steps_per_dispatch=self.k):
                for l in listeners:
                    l.on_fit_start(net)
                self.stopped = False
                for _ in range(start_epoch, epochs):
                    with span("fit.epoch", epoch=net.epoch):
                        self._run_epoch(iterable)
                    if self.stopped:
                        break
                for l in listeners:
                    l.on_fit_end(net)
        except BaseException as e:
            # the crash the flight recorder exists for: dump the ring
            # (recent spans, compiles, device memory) next to the error
            flight.dump("training_exception", exc=e)
            raise
        finally:
            flush_span_log()
        return net

    def _run_epoch(self, iterable) -> None:
        net = self.net
        listeners = net.listeners
        if self.epoch_start is not None:
            self.epoch_start()
        for l in listeners:
            l.on_epoch_start(net, net.epoch)
        buf: List = []
        it = iter(iterable)
        bi = -1
        while True:
            # the wait for the next batch; `etl` keeps its two clock
            # reads, which also feed train_etl_ms
            etl = span("fit.etl")
            with etl:
                ds = next(it, _END)
                if ds is _END:
                    etl.attrs["exhausted"] = True
            if ds is _END:
                break
            bi += 1
            if self.before_batch is not None:
                ds = self.before_batch(bi, ds)
                if ds is SKIP:
                    continue
                if ds is STOP:
                    self.stopped = True
                    break
            fusible = (self.k > 1 and self.fused_step is not None
                       and self.can_fuse(ds))
            if fusible and buf and \
                    batch_signature(buf[0][1]) != batch_signature(ds):
                self._drain(buf)
                buf = []
            if fusible:
                buf.append((bi, ds, etl.dur_ms))
                if len(buf) == self.k:
                    self._run_fused(buf)
                    buf = []
            else:
                self._drain(buf)
                buf = []
                self._run_one(bi, ds, etl.dur_ms)
        self._drain(buf)
        if self.stopped:
            return
        for l in listeners:
            l.on_epoch_end(net, net.epoch)
        net.epoch += 1
        if self.epoch_end is not None:
            self.epoch_end()
        # the ONE guaranteed materialization per epoch: score_ is a float
        # at every epoch boundary without per-step syncs. Its span is how
        # long the device took to drain what the host had queued.
        with span("fit.epoch_sync"):
            net._loss_tracker.materialize()
        _publish_routing_counters(net)

    # ---------------------------------------------------------- helpers
    def _witness_batch(self, ds) -> None:
        """Reshard-witness seam (commsmon, GL802): before a dispatch,
        compare the batch's COMMITTED shardings against the mesh spine's
        declared batch spec. Metadata-only, and `self._reshard` is None
        whenever commsmon is off, so the hot path pays one attribute
        read."""
        mesh_ctx = self.mesh_ctx
        if mesh_ctx is None:
            return
        from deeplearning4j_tpu.observe.commsmon import check_dispatch_args
        owner = type(self.net).__name__
        spec = mesh_ctx.batch_spec      # leaf -> P(batch_axis, None, ...)
        named = {}
        for field in ("features", "labels"):
            v = getattr(ds, field, None)
            if v is not None:
                named[field] = (v, lambda leaf: spec(leaf.ndim))
        check_dispatch_args(owner, named, witness=self._reshard)

    def _run_one(self, bi, ds, etl_ms) -> None:
        """One batch through the per-step path."""
        if self._reshard is not None:
            self._witness_batch(ds)
        disp = span("fit.dispatch", iteration=self.net.iteration, batch=bi,
                    steps=1, fused=False)
        with disp:
            loss = self.step(ds)
        self._finish(bi, loss, etl_ms, disp.dur_ms)
        if self.after_dispatch is not None:
            self.after_dispatch(bi)

    def _drain(self, buf) -> None:
        """Flush a partial fusion buffer through the per-step path (a
        short tail would need its own K'-sized compile)."""
        for bi, ds, etl_ms in buf:
            self._run_one(bi, ds, etl_ms)

    def _run_fused(self, buf) -> None:
        if self._reshard is not None:
            self._witness_batch(buf[0][1])
        disp = span("fit.dispatch", iteration=self.net.iteration,
                    batch=buf[0][0], steps=len(buf), fused=True)
        with disp:
            losses = self.fused_step([ds for _, ds, _ in buf])
        # one dispatch for K steps: attribute its enqueue cost evenly
        dispatch_ms = disp.dur_ms / len(buf)
        for j, (bi, ds, etl_ms) in enumerate(buf):
            # losses[j] stays on device — indexing does not sync
            self._finish(bi, losses[j], etl_ms, dispatch_ms)
        if self.after_dispatch is not None:
            # once per scan window: params now reflect all K steps, so a
            # checkpoint here is a consistent (step, rng, cursor) snapshot
            self.after_dispatch(buf[-1][0])

    def _finish(self, bi, loss, etl_ms, dispatch_ms: float = 0.0) -> None:
        net = self.net
        net._loss_tracker.update(loss)
        net.iteration += 1
        self._iter_counter.inc()
        self._etl_hist.observe(etl_ms)
        # host-side dispatch wall time per step: the training-side
        # series the sampler turns into train_dispatch_ms:p99
        self._dispatch_hist.observe(dispatch_ms)
        with span("fit.listeners"):
            for l in net.listeners:
                if hasattr(l, "set_etl_time"):
                    l.set_etl_time(etl_ms)
                l.iteration_done(net, net.iteration, net.epoch,
                                 net._loss_tracker.peek())
            if self.after_step is not None:
                self.after_step(bi)
