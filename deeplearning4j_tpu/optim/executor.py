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

from deeplearning4j_tpu.observe import get_registry, reqtrace, span
from deeplearning4j_tpu.observe.trace import flush_span_log, get_span_store
from deeplearning4j_tpu.observe.commsmon import get_reshard_witness
from deeplearning4j_tpu.observe.devicemon import maybe_start_monitor
from deeplearning4j_tpu.observe.flight import get_flight
from deeplearning4j_tpu.observe.watchdog import get_watchdog

__all__ = ["LossTracker", "TrainingExecutor", "SKIP", "STOP"]

# before_batch sentinels: skip this batch (resume replay) / stop cleanly
SKIP = object()
STOP = object()
_END = object()     # the iterator is exhausted


def _is_device_array(x) -> bool:
    import jax

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


def _publish_routing_counters(net) -> None:
    """The last step's counters of every layer that keeps some in its
    state (`parallel/moe.ExpertFeedForward`'s routing: the `moe_*`
    scalars, `moe_tokens_held` of a layer that routes by groups among
    them; a `MultiHeadAttention` with a block selection: the
    `sparse_blocks_*` scalars; a `SelectiveStateSpace`: `ssm_chunk_carry`,
    a share and so a float; an `ExitGatedOutputLayer`: `exit_entropy`
    and `exit_mass`, a value a pass and so `exit_mass{layer=, pass=}`; a
    `MultiTokenOutputLayer`: `main_loss` and `mtp_loss`, its two terms)
    out of the net's layer state into the gauges `<counter>{layer=}`.
    Called where the epoch has just synchronised with the device; a net
    without such a layer pays a walk over its state's keys."""
    counters = {}
    for name, st in (getattr(net, "state_tree", None) or {}).items():
        if isinstance(st, dict):
            own = {k: v for k, v in st.items()
                   if k.startswith(("moe_", "sparse_blocks_", "ssm_",
                                    "exit_", "mtp_", "main_loss"))}
            if own:
                counters[name] = own
    if counters:
        import jax

        # graft: allow-sync(the epoch has just synchronised; one read)
        for name, values in jax.device_get(counters).items():
            for key, value in values.items():
                if value.ndim == 0:
                    get_registry().gauge(key, layer=name).set(value.item())
                    continue
                for i, one in enumerate(value.tolist(), start=1):
                    get_registry().gauge(key, layer=name,
                                         **{"pass": str(i)}).set(one)


class TrainingExecutor:
    """The shared epoch/batch/listener loop with async-dispatch semantics.

    The model (or parallel trainer) supplies the step callables; the
    executor owns iteration bookkeeping, the fused-dispatch buffer,
    listener fan-out, and the epoch-end materialization. Each segment of
    a step is timed once, by its span (`observe/trace.py`): `fit.etl`
    (the wait for the next batch; the prefetch iterator's `data.put`
    spans are its children), `fit.dispatch`, `fit.listeners`, and once an
    epoch `fit.epoch_sync`. The `train_etl_ms` / `train_dispatch_ms`
    histograms take their values from those spans' own clock reads; the
    sampled `train.epoch` request trace reads the span store when an
    epoch ends.

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
        # per-epoch request trace (reqtrace): None when sampling is off
        self._rt = None
        self._rt_from = 0       # the span store's count at the epoch's start
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
                    ep = net.epoch
                    # one sampled trace per epoch, built from the epoch's
                    # fit.dispatch spans when it ends
                    self._rt = reqtrace.new_trace("train.epoch")
                    self._rt_from = get_span_store().count
                    with span("fit.epoch", epoch=ep):
                        self._run_epoch(iterable)
                    if self.stopped:
                        self._finish_epoch_trace(ep, stopped=True)
                        break
                    self._finish_epoch_trace(ep)
                for l in listeners:
                    l.on_fit_end(net)
        except BaseException as e:
            # close the epoch trace first so the flight dump's trace
            # block carries the crashed epoch's dispatch windows
            self._finish_epoch_trace(net.epoch, error=type(e).__name__)
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
    def _finish_epoch_trace(self, epoch: int, **attrs) -> None:
        """Close the per-epoch request trace (None-safe; resets _rt): one
        `train.dispatch` span keyed (epoch, step-window) per `fit.dispatch`
        span the store holds of this epoch, under the root. A window's
        duration is the host ENQUEUE time, never a device wait. When the
        comm ledger has priced this owner's compiled programs, each
        window also carries the owner-level collective totals (comm_ops /
        comm_bytes): host-side metadata from the watchdog."""
        rt, self._rt = self._rt, None
        if rt is None:
            return
        store = get_span_store()
        comm = self._comm_totals()
        for ev in store.events(self._rt_from):
            if ev["name"] != "fit.dispatch":
                continue
            a = ev["attrs"]
            lo, hi = a["batch"], a["batch"] + a["steps"] - 1
            extra = {} if comm is None else {
                "comm_ops": comm["ops"], "comm_bytes": comm["wire_bytes"]}
            reqtrace.record_span(
                rt.trace_id, "train.dispatch", parent_id=rt.span_id,
                ts=ev["ts"], dur_ms=ev["dur_ms"], epoch=epoch,
                window=f"{epoch}:{lo}-{hi}", steps=a["steps"],
                fused=a["fused"], **extra)
        reqtrace.finish_root(rt, epoch=epoch, iteration=self.net.iteration,
                             steps_per_dispatch=self.k, **attrs)

    def _comm_totals(self) -> Optional[dict]:
        """Owner-level compiled-collective totals for the net's active
        jit cache, or None when nothing was priced (ledger disabled,
        probe not fired yet, owner without a WatchedJitCache)."""
        try:
            tag = getattr(self.net._jit_cache, "owner_tag", None)
            if tag is None:
                return None
            return get_watchdog().owner_comm_totals(tag)
        # graft: allow(GL403): span decoration is best-effort by design
        except Exception:
            return None

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
