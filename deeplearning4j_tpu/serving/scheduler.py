"""Continuous-batching scheduler + admission control for the serving
control plane.

Replaces the fixed collect-then-run loop (`ParallelInference._collector`,
which waits up to `max_wait_ms` hoping to fill a batch) with the
scheduling discipline real inference servers use: a request joins the
very next device dispatch as soon as a slot frees. While a slot is busy
the queue naturally accumulates arrivals, so batches grow under load and
shrink to singletons when idle — occupancy tracks load with no tuned
wait timer (no chip run has compared its p99 with collect-then-run's:
ROADMAP D8).

Admission control is a bounded queue with a configurable policy:

  block    — the submitting thread waits (bounded by `block_timeout_s`)
             for space; backpressure propagates to the HTTP client
  shed     — a full queue rejects immediately (`RequestShedError`,
             mapped to HTTP 503)
  deadline — every request carries a deadline (per-request or
             `default_deadline_ms`); admission waits only until the
             deadline (`DeadlineExceededError`, HTTP 504)

Deadlines propagate INTO the scheduler: a request that expires while
queued is failed and never dispatched — the accelerator never burns a
batch slot on work nobody is waiting for.

Shutdown contract (extends `parallel/inference.py`'s drain guarantee):
every submitted request either completes or fails with an explicit
error; nothing hangs. Queued requests are failed with
`SchedulerClosedError`; the batch in flight runs to completion.
"""

from __future__ import annotations

import contextvars
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Dict, Optional

import numpy as np

from deeplearning4j_tpu.observe import reqtrace
from deeplearning4j_tpu.serving.metrics import ServingStats


class AdmissionPolicy:
    BLOCK = "block"
    SHED = "shed"
    DEADLINE = "deadline"

    ALL = (BLOCK, SHED, DEADLINE)


class RequestShedError(RuntimeError):
    """Admission queue full under the shed policy (HTTP 503)."""


class DeadlineExceededError(RuntimeError):
    """Request deadline passed before completion (HTTP 504)."""


class SchedulerClosedError(RuntimeError):
    """Scheduler shut down before (or while) holding this request."""


class WorkerCrashError(RuntimeError):
    """A slot worker crashed more than `max_worker_restarts` times in a
    row while holding this batch; the batch was failed rather than
    retried forever. The slot itself stays alive for new work."""


class _WorkerCrashed(BaseException):
    """Internal: carries the in-flight batch out of a crashed worker
    iteration to the supervisor (BaseException so nothing downstream
    accidentally swallows it)."""

    def __init__(self, batch, cause: BaseException):
        super().__init__(str(cause))
        self.batch = batch
        self.cause = cause


class _Request:
    __slots__ = ("x", "fut", "model", "deadline", "t_enqueue", "ctx",
                 "seq_key", "trace", "t_wall")

    def __init__(self, x, fut, model, deadline, ctx, seq_key, trace=None):
        self.x = x
        self.fut = fut
        self.model = model
        self.deadline = deadline
        self.t_enqueue = time.monotonic()
        self.ctx = ctx
        self.seq_key = seq_key
        # request-trace seam: None on the sampled-off fast path (no span
        # objects allocated); t_wall anchors the queue.wait span
        self.trace = trace
        self.t_wall = time.time() if trace is not None else 0.0


class ContinuousBatchingScheduler:
    """Slot workers pulling per-model FIFO queues; one registry behind.

    `registry` needs `acquire(name) -> entry` / `release(entry)` with
    `entry.run_batch(xs)` (the ModelRegistry contract; unit tests pass
    fakes). `slots` is the number of concurrent device dispatch lanes —
    1 for a single mesh, >1 when the runner multiplexes devices.
    """

    def __init__(self, registry, stats: Optional[ServingStats] = None, *,
                 max_batch_size: int = 64, queue_capacity: int = 256,
                 policy: str = AdmissionPolicy.BLOCK,
                 default_deadline_ms: Optional[float] = None,
                 slots: int = 1, block_timeout_s: float = 30.0,
                 max_worker_restarts: int = 3,
                 worker_restart_backoff_s: float = 0.05):
        if policy not in AdmissionPolicy.ALL:
            raise ValueError(
                f"admission policy must be one of {AdmissionPolicy.ALL}, "
                f"got {policy!r}")
        if policy == AdmissionPolicy.DEADLINE and not default_deadline_ms:
            raise ValueError(
                "deadline admission policy requires default_deadline_ms")
        self.registry = registry
        self.stats = stats if stats is not None else ServingStats()
        self.max_batch = max_batch_size
        self.capacity = queue_capacity
        self.policy = policy
        self.default_deadline = (default_deadline_ms / 1e3
                                 if default_deadline_ms else None)
        self.block_timeout = block_timeout_s
        # worker supervision: a crashed slot restarts with doubling
        # backoff; after max_worker_restarts consecutive crashes the held
        # batch is failed (WorkerCrashError) instead of retried forever
        self.max_worker_restarts = max(0, int(max_worker_restarts))
        self.worker_restart_backoff = float(worker_restart_backoff_s)
        self._cv = threading.Condition()
        # queue state is mutated by submitters and worker threads alike;
        # declared guards let graft-lint (GL701) verify every access —
        # helpers like _take_batch stay quiet because their only call
        # sites hold self._cv (interprocedural entry-held propagation)
        # graft: guarded-by(_cv)
        self._queues: Dict[str, deque] = {}
        # graft: guarded-by(_cv)
        self._depth = 0
        # graft: guarded-by(_cv)
        self._inflight = 0
        # graft: guarded-by(_cv)
        self._closed = False
        # per-worker CURRENT crash streaks (worker thread name → count);
        # restart_streak() reads the worst one for /healthz and the SLO
        self._streaks: Dict[str, int] = {}
        # chaos seam (inject_worker_fault): raise in the next N worker
        # iterations right after a batch is taken — guarded by self._cv
        self._fault_budget = 0
        self._fault_exc = None
        self._workers = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"serving-slot-{i}")
            for i in range(max(1, slots))]
        for w in self._workers:
            w.start()

    # ---------------------------------------------------------- public
    def queue_depth(self) -> int:
        with self._cv:
            return self._depth

    def restart_streak(self) -> int:
        """Worst current consecutive-crash streak across slot workers
        (0 = healthy). Nonzero means a slot is crash-looping RIGHT NOW —
        a healthy dispatch resets its worker's streak."""
        with self._cv:
            return max(self._streaks.values(), default=0)

    def _note_streak(self, n: int) -> None:
        with self._cv:
            self._streaks[threading.current_thread().name] = n
            worst = max(self._streaks.values())
        self.stats.worker_streak(worst)

    def submit(self, model: str, x,
               deadline_ms: Optional[float] = None, *,
               trace=None) -> Future:
        """Admit one request; returns a Future resolving to the output
        rows. Raises RequestShedError / DeadlineExceededError /
        SchedulerClosedError per the admission contract.

        `trace` carries the request's TraceContext across the admission
        seam (decode sessions resubmit from scheduler worker threads, so
        the contextvar carrier alone is not enough); when omitted, the
        edge's `reqtrace.current_trace()` is picked up. Shed / expired
        requests are force-traced regardless of the sampling rate and
        the trace id is stamped on the raised exception."""
        x = np.asarray(x)
        now = time.monotonic()
        dl_s = (deadline_ms / 1e3 if deadline_ms is not None
                else self.default_deadline)
        deadline = now + dl_s if dl_s is not None else None
        if trace is None:
            trace = reqtrace.current_trace()

        from deeplearning4j_tpu.parallel.ring_attention import (
            current_sequence_mesh,
        )

        with self._cv:
            if self._closed:
                raise SchedulerClosedError("scheduler is shut down")
            if self._depth >= self.capacity:
                if self.policy == AdmissionPolicy.SHED:
                    self.stats.shed(model)
                    err = RequestShedError(
                        f"admission queue full "
                        f"({self._depth}/{self.capacity})")
                    err.trace_id = reqtrace.error_trace(
                        "request.shed", ctx=trace, model=model,
                        queue_depth=self._depth, capacity=self.capacity)
                    raise err
                limit = now + self.block_timeout
                if deadline is not None:
                    limit = min(limit, deadline)
                while self._depth >= self.capacity and not self._closed:
                    remaining = limit - time.monotonic()
                    if remaining <= 0:
                        if (deadline is not None
                                and time.monotonic() >= deadline):
                            self.stats.expired(model)
                            err = DeadlineExceededError(
                                "deadline passed waiting for admission")
                            err.trace_id = reqtrace.error_trace(
                                "request.expired", ctx=trace, model=model,
                                where="admission")
                            raise err
                        self.stats.shed(model)
                        err = RequestShedError(
                            f"admission blocked > {self.block_timeout}s")
                        err.trace_id = reqtrace.error_trace(
                            "request.shed", ctx=trace, model=model,
                            queue_depth=self._depth,
                            blocked_s=round(self.block_timeout, 3))
                        raise err
                    self._cv.wait(remaining)
                if self._closed:
                    raise SchedulerClosedError("scheduler is shut down")
            fut: Future = Future()
            req = _Request(x, fut, model, deadline,
                           contextvars.copy_context(),
                           current_sequence_mesh(), trace)
            self._queues.setdefault(model, deque()).append(req)
            self._depth += 1
            self.stats.admitted(model)
            self._cv.notify_all()
        return fut

    def output(self, model: str, x,
               deadline_ms: Optional[float] = None) -> np.ndarray:
        """Blocking submit; the synchronous convenience the HTTP handler
        uses."""
        return self.submit(model, x, deadline_ms).result()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until the queue is empty and no batch is in flight."""
        with self._cv:
            return self._cv.wait_for(
                lambda: self._depth == 0 and self._inflight == 0, timeout)

    def shutdown(self):
        """Fail everything queued with SchedulerClosedError, let the
        in-flight batch finish, stop the slot workers."""
        with self._cv:
            self._closed = True
            leftovers = [r for q in self._queues.values() for r in q]
            self._queues.clear()
            self._depth = 0
            self._cv.notify_all()
        for r in leftovers:
            if not r.fut.done():
                r.fut.set_exception(SchedulerClosedError(
                    "scheduler shut down before serving this request"))
                self.stats.completed(r.model, 0.0, ok=False)
        for w in self._workers:
            w.join(timeout=10)

    # ---------------------------------------------------------- worker
    def _take_batch(self):
        """Pop the next single-(model, seq-context) batch, FIFO-fair
        across models by oldest head request. Called under self._cv."""
        name = min((n for n, q in self._queues.items() if q),
                   key=lambda n: self._queues[n][0].t_enqueue)
        q = self._queues[name]
        batch = [q.popleft()]
        rows = batch[0].x.shape[0]
        while (q and rows < self.max_batch
               and q[0].seq_key == batch[0].seq_key):
            nxt = q.popleft()
            batch.append(nxt)
            rows += nxt.x.shape[0]
        # graft: allow(GL301): caller holds self._cv (documented contract)
        self._depth -= len(batch)
        return batch

    def inject_worker_fault(self, *, times: int = 1,
                            exc_factory=None) -> None:
        """Chaos seam: make the next `times` worker iterations crash
        right after taking a batch — the thread-death scenario the
        supervisor exists for, injectable deterministically on CPU
        (tests/test_serving_failover)."""
        from deeplearning4j_tpu.parallel.chaos import InjectedFault
        with self._cv:
            self._fault_budget = int(times)
            self._fault_exc = exc_factory or (
                lambda: InjectedFault("injected worker crash"))

    def _worker(self):
        """Supervisor: before ISSUE 6 a crash here killed the daemon
        thread silently and the slot went dark — every later request
        hung until its deadline. Now the slot survives: the held batch
        is requeued at the FRONT (order preserved), the crash is
        flight-dumped and counted (`serving_worker_restarts_total`), and
        the loop restarts after a doubling backoff. A crash LOOP is
        bounded: after `max_worker_restarts` consecutive crashes the
        held batch fails with WorkerCrashError and the slot moves on."""
        streak = [0]               # consecutive crashes; dispatch resets
        backoff = self.worker_restart_backoff
        while True:
            try:
                self._worker_loop(streak)
                return             # clean shutdown
            except _WorkerCrashed as wc:
                batch, cause = wc.batch, wc.cause
            streak[0] += 1
            self._note_streak(streak[0])
            self.stats.worker_restarted()
            # a dead worker thread is a silent serving outage (daemon
            # threads die without a traceback anyone keeps): black box
            # first, then recover
            try:
                from deeplearning4j_tpu.observe.flight import get_flight
                get_flight().dump("scheduler_worker_crash", exc=cause)
            # graft: allow(GL403): the dump is best-effort forensics;
            # the restart below is the payload
            except Exception:
                pass
            if streak[0] > self.max_worker_restarts:
                for r in batch:
                    exc = WorkerCrashError(
                        f"worker crashed {streak[0]} consecutive "
                        f"times holding this batch: {cause!r}")
                    exc.trace_id = reqtrace.error_trace(
                        "request.worker_crash", ctx=r.trace,
                        model=r.model, crashes=streak[0],
                        cause=type(cause).__name__)
                    if not r.fut.done():
                        r.fut.set_exception(exc)
                    self.stats.completed(r.model, 0.0, ok=False)
                streak[0] = 0
                self._note_streak(0)
                backoff = self.worker_restart_backoff
                continue
            if batch:
                self._requeue(batch)
            time.sleep(backoff)
            backoff = min(backoff * 2.0, 1.0)

    def _requeue(self, batch) -> None:
        """Put a crashed worker's batch back at the head of its queue
        (oldest request first, so FIFO order survives the restart)."""
        with self._cv:
            if self._closed:
                closed = list(batch)
            else:
                closed = []
                q = self._queues.setdefault(batch[0].model, deque())
                for r in reversed(batch):
                    q.appendleft(r)
                self._depth += len(batch)
            self._cv.notify_all()
        for r in closed:        # raced shutdown: fail, don't strand
            if not r.fut.done():
                r.fut.set_exception(SchedulerClosedError(
                    "scheduler shut down while recovering this request"))
            self.stats.completed(r.model, 0.0, ok=False)

    def _worker_loop(self, streak):
        while True:
            try:
                with self._cv:
                    while not self._closed and self._depth == 0:
                        self._cv.wait()
                    if self._closed:
                        return
                    batch = self._take_batch()
                    self._inflight += 1
                    if self._fault_budget > 0:
                        self._fault_budget -= 1
                        fault = self._fault_exc()
                    else:
                        fault = None
                    self._cv.notify_all()   # wake admission waiters
            except BaseException as e:
                # a crash in the take phase holds no batch yet; it still
                # must reach the supervisor, not kill the thread
                raise _WorkerCrashed([], e) from e
            try:
                if fault is not None:
                    raise fault
                self._dispatch(batch)
                if streak[0]:          # healthy dispatch ends the streak
                    streak[0] = 0
                    self._note_streak(0)
            except _WorkerCrashed:
                raise
            except BaseException as e:
                raise _WorkerCrashed(batch, e) from e
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _dispatch(self, batch):
        now = time.monotonic()
        live = []
        for r in batch:
            if r.deadline is not None and now >= r.deadline:
                # expired while queued: never ship it to the device
                self.stats.expired(r.model)
                exc = DeadlineExceededError(
                    f"deadline exceeded after "
                    f"{now - r.t_enqueue:.3f}s in queue")
                exc.trace_id = reqtrace.error_trace(
                    "request.expired", ctx=r.trace, model=r.model,
                    where="queue", queue_s=round(now - r.t_enqueue, 3))
                if not r.fut.done():
                    r.fut.set_exception(exc)
                continue
            live.append(r)
        if not live:
            return
        model = live[0].model
        for r in live:
            # queue wait = admission → dispatch; one histogram observe
            # per request (same cost class as completed() below)
            self.stats.queue_waited(r.model, (now - r.t_enqueue) * 1e3)
        try:
            entry = self.registry.acquire(model)
        except BaseException as e:
            for r in live:
                if not r.fut.done():
                    r.fut.set_exception(e)
                self.stats.completed(r.model, 0.0, ok=False)
            return
        dt = None
        try:
            xs = (live[0].x if len(live) == 1
                  else np.concatenate([r.x for r in live], axis=0))
            self.stats.batch_dispatched(xs.shape[0], self.max_batch)
            traced = [r for r in live if r.trace is not None]
            if traced:
                # fan-in seam: close each trace's admission wait, then
                # open ONE dispatch window joining all co-batched traces
                # (begin_dispatch pins it to this worker thread so
                # run_batch can parent per-row session-step spans on it)
                t_w = time.time()
                for r in traced:
                    reqtrace.record_span(
                        r.trace.trace_id, "queue.wait",
                        parent_id=r.trace.span_id, ts=r.t_wall,
                        dur_ms=(t_w - r.t_wall) * 1e3, model=model)
                dt = reqtrace.begin_dispatch([r.trace for r in traced])
            ys = live[0].ctx.run(entry.run_batch, xs)
            done = time.monotonic()
            ver = getattr(entry, "version", None)
            reqtrace.end_dispatch(dt, model=model, rows=int(xs.shape[0]),
                                  requests=len(live), version=ver)
            dt = None
            off = 0
            for r in live:
                n = r.x.shape[0]
                if not r.fut.done():
                    # stamp which deployed version served this request
                    # BEFORE resolving, so result() readers see it —
                    # the hot-swap zero-downtime evidence
                    r.fut.version = ver
                    r.fut.set_result(ys[off:off + n])
                self.stats.completed(
                    r.model, done - r.t_enqueue,
                    trace_id=r.trace.trace_id if r.trace else None)
                off += n
        except BaseException as e:
            reqtrace.end_dispatch(dt, model=model, requests=len(live),
                                  error=type(e).__name__)
            for r in live:
                if not r.fut.done():
                    r.fut.set_exception(e)
                self.stats.completed(r.model, 0.0, ok=False)
            # per-batch faults surface through futures and stats; a ring
            # breadcrumb keeps them visible in a later crash dump too
            try:
                from deeplearning4j_tpu.observe.flight import get_flight
                get_flight().record("serving_dispatch_error", model=model,
                                    error=type(e).__name__,
                                    requests=len(live))
            # graft: allow(GL403): ring breadcrumb is best-effort; the
            # fault already reached every future and the stats above
            except Exception:
                pass
        finally:
            self.registry.release(entry)
