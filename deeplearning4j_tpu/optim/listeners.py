"""Training listeners — the observability seam.

Reference parity: `optimize/api/IterationListener.java` /
`TrainingListener.java` and `optimize/listeners/` (ScoreIterationListener,
PerformanceListener `:60` with samples/sec + ETL time, CollectScores,
TimeIteration). Listeners run on the HOST after each step; because JAX
dispatch is async, reading the score forces a device sync — listeners that
only need it every N iterations therefore only sync every N iterations
(the reference pays a similar cost reading scalars off-device).

Async-dispatch contract (see PERF_NOTES): the `score` passed to
``iteration_done`` is the RAW value off the step — in the deferred-sync
fit path that is a jax device array, not a float. A listener that calls
``float(score)`` (or reads ``model.score_``) pays exactly the host sync it
asks for, stalling the dispatch pipeline for that step; listeners that
don't touch the score (PerformanceListener, TimeIterationListener) cost
nothing. Prefer a ``frequency``/``print_iterations`` cadence ≥10 in hot
loops, or pass ``sync_every=N`` to ``fit()`` to batch materializations.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, List, Optional

logger = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    """Reference: `optimize/api/TrainingListener.java` (onEpochStart/
    onEpochEnd/iterationDone; forward/backward hooks collapse into
    iteration_done because the step is one fused XLA computation)."""

    def iteration_done(self, model, iteration: int, epoch: int, score) -> None:
        pass

    def on_epoch_start(self, model, epoch: int) -> None:
        pass

    def on_epoch_end(self, model, epoch: int) -> None:
        pass

    def on_fit_start(self, model) -> None:
        pass

    def on_fit_end(self, model) -> None:
        pass


class ScoreIterationListener(TrainingListener):
    """Log score every N iterations. Reference: ScoreIterationListener."""

    def __init__(self, print_iterations: int = 10, out: Optional[Callable] = None):
        self.n = max(1, print_iterations)
        self._out = out or (lambda msg: logger.info(msg))

    def iteration_done(self, model, iteration, epoch, score):
        if iteration % self.n == 0:
            self._out(f"Score at iteration {iteration} is {float(score):.6f}")


class CollectScoresIterationListener(TrainingListener):
    """Accumulate (iteration, score) pairs. Reference: CollectScoresIterationListener."""

    def __init__(self, frequency: int = 1):
        self.frequency = max(1, frequency)
        self.scores: List[tuple] = []

    def iteration_done(self, model, iteration, epoch, score):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(score)))


class PerformanceListener(TrainingListener):
    """Throughput tracking: samples/sec, batches/sec, ETL time.
    Reference: `optimize/listeners/PerformanceListener.java:24-25,60`."""

    def __init__(self, frequency: int = 10, report: Optional[Callable] = None,
                 *, flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        self.frequency = max(1, frequency)
        self._report = report or (lambda msg: logger.info(msg))
        self._last_time = None
        self._last_iter = 0
        self.last_samples_per_sec = 0.0
        self.last_batches_per_sec = 0.0
        self.last_etl_ms = 0.0
        # MFU reporting (TPU-native extension of the reference's counters):
        # flops_per_step from utils/profiling.step_flops(model, x, y);
        # peak_flops defaults to the chip's spec-sheet bf16 peak — resolved
        # ONCE here, not on the reporting path (the spec lookup + device
        # count don't change mid-fit).
        self.flops_per_step = flops_per_step
        if flops_per_step and peak_flops is None:
            try:
                import jax
                from deeplearning4j_tpu.utils.profiling import peak_flops as \
                    _peak
                # step_flops is the GLOBAL step's HLO count, so the default
                # peak must cover every participating chip. An unknown
                # device kind leaves peak_flops None — peak_flops() warns
                # once naming the kind, and the MFU gauge is OMITTED
                # below instead of publishing NaN.
                per_chip = _peak()
                if per_chip:
                    peak_flops = per_chip * jax.device_count()
            except Exception:
                peak_flops = None
        if peak_flops is not None and not peak_flops > 0:
            peak_flops = None      # NaN/0/negative: same no-gauge path
        self.peak_flops = peak_flops
        self.last_mfu: Optional[float] = None
        self.last_step_ms: Optional[float] = None
        self.last_syncs_per_step: Optional[float] = None
        from deeplearning4j_tpu.observe import get_registry

        reg = get_registry()
        self._g_sps = reg.gauge("train_samples_per_sec")
        self._g_step_ms = reg.gauge("train_step_ms")
        self._g_mfu = reg.gauge("train_mfu")
        self._g_syncs = reg.gauge("train_host_syncs_per_step")

    def set_etl_time(self, ms: float) -> None:
        """Reference: setLastEtlTime threading (`MultiLayerNetwork.java:1092`)."""
        self.last_etl_ms = ms

    def iteration_done(self, model, iteration, epoch, score):
        now = time.perf_counter()
        if self._last_time is None:
            self._last_time = now
            self._last_iter = iteration
            return
        if iteration - self._last_iter >= self.frequency:
            dt = now - self._last_time
            n_batches = iteration - self._last_iter
            bs = getattr(model, "last_batch_size", None) or 0
            self.last_batches_per_sec = n_batches / dt
            self.last_samples_per_sec = n_batches * bs / dt
            self.last_step_ms = dt / n_batches * 1e3
            self._g_sps.set(self.last_samples_per_sec)
            self._g_step_ms.set(self.last_step_ms)
            msg = (f"iteration {iteration}: "
                   f"{self.last_samples_per_sec:.1f} samples/sec, "
                   f"{self.last_batches_per_sec:.2f} batches/sec, "
                   f"{self.last_step_ms:.1f} ms/step, "
                   f"ETL {self.last_etl_ms:.1f} ms")
            if self.flops_per_step and self.peak_flops:
                # over the wall step time: a host stall reads as a slower
                # device (a device trace gives the step itself)
                self.last_mfu = (self.flops_per_step / (dt / n_batches)
                                 / self.peak_flops)
                self._g_mfu.set(self.last_mfu)
                msg += f", MFU {self.last_mfu:.1%}"
            from deeplearning4j_tpu.observe import current_monitor

            mon = current_monitor()
            if mon is not None:
                # syncs since the last report window — the runtime version
                # of the perf-guard's dispatch-depth assertion
                self.last_syncs_per_step = mon.take() / n_batches
                self._g_syncs.set(self.last_syncs_per_step)
                msg += f", {self.last_syncs_per_step:.2f} syncs/step"
            self._report(msg)
            self._last_time = now
            self._last_iter = iteration


class TimeIterationListener(TrainingListener):
    """ETA logging. Reference: TimeIterationListener."""

    def __init__(self, total_iterations: int, frequency: int = 100):
        self.total = total_iterations
        self.frequency = max(1, frequency)
        self._start = None
        self._start_iter = 0

    def on_fit_start(self, model):
        # the clock starts at fit start, not at the end of the first step —
        # the old lazy init swallowed the first iteration's report and
        # based the rate on a denominator one step too large
        self._start = time.perf_counter()
        self._start_iter = getattr(model, "iteration", 0)

    def iteration_done(self, model, iteration, epoch, score):
        if self._start is None:
            # attached mid-fit (or driven without on_fit_start): anchor the
            # clock one step back so this report still has a rate
            self._start = time.perf_counter()
            self._start_iter = iteration - 1
        if iteration % self.frequency == 0 and iteration > 0:
            done = iteration - self._start_iter
            if done <= 0:
                return
            elapsed = time.perf_counter() - self._start
            rate = elapsed / done
            if self.total and self.total > 0:
                remaining = rate * max(self.total - iteration, 0)
                logger.info(
                    f"iteration {iteration}/{self.total}, "
                    f"ETA {remaining:.0f}s")
            else:
                # total unknown/invalid: report progress without an ETA
                # instead of a nonsense negative estimate
                logger.info(
                    f"iteration {iteration}, {rate * 1e3:.1f} ms/iter")


class EvaluativeListener(TrainingListener):
    """Periodic evaluation on a held-out iterator. Reference: EvaluativeListener."""

    def __init__(self, iterator, frequency: int = 1, on_epoch: bool = True):
        self.iterator = iterator
        self.frequency = max(1, frequency)
        self.on_epoch = on_epoch
        self.evaluations: List = []

    def on_epoch_end(self, model, epoch):
        if self.on_epoch and epoch % self.frequency == 0:
            e = model.evaluate(self.iterator)
            self.evaluations.append(e)
            logger.info(f"epoch {epoch} eval: accuracy={e.accuracy():.4f}")
