"""DataSet iterators: base protocol, array-backed, async prefetch, decorators.

Reference parity: `DataSetIterator` (ND4J iface) + dl4j-nn
`datasets/iterator/`: `AsyncDataSetIterator.java:30-68` (background thread +
LinkedBlockingQueue — here a Python thread + queue feeding the device while
TPU computes), `MultipleEpochsIterator`, `EarlyTerminationDataSetIterator`,
`BenchmarkDataSetIterator` (synthetic fixed batches for throughput
measurement).

The async iterator is the host↔device overlap seam: JAX dispatch is already
asynchronous, so the thread only needs to hide HOST-side ETL (decode,
augmentation, numpy collation), exactly the role the reference gives it.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Callable, Iterator, List, Optional

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.observe import get_registry, span


class DataSetIterator:
    """Base protocol. Mirrors the reference DataSetIterator (hasNext/next/
    reset/batch/totalOutcomes) as a Python iterable with reset()."""

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        raise StopIteration

    def reset(self) -> None:
        pass

    @property
    def batch_size(self) -> Optional[int]:
        return None

    @property
    def num_outcomes(self) -> Optional[int]:
        return None

    def async_(self, prefetch: int = 2) -> "AsyncDataSetIterator":
        return AsyncDataSetIterator(self, prefetch)


class ArrayDataSetIterator(DataSetIterator):
    """Batches over in-memory arrays (the workhorse for tests + canned data)."""

    def __init__(self, features, labels=None, batch_size: int = 32,
                 features_mask=None, labels_mask=None,
                 shuffle: bool = False, seed: int = 0, drop_last: bool = False):
        self._data = DataSet(
            np.asarray(features),
            None if labels is None else np.asarray(labels),
            features_mask, labels_mask,
        )
        self._bs = batch_size
        self._shuffle = shuffle
        self._seed = seed
        self._epoch = 0
        self._drop_last = drop_last
        self._pos = 0
        self._cur = self._data

    def reset(self):
        self._pos = 0
        if self._shuffle:
            self._cur = self._data.shuffle(self._seed + self._epoch)
            self._epoch += 1

    def __next__(self) -> DataSet:
        n = self._cur.num_examples()
        if self._pos >= n:
            raise StopIteration
        hi = min(self._pos + self._bs, n)
        if self._drop_last and hi - self._pos < self._bs:
            raise StopIteration
        sl = lambda a: None if a is None else a[self._pos:hi]
        d = DataSet(self._cur.features[self._pos:hi], sl(self._cur.labels),
                    sl(self._cur.features_mask), sl(self._cur.labels_mask))
        self._pos = hi
        return d

    @property
    def batch_size(self):
        return self._bs

    @property
    def num_outcomes(self):
        if self._data.labels is not None and self._data.labels.ndim >= 2:
            return int(self._data.labels.shape[-1])
        return None


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch. Reference:
    `datasets/iterator/AsyncDataSetIterator.java:30-68`."""

    _SENTINEL = object()

    def __init__(self, base: DataSetIterator, prefetch: int = 2):
        self._base = base
        self._prefetch = prefetch
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._stop: Optional[threading.Event] = None
        reg = get_registry()
        self._m_batches = reg.counter("etl_batches_total", stage="async")
        self._m_hits = reg.counter("prefetch_hits_total", stage="async")
        self._m_misses = reg.counter("prefetch_misses_total", stage="async")
        self._m_depth = reg.gauge("prefetch_queue_depth", stage="async")

    def _pump(self, q: queue.Queue, stop: threading.Event):
        try:
            for d in self._base:
                # Bounded put that aborts when a reset() orphaned this thread,
                # so abandoned pumps don't block forever holding batches.
                while not stop.is_set():
                    try:
                        q.put(d, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # surfaced on the consumer side
            self._error = e
        finally:
            # The sentinel MUST reach the consumer (a dropped sentinel hangs
            # the consumer) — block with the same stop-aware loop.
            while not stop.is_set():
                try:
                    q.put(self._SENTINEL, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def reset(self):
        if self._stop is not None:
            self._stop.set()
        self._queue = queue.Queue(maxsize=self._prefetch + 1)  # +1: sentinel
        self._error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._pump, args=(self._queue, self._stop), daemon=True)
        self._thread.start()

    def __next__(self) -> DataSet:
        if self._queue is None:
            self.reset()
        if self._error is not None:
            # Fail fast: don't hand out already-buffered batches once the
            # pump has died — the consumer would train on a silently
            # truncated epoch before seeing the error.
            err, self._error = self._error, None
            self.close()
            raise err
        # qsize() before the get: non-empty means the pump stayed ahead
        # of the consumer (a prefetch hit); empty means this step waited
        # on host ETL. Advisory but cheap — the ratio is the signal.
        depth = self._queue.qsize()
        self._m_depth.set(depth)
        item = self._queue.get()
        if item is self._SENTINEL:
            self._queue = None
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            raise StopIteration
        (self._m_hits if depth > 0 else self._m_misses).inc()
        self._m_batches.inc()
        return item

    def close(self) -> None:
        """Stop the pump and join the worker thread. Safe to call twice;
        called automatically when used as a context manager."""
        if self._stop is not None:
            self._stop.set()
        q, t = self._queue, self._thread
        if q is not None:
            # Drain so a pump blocked on a full queue observes the stop
            # event and exits promptly.
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:  # graft: allow(GL403): drain-until-empty
                pass
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        self._queue = None
        self._thread = None

    def __enter__(self) -> "AsyncDataSetIterator":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    @property
    def batch_size(self):
        return self._base.batch_size

    @property
    def num_outcomes(self):
        return self._base.num_outcomes


class MultipleEpochsIterator(DataSetIterator):
    """Repeat a base iterator N times. Reference: MultipleEpochsIterator."""

    def __init__(self, base: DataSetIterator, epochs: int):
        self._base = base
        self._epochs = epochs
        self._epoch = 0
        self._inner: Optional[Iterator] = None

    def reset(self):
        self._epoch = 0
        self._inner = iter(self._base)

    def __next__(self) -> DataSet:
        if self._inner is None:
            self.reset()
        while True:
            try:
                return next(self._inner)
            except StopIteration:
                self._epoch += 1
                if self._epoch >= self._epochs:
                    raise
                self._inner = iter(self._base)


class EarlyTerminationDataSetIterator(DataSetIterator):
    """Cap the number of minibatches. Reference: EarlyTerminationDataSetIterator."""

    def __init__(self, base: DataSetIterator, max_batches: int):
        self._base = base
        self._max = max_batches
        self._count = 0
        self._inner: Optional[Iterator] = None

    def reset(self):
        self._count = 0
        self._inner = iter(self._base)

    def __next__(self) -> DataSet:
        if self._inner is None:
            self.reset()
        if self._count >= self._max:
            raise StopIteration
        self._count += 1
        return next(self._inner)


class BenchmarkDataSetIterator(DataSetIterator):
    """Synthetic fixed batches for throughput measurement. Reference:
    `datasets/iterator/impl/BenchmarkDataSetIterator.java`."""

    def __init__(self, feature_shape, num_classes: int, num_batches: int,
                 seed: int = 0, label_shape=None):
        rng = np.random.default_rng(seed)
        self._features = rng.standard_normal(feature_shape, dtype=np.float32)
        b = feature_shape[0]
        if label_shape is None:
            labels = np.zeros((b, num_classes), dtype=np.float32)
            labels[np.arange(b), rng.integers(0, num_classes, b)] = 1.0
        else:
            labels = rng.standard_normal(label_shape).astype(np.float32)
        self._labels = labels
        self._n = num_batches
        self._i = 0

    def reset(self):
        self._i = 0

    def __next__(self) -> DataSet:
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        return DataSet(self._features, self._labels)

    @property
    def batch_size(self):
        return int(self._features.shape[0])

    @property
    def num_outcomes(self):
        return int(self._labels.shape[-1])


class IterableDataSetIterator(DataSetIterator):
    """Adapt any Python iterable of pre-built DataSet/MultiDataSet batches
    (list, generator, custom loader) to the DataSetIterator protocol.

    Re-iterables (lists, custom __iter__ objects) get a fresh ``iter()``
    every reset, so multi-epoch ``fit(..., epochs=N)`` replays each epoch.
    One-shot iterators/generators are replay-cached: batches seen in the
    first pass are recorded and replayed on subsequent resets (the
    generator itself can only be consumed once)."""

    def __init__(self, source):
        self._replay = isinstance(source, Iterator)
        self._source = iter(source) if self._replay else source
        self._cache: List = []
        self._first_pass = True
        self._inner: Optional[Iterator] = None

    def reset(self):
        if self._replay:
            if self._first_pass:
                self._inner = self._source
            else:
                self._inner = iter(self._cache)
        else:
            self._inner = iter(self._source)

    def __next__(self):
        if self._inner is None:
            self.reset()
        try:
            item = next(self._inner)
        except StopIteration:
            if self._replay and self._first_pass:
                self._first_pass = False
            raise
        if self._replay and self._first_pass:
            self._cache.append(item)
        return item


class DevicePrefetchIterator(DataSetIterator):
    """Overlap host→device transfer with compute: issue `jax.device_put`
    for batch N+1 while batch N's step is still executing.

    `device_put` merely ENQUEUES the transfer (JAX dispatch is async), so
    no thread is needed — this iterator just runs ``depth`` batches ahead
    of the consumer, double-buffered by default. Composes with
    `AsyncDataSetIterator` underneath (thread hides host ETL, this hides
    the H2D copy).

    ``put_fn(array) -> jax.Array`` defaults to the active sharding
    spine's batch placement (`parallel.mesh.current_mesh_context()`) when
    one is installed — each batch lands pre-sharded over the batch axis
    in ONE device_put — and to plain `jax.device_put` (single device)
    otherwise. The data-parallel trainer passes its spine's put
    explicitly. ``transform(ds) -> ds`` is the data-parallel trainer's
    host-side padding hook (to a device-count divisible batch), applied
    before the put; nothing else uses it. A batch goes up in the dtype
    the base iterator gave it: the consumer casts on the device.
    """

    def __init__(self, base: DataSetIterator, depth: int = 2,
                 put_fn: Optional[Callable] = None,
                 transform: Optional[Callable] = None):
        self._base = base
        self._depth = max(1, int(depth))
        self._put_fn = put_fn
        self._transform = transform
        self._inner: Optional[Iterator] = None
        self._buf: List = []
        self._exhausted = False
        self._pending: Optional[BaseException] = None
        reg = get_registry()
        self._m_hits = reg.counter("prefetch_hits_total", stage="device")
        self._m_misses = reg.counter("prefetch_misses_total", stage="device")
        self._m_batches = reg.counter("etl_batches_total", stage="device")

    def _put(self, ds):
        """One batch to the device(s), under a `data.put` span (a child of
        the fit loop's `fit.etl` wait) whose `bytes` are those handed to
        `device_put`: a put that blocks on a full transfer queue and a
        slow feed both read as a long etl wait, and this span tells them
        apart."""
        import jax

        put = self._put_fn
        if put is None:
            # resolved per batch: the spine is active only for the
            # duration of the fit driving this iterator
            from deeplearning4j_tpu.parallel.mesh import (
                current_mesh_context,
            )
            ctx = current_mesh_context()
            put = ctx.put_batch if ctx is not None else jax.device_put
        if self._transform is not None:
            ds = self._transform(ds)
        sent = span("data.put", bytes=0)

        def p(a):
            if a is None:
                return None
            sent.attrs["bytes"] += getattr(a, "nbytes", 0)
            return put(a)

        with sent:
            if hasattr(ds, "features_masks"):   # MultiDataSet
                cls = type(ds)
                pl = lambda xs: None if xs is None else type(xs)(
                    p(x) for x in xs)
                return cls(pl(ds.features), pl(ds.labels),
                           pl(ds.features_masks), pl(ds.labels_masks))
            return DataSet(p(ds.features), p(ds.labels),
                           p(ds.features_mask), p(ds.labels_mask))

    def _fill(self):
        while (not self._exhausted and self._pending is None
               and len(self._buf) < self._depth):
            try:
                self._buf.append(self._put(next(self._inner)))
            except StopIteration:
                self._exhausted = True
            except BaseException as e:
                # a failed AHEAD fetch must not poison the batch already
                # in hand: hold the error until the consumer actually
                # reaches the failed position (exact-resume cursors and
                # checkpoints then reflect every batch that trained)
                self._pending = e

    def reset(self):
        self._inner = iter(self._base)
        self._buf = []
        self._exhausted = False
        self._pending = None

    def __next__(self):
        if self._inner is None:
            self.reset()
        # a batch already buffered = its device_put was enqueued while the
        # consumer computed (hit); an empty buffer = this step pays the
        # host-side fetch+put latency in line (miss)
        ready = bool(self._buf)
        self._fill()
        if not self._buf:
            if self._pending is not None:
                e, self._pending = self._pending, None
                raise e
            raise StopIteration
        (self._m_hits if ready else self._m_misses).inc()
        self._m_batches.inc()
        item = self._buf.pop(0)
        self._fill()    # immediately enqueue the replacement transfer
        return item

    @property
    def batch_size(self):
        return self._base.batch_size

    @property
    def num_outcomes(self):
        return self._base.num_outcomes


def as_iterator(data, labels=None, batch_size: int = 32) -> DataSetIterator:
    """Coerce arrays / DataSet / iterables of DataSets / iterator into a
    DataSetIterator."""
    if isinstance(data, DataSetIterator):
        return data
    if isinstance(data, DataSet):
        return ArrayDataSetIterator(
            data.features, data.labels, batch_size,
            data.features_mask, data.labels_mask,
        )
    if labels is None and _is_dataset_iterable(data):
        return IterableDataSetIterator(data)
    return ArrayDataSetIterator(data, labels, batch_size)


def _is_dataset_iterable(data) -> bool:
    """True for generators/iterators, and for non-array iterables whose
    first element is a DataSet-like batch (has .features)."""
    if isinstance(data, Iterator):
        return True
    if isinstance(data, np.ndarray) or hasattr(data, "shape"):
        return False
    if isinstance(data, (list, tuple)):
        return bool(data) and hasattr(data[0], "features")
    # custom iterable wrappers (loaders, the chaos injectors in
    # parallel/chaos.py) satisfy "any iterable of DataSets" too — anything
    # non-array that can produce an iterator is a batch source
    return hasattr(data, "__iter__")


class FileSplitDataSetIterator(DataSetIterator):
    """One pre-saved DataSet file per step. Reference:
    `datasets/iterator/FileSplitDataSetIterator.java` (file list + load
    callback) / `ExistingMiniBatchDataSetIterator` — the executor side of
    Spark's fitPaths (`SparkDl4jMultiLayer.java:259`): minibatches are
    materialized to storage once, then any number of training runs
    stream them back. `files`: an iterable of paths or a directory
    (sorted *.npz); `loader` defaults to DataSet.load."""

    def __init__(self, files, loader=None):
        if isinstance(files, (str, os.PathLike)):
            d = os.fspath(files)
            self.files = [
                os.path.join(d, n) for n in sorted(os.listdir(d))
                if n.endswith(".npz")]
        else:
            self.files = [os.fspath(f) for f in files]
        if not self.files:
            raise ValueError("FileSplitDataSetIterator: no files")
        self.loader = loader or DataSet.load
        self._i = 0

    def reset(self):
        self._i = 0

    def __next__(self):
        if self._i >= len(self.files):
            raise StopIteration    # stays exhausted; __iter__ resets
        ds = self.loader(self.files[self._i])
        self._i += 1
        return ds
