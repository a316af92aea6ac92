"""DataSet iterators.

Reference parity: org.nd4j.linalg.dataset.api.iterator.DataSetIterator and
the utility iterators (deeplearning4j-utility-iterators): Async prefetch
(AsyncDataSetIterator.java:32), Existing/List/INDArray iterators,
BenchmarkDataSetIterator, MultipleEpochsIterator, EarlyTermination,
Sampling.

TPU-native addition: DeviceCachedIterator — uploads the whole dataset to
HBM ONCE and yields device-resident slices, so the training loop's only
host↔device traffic is the dispatch stream. On a host-bottlenecked
feed this is the difference between transfer-bound and
compute-bound training; the reference's nearest analogue is workspace-
cached DataSets, which still live host-side.

For datasets that do NOT fit in HBM (or host RAM), the disk-backed
counterpart is ``datapipe.StreamingDataPipeline``: checksummed shard
directories, supervised parallel prefetch, and seekable mid-epoch
resume state — a DataSetIterator like everything here, so it drops into
any fit()/RetryingIterator/AsyncDataSetIterator composition
(docs/data_pipeline.md).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.dataset.dataset import DataSet


class DataSetIterator:
    """Base protocol: iterable of (features, labels) or DataSet batches."""

    def reset(self) -> None: ...

    def __iter__(self):
        raise NotImplementedError

    def batch_size(self) -> Optional[int]:
        return getattr(self, "_batch", None)


class ArrayDataSetIterator(DataSetIterator):
    """Batches over in-memory arrays (reference: INDArrayDataSetIterator)."""

    def __init__(self, features, labels, batch_size: int = 32,
                 shuffle: bool = False, seed: Optional[int] = None):
        self.X = np.asarray(features)
        self.Y = np.asarray(labels)
        self._batch = batch_size
        self._shuffle = shuffle
        self._rng = np.random.default_rng(seed)

    def __iter__(self):
        idx = np.arange(len(self.X))
        if self._shuffle:
            self._rng.shuffle(idx)
        for i in range(0, len(idx), self._batch):
            j = idx[i:i + self._batch]
            yield self.X[j], self.Y[j]


class ListDataSetIterator(DataSetIterator):
    """Iterates a list of DataSets (reference: ListDataSetIterator)."""

    def __init__(self, datasets: Sequence[DataSet], batch_size: Optional[int] = None):
        if batch_size is not None:
            merged = DataSet.merge(list(datasets))
            datasets = merged.batch_by(batch_size)
        self._datasets = list(datasets)
        self._batch = batch_size

    def __iter__(self):
        for d in self._datasets:
            yield d.features, d.labels


class DeviceCachedIterator(DataSetIterator):
    """Uploads features/labels to device(s) once; yields device slices.

    With a sharding, data lands pre-sharded over the mesh (the 'data' axis)
    and every epoch's batches are zero-copy views of HBM.
    """

    def __init__(self, features, labels, batch_size: int = 32, sharding=None):
        import jax
        import jax.numpy as jnp
        def _is_multi(v):
            # multi-input = a list/tuple OF ARRAYS; nested python lists
            # (e.g. [[1., 2.], [3., 4.]]) stay a single 2-d array exactly
            # as np.asarray always treated them
            return isinstance(v, (list, tuple)) and len(v) > 0 and \
                all(hasattr(e, "ndim") for e in v)

        self._multi_f = _is_multi(features)
        self._multi_l = _is_multi(labels)
        feats = [np.asarray(f) for f in features] if self._multi_f \
            else [np.asarray(features)]
        labs = [np.asarray(l) for l in labels] if self._multi_l \
            else [np.asarray(labels)]
        lens = {len(a) for a in feats + labs}
        if len(lens) != 1:
            raise ValueError(
                f"all feature/label arrays must share the leading length; "
                f"got {[len(a) for a in feats]} / {[len(a) for a in labs]}")
        n = (len(feats[0]) // batch_size) * batch_size
        if n == 0:
            raise ValueError("dataset smaller than one batch")
        self._batch = batch_size
        self._n = n

        def _put(a):
            return jax.device_put(a[:n], sharding) if sharding is not None \
                else jnp.asarray(a[:n])

        self.Xs = [_put(f) for f in feats]
        self.Ys = [_put(l) for l in labs]

    # single-input views (back-compat)
    @property
    def X(self):
        return self.Xs[0]

    @property
    def Y(self):
        return self.Ys[0]

    def __iter__(self):
        for i in range(0, self._n, self._batch):
            fs = [x[i:i + self._batch] for x in self.Xs]
            ls = [y[i:i + self._batch] for y in self.Ys]
            yield (fs if self._multi_f else fs[0],
                   ls if self._multi_l else ls[0])

    def stacked_batches(self):
        """Device-resident batches stacked on a leading steps axis —
        feeds SameDiff's scanned whole-epoch train step (([X...], [Y...])
        with each array of shape (steps, batch, ...))."""
        steps = self._n // self._batch

        def _stk(a):
            return a.reshape(steps, self._batch, *a.shape[1:])

        return [_stk(x) for x in self.Xs], [_stk(y) for y in self.Ys]


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch (reference: AsyncDataSetIterator.java:32,
    wrapped around fit() inputs at MultiLayerNetwork.java:1678).

    Shutdown-safe: the worker uses a bounded put that polls a stop flag,
    and the consumer's ``finally`` (run on normal exhaustion AND on
    ``GeneratorExit`` when a consumer abandons the generator mid-epoch)
    sets the flag, drains the queue, and joins the thread — an abandoned
    iteration can no longer strand a daemon thread blocked on ``q.put``
    forever.

    Worker-thread failures travel IN the stream: the worker enqueues a
    poisoned sentinel carrying the exception and the index of the batch
    that failed to materialize, and the consumer re-raises it — in
    stream order, after the batches that preceded it — as a structured
    ``faults.DataPipelineError`` (the original exception chained as
    ``__cause__``). An epoch can no longer end silently short, and the
    recovery rail learns WHICH batch died."""

    _END = object()

    def __init__(self, wrapped: DataSetIterator, queue_size: int = 4):
        self._wrapped = wrapped
        self._queue_size = queue_size
        self._last_thread: Optional[threading.Thread] = None  # test hook

    def reset(self):
        if hasattr(self._wrapped, "reset"):
            self._wrapped.reset()

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self._queue_size)
        stop = threading.Event()

        class _Poison:
            def __init__(self, error: BaseException, batch_index: int):
                self.error = error
                self.batch_index = batch_index

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            index = 0                   # batch currently being produced
            try:
                for item in self._wrapped:
                    if not put(item):
                        return          # consumer gone
                    index += 1
            except BaseException as e:   # poisoned sentinel, in-stream
                put(_Poison(e, index))
                return
            finally:
                put(self._END)

        t = threading.Thread(target=worker, daemon=True)
        self._last_thread = t
        t.start()
        poison: List[_Poison] = []
        try:
            while True:
                item = q.get()
                if item is self._END:
                    break
                if isinstance(item, _Poison):
                    poison.append(item)
                    break
                yield item
        finally:
            stop.set()
            while True:                  # unblock a worker stuck on put
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5)
        if poison:
            from deeplearning4j_tpu.faults.errors import DataPipelineError
            p = poison[0]
            raise DataPipelineError(
                f"async prefetch worker failed producing batch "
                f"{p.batch_index}: {p.error!r}",
                batch_index=p.batch_index,
                cause="async_worker") from p.error


class BenchmarkDataSetIterator(DataSetIterator):
    """Synthetic fixed batches (reference: BenchmarkDataSetIterator.java —
    same batch object yielded n times; measures pure train throughput).

    ``device_cached=True`` uploads the one batch to HBM ONCE and yields
    the resident array every step — without it, every step pays a
    redundant host→device transfer of identical bytes, and a dispatch-
    bound benchmark measures the host link instead of the model.
    ``stacked_batches()`` additionally exposes the scanned-tier
    contract: the batch broadcast along a leading steps axis. NOTE the
    broadcast is committed to HBM (n_batches × batch bytes — XLA needs
    a concrete scan operand); for step counts where that doesn't fit,
    keep ``device_cached=False`` and train through the fused-window
    tier (``fused_steps``), whose stager stages K batches at a time."""

    def __init__(self, feature_shape: Sequence[int], n_classes: int,
                 n_batches: int, seed: int = 0, regression: bool = False,
                 device_cached: bool = False):
        rng = np.random.default_rng(seed)
        self._X = rng.normal(size=tuple(feature_shape)).astype(np.float32)
        if regression:
            self._Y = rng.normal(size=(feature_shape[0], n_classes)).astype(np.float32)
        else:
            self._Y = np.eye(n_classes, dtype=np.float32)[
                rng.integers(0, n_classes, feature_shape[0])]
        self._n = n_batches
        self._batch = feature_shape[0]
        self._device_cached = device_cached
        self._dev = None
        if device_cached:
            # the scanned tier routes on hasattr(it, "stacked_batches"),
            # so the method is exposed per-instance, only in cached mode
            self.stacked_batches = self._stacked_batches

    def _device_batch(self):
        if self._dev is None:
            import jax.numpy as jnp
            self._dev = (jnp.asarray(self._X), jnp.asarray(self._Y))
        return self._dev

    def __iter__(self):
        if self._device_cached:
            X, Y = self._device_batch()
        else:
            X, Y = self._X, self._Y
        for _ in range(self._n):
            yield X, Y

    def _stacked_batches(self):
        """Scanned-tier contract (see DeviceCachedIterator): the single
        batch broadcast to (n_batches, batch, ...) on device."""
        import jax.numpy as jnp
        X, Y = self._device_batch()
        return ([jnp.broadcast_to(X[None], (self._n, *X.shape))],
                [jnp.broadcast_to(Y[None], (self._n, *Y.shape))])


class MultipleEpochsIterator(DataSetIterator):
    """Replays the wrapped iterator N times as one pass (reference:
    MultipleEpochsIterator)."""

    def __init__(self, wrapped: DataSetIterator, n_epochs: int):
        self._wrapped = wrapped
        self._n = n_epochs

    def reset(self):
        if hasattr(self._wrapped, "reset"):
            self._wrapped.reset()

    def __iter__(self):
        for _ in range(self._n):
            self.reset()
            yield from self._wrapped


class EarlyTerminationIterator(DataSetIterator):
    """Caps batches per pass (reference: EarlyTerminationDataSetIterator)."""

    def __init__(self, wrapped: DataSetIterator, max_batches: int):
        self._wrapped = wrapped
        self._max = max_batches

    def reset(self):
        if hasattr(self._wrapped, "reset"):
            self._wrapped.reset()

    def __iter__(self):
        for i, item in enumerate(self._wrapped):
            if i >= self._max:
                break
            yield item


class SamplingDataSetIterator(DataSetIterator):
    """Random with-replacement batches (reference: SamplingDataSetIterator)."""

    def __init__(self, dataset: DataSet, batch_size: int, n_batches: int,
                 seed: Optional[int] = None):
        self._ds = dataset
        self._batch = batch_size
        self._n = n_batches
        self._rng = np.random.default_rng(seed)

    def __iter__(self):
        for _ in range(self._n):
            idx = self._rng.integers(0, self._ds.num_examples(), self._batch)
            yield self._ds.features[idx], self._ds.labels[idx]
