"""Dataset iterator algebra + async prefetch.

Parity: ref datasets/iterator/ — AsyncDataSetIterator.java:30 (AsyncPrefetchThread
:382-406), ListDataSetIterator, ExistingDataSetIterator, EarlyTerminationDataSetIterator,
MultipleEpochsIterator, SamplingDataSetIterator, INDArrayDataSetIterator,
impl/BenchmarkDataSetIterator.java:20. Iterators are plain Python iterables yielding
`DataSet`s; AsyncDataSetIterator runs a background thread that stages host→device
transfer ahead of the training loop (the TPU infeed double-buffer).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, List, Optional

import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.dataset import DataSet


class DataSetIterator:
    """Base: iterable over DataSets with reset()."""
    async_supported = True

    def __iter__(self) -> Iterator[DataSet]:
        raise NotImplementedError

    def reset(self):
        pass

    def batch(self) -> int:
        return -1

    def total_outcomes(self) -> int:
        return -1

    def input_columns(self) -> int:
        return -1


class ListDataSetIterator(DataSetIterator):
    """(ref datasets/iterator/impl/ListDataSetIterator.java)"""

    def __init__(self, datasets: List[DataSet], batch: Optional[int] = None):
        if batch is not None and len(datasets) == 1:
            datasets = datasets[0].batch_by(batch)
        self._list = list(datasets)
        self._batch = batch or (self._list[0].num_examples() if self._list else -1)

    def __iter__(self):
        return iter(self._list)

    def batch(self):
        return self._batch

    def __len__(self):
        return len(self._list)


class INDArrayDataSetIterator(DataSetIterator):
    """Iterate (features, labels) pairs in minibatches
    (ref datasets/iterator/INDArrayDataSetIterator.java)."""

    def __init__(self, features, labels, batch_size: int):
        self.features = np.asarray(features)
        self.labels = np.asarray(labels)
        self.batch_size = int(batch_size)

    def __iter__(self):
        n = self.features.shape[0]
        for i in range(0, n, self.batch_size):
            yield DataSet(self.features[i:i + self.batch_size],
                          self.labels[i:i + self.batch_size])

    def batch(self):
        return self.batch_size


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any iterable of DataSets (ref ExistingDataSetIterator.java)."""

    def __init__(self, iterable: Iterable[DataSet]):
        self._iterable = iterable

    def __iter__(self):
        return iter(self._iterable)


class EarlyTerminationDataSetIterator(DataSetIterator):
    """Cap the number of minibatches (ref EarlyTerminationDataSetIterator.java)."""

    def __init__(self, underlying: DataSetIterator, max_batches: int):
        self.underlying = underlying
        self.max_batches = int(max_batches)

    def __iter__(self):
        for i, ds in enumerate(self.underlying):
            if i >= self.max_batches:
                break
            yield ds

    def reset(self):
        self.underlying.reset()


class MultipleEpochsIterator(DataSetIterator):
    """Repeat an iterator N times (ref MultipleEpochsIterator.java)."""

    def __init__(self, epochs: int, underlying: DataSetIterator):
        self.epochs = int(epochs)
        self.underlying = underlying

    def __iter__(self):
        for _ in range(self.epochs):
            self.underlying.reset()
            yield from self.underlying

    def reset(self):
        self.underlying.reset()


class SamplingDataSetIterator(DataSetIterator):
    """Sample with replacement from a base DataSet (ref SamplingDataSetIterator.java)."""

    def __init__(self, base: DataSet, batch_size: int, total_samples: int, seed: int = 123):
        self.base = base
        self.batch_size = int(batch_size)
        self.total_samples = int(total_samples)
        self.seed = seed
        self._epoch = 0

    def __iter__(self):
        rng = np.random.RandomState(self.seed + self._epoch)
        self._epoch += 1
        n = self.base.num_examples()
        emitted = 0
        while emitted < self.total_samples:
            take = min(self.batch_size, self.total_samples - emitted)
            idx = rng.randint(0, n, size=take)
            yield DataSet(np.asarray(self.base.features)[idx],
                          np.asarray(self.base.labels)[idx])
            emitted += take


class BenchmarkDataSetIterator(DataSetIterator):
    """Synthetic random tensors for benchmarking — isolates compute from ETL
    (ref datasets/iterator/impl/BenchmarkDataSetIterator.java:20)."""

    def __init__(self, feature_shape, num_classes: int, num_batches: int, seed: int = 42,
                 label_shape=None):
        rng = np.random.RandomState(seed)
        self.features = rng.rand(*feature_shape).astype(np.float32)
        if label_shape is None:
            label_shape = (feature_shape[0], num_classes)
        labels = np.zeros(label_shape, np.float32)
        cls = rng.randint(0, num_classes, size=feature_shape[0])
        if len(label_shape) == 2:
            labels[np.arange(feature_shape[0]), cls] = 1.0
        else:
            labels[np.arange(feature_shape[0]), cls, :] = 1.0
        self.labels = labels
        self.num_batches = int(num_batches)

    def __iter__(self):
        for _ in range(self.num_batches):
            yield DataSet(self.features, self.labels)


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch with a bounded queue
    (ref AsyncDataSetIterator.java:30, AsyncPrefetchThread :382-406). Stages device_put
    so host→HBM transfer overlaps the previous step's compute."""
    async_supported = False  # don't double-wrap
    #: the training iteration the first batch feeds: `fit` sets it, so that the
    #: producer's spans carry the `step` of the training thread's spans
    first_step = 0

    def __init__(self, underlying, queue_size: int = 4, device_prefetch: bool = True):
        self.underlying = underlying
        self.queue_size = int(queue_size)
        self.device_prefetch = device_prefetch

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.queue_size)
        _END = object()
        err: List[BaseException] = []
        stop = threading.Event()

        def _put(item, step) -> bool:
            # bounded put that aborts if the consumer went away — otherwise a full
            # queue would park this thread forever holding the underlying iterator
            try:
                q.put_nowait(item)
                return True
            except queue.Full:
                pass
            with telemetry.span("dl4j.async.put_wait", step=step):
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return True
                    except queue.Full:
                        continue
            return False

        def producer():
            step = self.first_step
            try:
                it = iter(self.underlying)
                while True:
                    with telemetry.span("dl4j.async.produce", step=step):
                        ds = next(it, _END)
                    if ds is _END or stop.is_set():
                        return
                    if self.device_prefetch:
                        try:    # a MultiDataSet goes on as it is
                            import jax
                            parts = (ds.features, ds.labels, ds.features_mask,
                                     ds.labels_mask)
                            nbytes = sum(int(getattr(a, "nbytes", 0))
                                         for a in parts)
                            with telemetry.span("dl4j.async.stage", step=step,
                                                bytes=nbytes):
                                ds = DataSet(*(
                                    a if a is None
                                    else jax.device_put(np.asarray(a))
                                    for a in parts))
                        except Exception:
                            pass
                    if not _put(ds, step):
                        return
                    step += 1
            except BaseException as e:  # propagate into consumer
                err.append(e)
            finally:
                _put(_END, step)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                yield item
        finally:
            # consumer abandoned (exception/early break): release the producer
            stop.set()
            t.join(timeout=5.0)
        if err:
            raise err[0]

    def reset(self):
        self.underlying.reset()


def waited_batches(it, net):
    """Yields `it`'s batches, each wait for the next under a
    `dl4j.fit.next_batch` span that carries the iteration `net` is at: the
    interval `net.last_etl_ms` times, in `fit`'s loop."""
    it = iter(it)
    end = object()
    while True:
        with telemetry.span("dl4j.fit.next_batch", step=net._step):
            ds = next(it, end)
        if ds is end:
            return
        yield ds
