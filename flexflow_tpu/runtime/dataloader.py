"""Data loading: host dataset -> mesh-sharded device batches.

Reference: SingleDataLoader (python/flexflow_dataloader.h:34,
flexflow_dataloader.cc 574 LoC + CUDA copy kernels): whole dataset
pinned in zero-copy DRAM, per-batch index-launch copy tasks to each GPU
shard. TPU-native: the dataset stays in host numpy; each batch is
device_put with the input's NamedSharding so every chip receives only
its shard (XLA runtime does the host->HBM DMA), and a one-deep
background prefetch thread overlaps the next batch's transfer with the
current step (the reference gets this overlap from Legion task
pipelining).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, List, Optional, Sequence

import jax
import numpy as np

from ..obs.steptrace import phase

_native_gather = None  # cached: function, or False after a failed import


def _get_native_gather():
    global _native_gather
    if _native_gather is None:
        try:
            from .._native import batch_gather as f

            _native_gather = f
        except Exception:
            _native_gather = False
    return _native_gather or None


class SingleDataLoader:
    """Batches one array; reference: SingleDataLoader (flexflow_cffi.py:2433)."""

    def __init__(self, full_array: np.ndarray, batch_size: int, shuffle: bool = False, seed: int = 0, sharding=None):
        self.data = np.ascontiguousarray(full_array)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.sharding = sharding
        self.num_samples = self.data.shape[0]
        self.num_batches = self.num_samples // batch_size
        self._epoch = 0

    def _order(self) -> np.ndarray:
        if not self.shuffle:
            return np.arange(self.num_samples)
        rs = np.random.RandomState(self.seed + self._epoch)
        return rs.permutation(self.num_samples)

    def reset(self):
        self._epoch = 0

    def next_epoch(self):
        self._epoch += 1

    def _gather(self, idx: np.ndarray) -> np.ndarray:
        """Assemble one batch; native threaded row-gather when available
        (the TPU-side analog of the reference's CUDA copy kernels in
        flexflow_dataloader.cu — here the copy is host-side, the
        host->HBM DMA happens in device_put)."""
        native = _get_native_gather()
        if native is not None:
            try:
                out = np.empty((len(idx),) + self.data.shape[1:], self.data.dtype)
                native(self.data, out, idx)
                return out
            except Exception:
                pass
        return self.data[idx]

    def batches(self) -> Iterator[jax.Array]:
        order = self._order()
        for b in range(self.num_batches):
            idx = order[b * self.batch_size : (b + 1) * self.batch_size]
            batch = self._gather(idx)
            if self.sharding is not None:
                yield jax.device_put(batch, self.sharding)
            else:
                yield jax.device_put(batch)


class DataLoader:
    """Zips input + label loaders with background prefetch.

    Reference: FFModel.create_data_loader + the fit loop's per-batch
    next_batch index launches (flexflow_cffi.py:2178,2044).
    """

    def __init__(
        self,
        xs: Sequence[np.ndarray],
        y: np.ndarray,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        shardings: Optional[Sequence] = None,
        label_sharding=None,
        prefetch: int = 2,
    ):
        n = y.shape[0]
        assert all(x.shape[0] == n for x in xs), "input/label sample counts differ"
        shardings = shardings or [None] * len(xs)
        self.loaders: List[SingleDataLoader] = [
            SingleDataLoader(x, batch_size, shuffle, seed, sh) for x, sh in zip(xs, shardings)
        ]
        self.label_loader = SingleDataLoader(y, batch_size, shuffle, seed, label_sharding)
        self.num_batches = self.label_loader.num_batches
        self.prefetch = max(1, prefetch)

    def epoch(self) -> Iterator:
        """Yield (inputs, label) device batches for one epoch, prefetched
        on a worker thread so host slicing/transfer overlaps compute."""
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def put(item) -> bool:
            """Bounded put that keeps checking stop so an abandoned epoch
            (consumer broke out of the generator) can't wedge the thread
            on a full queue."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            iters = [ld.batches() for ld in self.loaders] + [self.label_loader.batches()]
            try:
                for _ in range(self.num_batches):
                    if stop.is_set():
                        return
                    # numpy gather -> device_put, on the prefetch thread
                    with phase("data.produce"):
                        vals = [next(it) for it in iters]
                    if not put((vals[:-1], vals[-1])):
                        return
                put(None)
            except Exception as e:  # surface worker errors to the consumer
                put(e)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                # the consumer blocked on the prefetch queue: device idle
                # under this span is the loader's, not the step's
                with phase("data.wait"):
                    item = q.get()
                if item is None:
                    break
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()
        for ld in self.loaders:
            ld.next_epoch()
        self.label_loader.next_epoch()
