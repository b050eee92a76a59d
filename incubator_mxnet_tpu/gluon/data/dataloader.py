"""DataLoader (ref: python/mxnet/gluon/data/dataloader.py).

The reference forks worker processes and ships batches through POSIX
shared-memory NDArrays (dataloader.py:72 rebuild_ndarray).  The TPU-native
pipeline keeps augmentation on host CPU in a thread pool — numpy transforms
release the GIL, jax.device_put overlaps H2D with compute — and hands the
device exactly one ready batch ahead (double-buffering, the same effect the
reference's prefetcher iterators achieve: src/io/iter_prefetcher.h).

graftduplex prefetch-to-device (GRAFT_PREFETCH_DEVICE, default on): each
lookahead batch's host→device transfer is ISSUED on the worker thread
under ``engine.offband()`` the moment the batch is built
(``io.issue_device_prefetch`` — the same issue/wait split ``ReduceHandle``
gave the gradient wire), so batch N+1's bytes stream to the device while
batch N computes.  With ``num_workers=0`` the loader now runs the same
one-batch-lookahead pipeline on a single pool thread (batches stay
sequential and in order — the reference's prefetcher iterators thread the
"synchronous" path the same way, iter_prefetcher.h); set
``GRAFT_PREFETCH_DEVICE=0`` or ``prefetch_device=False`` for the strictly
consumer-thread behavior.
"""
from __future__ import annotations

import os

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ...io import device_prefetch_enabled, issue_device_prefetch
from ...ndarray import NDArray
from ... import ndarray as _nd
from .sampler import BatchSampler, RandomSampler, SequentialSampler, Sampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (ref: dataloader.py default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return _nd.ndarray.concatenate([d.expand_dims(0) for d in data], axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return _nd.array(data, dtype=data.dtype)


def prefetch_depth_default():
    """GRAFT_PREFETCH_DEPTH (default 2, floor 1): how many lookahead
    batches the pooled pipeline keeps in flight beyond what the worker
    count implies.  2 is classic double-buffering; deeper absorbs
    per-batch build-time variance (one slow batch no longer stalls the
    consumer) at the cost of that many batches resident on host."""
    try:
        v = int(os.environ.get("GRAFT_PREFETCH_DEPTH", "2"))
    except ValueError:
        v = 2
    return max(1, v)


class DataLoader(object):
    """Loads batches from a Dataset (ref: dataloader.py class DataLoader)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, prefetch_device=None):
        self._dataset = dataset
        self._prefetch_device = prefetch_device     # None = GRAFT_PREFETCH_DEVICE
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler is "
                                 "specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch must "
                             "not be specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = num_workers
        self._prefetch_depth = None     # None = GRAFT_PREFETCH_DEPTH
        self._pool = None       # lazily-created per-loader worker pool
        if batchify_fn is None:
            self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn

    def prefetch_depth(self):
        """Effective lookahead depth: the live per-loader override when
        one is set (``set_prefetch_depth``), else
        :func:`prefetch_depth_default`."""
        d = self._prefetch_depth
        return prefetch_depth_default() if d is None else d

    def set_prefetch_depth(self, n):
        """Override the lookahead depth for this loader.  An open epoch
        iterator re-reads the depth on its next batch, so growth deepens
        the pipeline mid-epoch; shrinking drains naturally (in-flight
        futures complete, top-up just stops earlier)."""
        self._prefetch_depth = max(1, int(n))

    def _worker_pool(self):
        """The loader's thread pool, created on first use and REUSED
        across epochs — tearing a pool down and respawning its threads
        every ``__iter__`` (one per epoch) paid thread start-up latency
        exactly when the next epoch's first batches were needed."""
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=max(1, self._num_workers),
                thread_name_prefix="graft-dataloader")
        return self._pool

    def close(self):
        """Shut the worker pool down (idempotent; a later ``__iter__``
        lazily recreates it).  Do not call while an epoch iterator is
        mid-flight — its next lookahead submit would raise."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass                # interpreter teardown: nothing to save

    def __iter__(self):
        prefetch = device_prefetch_enabled(self._prefetch_device)
        it = iter(self._batch_sampler)
        if self._num_workers == 0 and not prefetch:
            for batch in it:
                yield self._batchify_fn(
                    [self._dataset[idx] for idx in batch])
            return
        # thread-pool pipeline with one-batch lookahead (double
        # buffering); num_workers=0 + device prefetch runs the same
        # pipeline on ONE thread — batches stay sequential and ordered,
        # but batch N+1 builds (and its H2D issues) under batch N's
        # compute instead of under the consumer's wait
        pool = self._worker_pool()

        def make(batch):
            # graftarmor chaos site: a worker-thread batch build can be
            # delayed (slow disk) or failed (bad record) by GRAFT_FAULTS
            from ...armor import faults as _faults
            _faults.fault_point("dataloader.worker", n=len(batch))
            out = self._batchify_fn([self._dataset[idx] for idx in batch])
            if prefetch:
                # the lookahead batch's host→device transfer goes on the
                # wire NOW, from the worker thread (engine.offband keeps
                # any open bulk segment on this thread untouched)
                issue_device_prefetch(out)
            return out
        futures = []

        def top_up():
            # lookahead depth is re-read each batch so a live
            # set_prefetch_depth deepens the pipeline mid-epoch
            want = max(self.prefetch_depth(), self._num_workers)
            try:
                while len(futures) < want:
                    futures.append(pool.submit(make, next(it)))
            except StopIteration:
                pass
        try:
            top_up()
            while futures:
                out = futures.pop(0).result()
                top_up()
                yield out
        finally:
            # abandoned epoch (break / exception in the consumer): the
            # pool now outlives the iterator, so queued lookahead work
            # must not linger into the next epoch
            for f in futures:
                f.cancel()

    def __len__(self):
        return len(self._batch_sampler)
