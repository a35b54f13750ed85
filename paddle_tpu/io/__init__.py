"""paddle.io — datasets, samplers, DataLoader.
Parity: python/paddle/io/__init__.py + python/paddle/fluid/dataloader/.

DataLoader design for TPU: the bottleneck is keeping the jitted step fed,
so the loader overlaps host-side batch assembly (thread/process workers)
with device compute via a prefetch ring buffer (the role buffered_reader.cc
plays in the reference). The native C++ prefetch core lives in
paddle_tpu/runtime; this module is the API layer and pure-python fallback.
"""
import bisect
import itertools
import math
import os
import queue
import threading

import numpy as np

from ..framework.core import Tensor
from ..framework import random as fw_random
from ..profiler import statistic as _stat
from ..profiler import monitor as _monitor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ChainDataset",
           "ComposeDataset", "ConcatDataset", "Subset", "random_split",
           "Sampler", "SequenceSampler", "RandomSampler",
           "WeightedRandomSampler", "BatchSampler",
           "DistributedBatchSampler", "DataLoader", "get_worker_info",
           "default_collate_fn"]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        n = len(tensors[0])
        assert all(len(t) == n for t in tensors)
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        assert all(len(d) == len(self.datasets[0]) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)

    def __len__(self):
        return len(self.datasets[0])


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = list(itertools.accumulate(len(d) for d in self.datasets))

    def __len__(self):
        return self.cum[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = bisect.bisect_right(self.cum, idx)
        prev = 0 if di == 0 else self.cum[di - 1]
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if sum(lengths) != len(dataset):
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(len(dataset))
    out, offset = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[offset:offset + n].tolist()))
        offset += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n,
                                          size=self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(
            weights.numpy() if isinstance(weights, Tensor) else weights,
            dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), size=self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        if sampler is None:
            sampler = RandomSampler(dataset) if shuffle \
                else SequenceSampler(dataset)
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Parity: fluid/dataloader/batch_sampler.py:DistributedBatchSampler.
    On the TPU single-controller the full global batch is assembled and
    sharded over 'dp' by the train step, so rank slicing applies only in
    multi-host runs (num_replicas = process count)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        import jax
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None \
            else jax.process_count()
        self.local_rank = rank if rank is not None else jax.process_index()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(
            math.ceil(len(dataset) / self.nranks)) if not drop_last else \
            len(dataset) // self.nranks
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = list(range(n))
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices += indices[:(self.total_size - len(indices))]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


class WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (Tensor,)):
        return Tensor(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, dtype=np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, dtype=np.float32))
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch])
                for k in sample}
    if isinstance(sample, (tuple, list)):
        return [default_collate_fn([s[i] for s in batch])
                for i in range(len(sample))]
    return batch


def _tensorify_tree(batch):
    """numpy tree from a worker process → Tensor leaves (parent side)."""
    if isinstance(batch, np.ndarray):
        return Tensor(batch)
    if isinstance(batch, dict):
        return {k: _tensorify_tree(v) for k, v in batch.items()}
    if isinstance(batch, (list, tuple)):
        if batch and isinstance(batch[0], (str, bytes)):
            return list(batch)
        return [_tensorify_tree(v) for v in batch]
    return batch


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, prefetch_to_device=0):
        """prefetch_to_device: ring depth for the device prefetch layer
        (io/device_prefetch.py) — a background thread jax.device_puts up
        to this many upcoming batches (with the train step's input
        shardings, see `set_batch_sharding`) while the current step
        computes, so the consumer-side `dataloader.next` wait is ~0 in
        steady state. 0/False disables (default); True means depth 2."""
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self.persistent_workers = persistent_workers
        self.prefetch_to_device = 2 if prefetch_to_device is True \
            else int(prefetch_to_device or 0)
        self._batch_sharding_fn = None
        self._sharding_from_fit = False  # fit-bound fns rebind per fit
        self._mp_pool = None
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", None)
        elif not self._iterable_mode:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
            self.batch_size = batch_size
        else:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last

    def __call__(self):
        """Legacy fluid idiom `for batch in loader():` (reference
        docstring examples use it; DataLoader.__call__ returns the
        iterator, same as iterating the loader directly)."""
        return iter(self)

    @staticmethod
    def from_generator(feed_list=None, capacity=None,
                       use_double_buffer=True, iterable=True,
                       return_list=True, use_multiprocess=False,
                       drop_last=True):
        """Legacy fluid API (reference python/paddle/fluid/reader.py
        DataLoader.from_generator): returns a loader whose data source is
        attached afterwards via set_sample_generator /
        set_sample_list_generator / set_batch_generator."""
        return _GeneratorLoader(feed_list, capacity, use_double_buffer,
                                iterable, return_list, use_multiprocess,
                                drop_last)

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        """Legacy fluid API: iterate a (possibly distributed ps-style)
        dataset directly."""
        loader = _GeneratorLoader(return_list=True, drop_last=drop_last)

        def gen():
            for item in dataset:
                yield item if isinstance(item, (list, tuple)) else (item,)
        loader.set_sample_generator(gen, batch_size=1, drop_last=drop_last)
        return loader

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no fixed length")
        return len(self.batch_sampler)

    def _make_batch(self, indices):
        return self.collate_fn([self.dataset[i] for i in indices])

    def _iter_iterable(self):
        batch = []
        for item in self.dataset:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield self.collate_fn(batch)

    def set_batch_sharding(self, fn):
        """Per-leaf sharding callable (`TrainStep.input_sharding` /
        `HybridTrainStep.input_sharding`) the device prefetch ring places
        staged batches with. hapi `Model.fit` wires this automatically;
        set it yourself when driving a step object directly with
        `prefetch_to_device` enabled. A fn set here is yours: fit won't
        replace it (fit-bound fns, by contrast, rebind on every fit so a
        stale step's device state is never pinned)."""
        self._batch_sharding_fn = fn
        self._sharding_from_fit = False
        return self

    def __iter__(self):
        """Iteration wraps the concrete source with telemetry: every
        batch's host-side wait (assembly + queue time — the gap the
        prefetch layers exist to hide) lands as a "dataloader.next" span
        and in the dataloader.wait_s histogram, so a starved train step
        is visible in Profiler.summary() rather than inferred. With
        `prefetch_to_device` set, the device prefetch ring sits between
        the source and this wait, so the span measures what the *step
        loop* actually waited — ~0 when the ring keeps up."""
        inner = self._iter_source()
        if self.prefetch_to_device:
            from .device_prefetch import device_prefetch_iterator
            inner = device_prefetch_iterator(inner, self.prefetch_to_device,
                                             self._batch_sharding_fn)
        while True:
            _stat.begin_span("dataloader.next")
            try:
                batch = next(inner)
            except StopIteration:
                return
            finally:
                dt = _stat.end_span()
            _monitor.histogram("dataloader.wait_s").observe(dt)
            _monitor.counter("dataloader.batches").inc()
            yield batch

    def _iter_source(self):
        if self._iterable_mode:
            yield from self._iter_iterable()
            return
        if self.num_workers == 0:
            for indices in self.batch_sampler:
                yield self._make_batch(indices)
            return
        if self.use_shared_memory:
            it = self._iter_multiprocess()
            if it is not None:
                yield from it
                return
        yield from self._iter_threaded()

    def _iter_multiprocess(self):
        """Subprocess workers (reference
        fluid/dataloader/dataloader_iter.py:326): CPU-bound transforms
        scale past the GIL. Returns None when the dataset/collate_fn
        can't be pickled — caller falls back to the threaded path."""
        from .mp_loader import MultiprocessPool
        pool = self._mp_pool
        if pool is None or not pool._alive:
            try:
                # only a python collate_fn travels to the workers; the
                # default collate runs as numpy there, tensorified here
                custom = None if self.collate_fn is default_collate_fn \
                    else self.collate_fn
                pool = MultiprocessPool(self.dataset, custom,
                                        self.num_workers,
                                        self.worker_init_fn,
                                        self.prefetch_factor)
            except Exception:
                return None  # unpicklable → threaded fallback
            self._mp_pool = pool

        def gen():
            try:
                for batch in pool.run_epoch(iter(self.batch_sampler),
                                            self.timeout):
                    yield _tensorify_tree(batch)
            finally:
                if not self.persistent_workers:
                    pool.shutdown()
                    self._mp_pool = None
        return gen()

    def _iter_threaded(self):
        """Prefetching iterator: worker threads assemble batches into a
        bounded ring buffer (native core used when available)."""
        from ..runtime import prefetch
        index_iter = iter(self.batch_sampler)
        yield from prefetch.prefetch_iterator(
            index_iter, self._make_batch, self.num_workers,
            self.num_workers * self.prefetch_factor, self.timeout,
            self.worker_init_fn)


class _GeneratorLoader:
    """Loader built by DataLoader.from_generator (legacy fluid API,
    parity: python/paddle/fluid/reader.py GeneratorLoader). The three
    source setters mirror the reference: per-sample generator (batched
    here), per-sample-list generator (collated), per-batch generator
    (passed through). Iterating yields Tensor lists (return_list=True,
    the dygraph default) or name->Tensor dicts for the static feed."""

    def __init__(self, feed_list=None, capacity=None,
                 use_double_buffer=True, iterable=True, return_list=True,
                 use_multiprocess=False, drop_last=True):
        self._feed_list = feed_list or []
        self._iterable = iterable
        self._return_list = return_list
        self._drop_last = drop_last
        self._gen = None
        self._mode = None
        self._batch_size = None

    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        self._gen, self._mode = reader, "sample"
        self._batch_size = batch_size
        self._drop_last = drop_last
        return self

    def set_sample_list_generator(self, reader, places=None):
        self._gen, self._mode = reader, "sample_list"
        return self

    def set_batch_generator(self, reader, places=None):
        self._gen, self._mode = reader, "batch"
        return self

    def _wrap(self, fields):
        ts = [Tensor(np.asarray(f)) if not isinstance(f, Tensor) else f
              for f in fields]
        if self._return_list:
            return ts
        names = [getattr(v, "name", None) or f"f{i}"
                 for i, v in enumerate(self._feed_list)]
        # never truncate: fields beyond feed_list get generated names
        names += [f"f{i}" for i in range(len(names), len(ts))]
        return {n: t for n, t in zip(names, ts)}

    def __iter__(self):
        if self._gen is None:
            raise RuntimeError(
                "set a data source first: set_sample_generator / "
                "set_sample_list_generator / set_batch_generator")
        if self._mode == "batch":
            for batch in self._gen():
                yield self._wrap(list(batch))
            return
        if self._mode == "sample_list":
            for samples in self._gen():
                fields = list(zip(*samples))
                yield self._wrap([np.stack(f) for f in fields])
            return
        buf = []
        for sample in self._gen():
            buf.append(sample if isinstance(sample, (list, tuple))
                       else (sample,))
            if len(buf) == self._batch_size:
                fields = list(zip(*buf))
                yield self._wrap([np.stack(f) for f in fields])
                buf = []
        if buf and not self._drop_last:
            fields = list(zip(*buf))
            yield self._wrap([np.stack(f) for f in fields])

    __call__ = __iter__  # legacy `for batch in loader():`

    def start(self):  # non-iterable (start/reset) mode parity: no-op —
        pass          # iteration drives the generator directly

    def reset(self):
        pass
