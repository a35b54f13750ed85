"""Multiprocess DataLoader workers.

Parity: python/paddle/fluid/dataloader/dataloader_iter.py:326
(_DataLoaderIterMultiProcess) — subprocess workers so CPU-bound python
transforms actually scale past the GIL (the threaded path can't).

Design:
- spawn context (fork would duplicate an initialized TPU/jax runtime);
- the dataset/collate_fn travel as pickle blobs and are unpickled INSIDE
  the worker after its env is pinned to the CPU jax backend, so worker
  code can never take the chip from the parent;
- workers return NUMPY trees; the parent converts leaves to Tensors
  (device put happens once, in the parent, next to the consumer);
- an index queue feeds (batch_id, indices); a reorder buffer on the
  parent restores deterministic batch order (reference semantics);
- persistent_workers keeps the pool across epochs.

Falls back to the threaded ring-buffer path when the dataset or
collate_fn cannot be pickled (the caller handles that).
"""
import os
import pickle
import queue
import traceback

import numpy as np

_SENTINEL = None


def _np_collate(batch):
    """default_collate over numpy — no jax/Tensor in the workers."""
    sample = batch[0]
    tname = type(sample).__name__
    if tname == "Tensor":  # dataset made Tensors (cpu jax) — detach to np
        return np.stack([np.asarray(s.numpy()) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, dtype=np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, dtype=np.float32)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: _np_collate([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        return [_np_collate([s[i] for s in batch])
                for i in range(len(sample))]
    return batch


def _np_detach(tree):
    """Tensors (weakref-bearing, unpicklable) → numpy before the queue."""
    if type(tree).__name__ == "Tensor":
        return np.asarray(tree.numpy())
    if hasattr(tree, "dtype") and hasattr(tree, "__array__") and \
            not isinstance(tree, np.ndarray):
        return np.asarray(tree)  # jax arrays etc.
    if isinstance(tree, dict):
        return {k: _np_detach(v) for k, v in tree.items()}
    if isinstance(tree, tuple):
        return tuple(_np_detach(v) for v in tree)
    if isinstance(tree, list):
        return [_np_detach(v) for v in tree]
    return tree


def _worker_loop(dataset_blob, collate_blob, init_blob, index_q, result_q,
                 wid, num_workers):
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        dataset = pickle.loads(dataset_blob)
        collate = pickle.loads(collate_blob)
        init_fn = pickle.loads(init_blob)
        if init_fn is not None:
            init_fn(wid)
        try:
            from . import _worker_info, WorkerInfo
            _worker_info.info = WorkerInfo(wid, num_workers, dataset)
        except Exception:
            pass
    except Exception:
        result_q.put((-1, None, traceback.format_exc()))
        return
    while True:
        item = index_q.get()
        if item is _SENTINEL:
            return
        bid, indices = item
        try:
            samples = [dataset[i] for i in indices]
            batch = collate(samples) if collate is not None \
                else _np_collate(samples)
            result_q.put((bid, _np_detach(batch), None))
        except Exception:
            result_q.put((bid, None, traceback.format_exc()))


class MultiprocessPool:
    """A spawn-context worker pool + ordered batch iterator."""

    def __init__(self, dataset, collate_fn, num_workers, worker_init_fn,
                 prefetch_factor=2):
        import multiprocessing as mp
        ctx = mp.get_context("spawn")
        # pickle up front: raises immediately if not transportable
        self._blobs = (pickle.dumps(dataset), pickle.dumps(collate_fn),
                       pickle.dumps(worker_init_fn))
        self.num_workers = num_workers
        self.prefetch = max(1, prefetch_factor) * num_workers
        self._index_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self._procs = [
            ctx.Process(target=_worker_loop,
                        args=(*self._blobs, self._index_q, self._result_q,
                              i, num_workers),
                        daemon=True)
            for i in range(num_workers)]
        for p in self._procs:
            p.start()
        self._alive = True

    def run_epoch(self, index_iter, timeout):
        """Yield collated numpy batches in sampler order."""
        if not self._alive:
            raise RuntimeError("worker pool already shut down")
        pending = {}
        next_out = 0
        next_in = 0
        exhausted = False
        index_iter = iter(index_iter)
        inflight = 0
        while True:
            while not exhausted and inflight < self.prefetch:
                try:
                    indices = next(index_iter)
                except StopIteration:
                    exhausted = True
                    break
                self._index_q.put((next_in, list(indices)))
                next_in += 1
                inflight += 1
            if exhausted and inflight == 0:
                return
            try:
                bid, batch, err = self._result_q.get(
                    timeout=timeout if timeout else None)
            except queue.Empty:
                self.shutdown()
                raise RuntimeError(
                    f"DataLoader worker timed out after {timeout}s")
            if err is not None:
                self.shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            inflight -= 1
            pending[bid] = batch
            while next_out in pending:
                yield pending.pop(next_out)
                next_out += 1

    def shutdown(self):
        if not self._alive:
            return
        self._alive = False
        for _ in self._procs:
            try:
                self._index_q.put(_SENTINEL)
            except Exception:
                pass
        for p in self._procs:
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass
