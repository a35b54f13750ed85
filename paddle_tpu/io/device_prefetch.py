"""Device prefetch ring — H2D transfers overlapped with device compute.

The DataLoader's thread/process workers hide host-side batch *assembly*;
this module hides the last hop: `jax.device_put` of the assembled batch
onto the accelerator, placed with the train step's input shardings. A
background thread stages up to `depth` batches ahead of the consumer
while step k computes, so the steady-state step loop pops an
already-resident batch in ~0 time (the `dataloader.next` span goes flat)
and the device never waits on an H2D copy.

    ring = DevicePrefetchRing(loader_iter, depth=2,
                              sharding_fn=step.input_sharding)
    for batch in ring:           # Tensor leaves, already on device
        loss = step(*batch)      # _prep sees the sharding and skips the put

or, one level up, `DataLoader(..., prefetch_to_device=2)` — the hapi
`Model.fit` wires the step's `input_sharding` in automatically.

Telemetry: per-batch staging lands as the "prefetch.h2d" span, real
staging traffic (host arrays moved to device, or device arrays re-placed
to the step's sharding — NOT copy-free pass-throughs of already-placed
batches) in the `prefetch.h2d_bytes` counter, and the ring's fill level
in the `prefetch.depth` gauge (a gauge pinned at 0 means the consumer is
data-bound, not compute-bound).
"""
import queue
import threading

import numpy as np
import jax

from ..framework.core import Tensor
from ..profiler import statistic as _stat
from ..profiler import monitor as _monitor
from ..profiler import mem_observatory as _mobs

__all__ = ["DevicePrefetchRing", "device_prefetch_iterator"]

_END = object()


class _Failure:
    """Carries a producer-side exception to the consumer thread."""

    def __init__(self, exc):
        self.exc = exc


def _stage(x, sharding_fn):
    """device_put every array leaf of a batch structure (list/tuple/dict
    of Tensors / numpy arrays), placed per the step's input sharding;
    non-array leaves (strings, ints) pass through untouched."""
    if isinstance(x, Tensor):
        return Tensor(_put(x.value, sharding_fn))
    if isinstance(x, (list, tuple)):
        return [_stage(v, sharding_fn) for v in x]
    if isinstance(x, dict):
        return {k: _stage(v, sharding_fn) for k, v in x.items()}
    if isinstance(x, (np.ndarray, jax.Array)):
        return Tensor(_put(x, sharding_fn))
    return x


def _put(a, sharding_fn):
    """One staging hop, honestly accounted: a host (numpy) leaf moves to
    its target placement in a single device_put (the sharding_fn only
    reads ndim/shape, which numpy has); a device-resident jax array is
    re-placed only when its sharding differs from the target, and passes
    through FREE otherwise — so `prefetch.h2d_bytes` counts real staging
    traffic, not copy-free commits of already-resident batches."""
    sh = sharding_fn(a) if sharding_fn is not None else None
    if isinstance(a, jax.Array):
        if sh is None or getattr(a, "sharding", None) == sh:
            return a
        a = jax.device_put(a, sh)
    else:
        a = np.asarray(a)  # hot-sync-ok: host ndarray normalization, not a device read
        a = jax.device_put(a, sh) if sh is not None else jax.device_put(a)
    try:
        _monitor.counter("prefetch.h2d_bytes").inc(int(a.nbytes))
    except (AttributeError, TypeError):
        pass
    return a


class DevicePrefetchRing:
    """Bounded ring of device-resident batches, filled by a background
    thread. `depth` bounds device memory: at most `depth` staged batches
    queue ahead of the consumer, plus the one the producer is holding —
    size depth for HBM assuming depth+1 extra batches resident. Iterate
    it like any batch iterator; `close()` (or abandonment via
    `device_prefetch_iterator`) stops the producer promptly."""

    def __init__(self, source, depth=2, sharding_fn=None):
        self.depth = max(1, int(depth))
        self._sharding_fn = sharding_fn
        self._q = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._producer, args=(iter(source),),
            name="device-prefetch", daemon=True)
        self._thread.start()

    def _producer(self, it):
        try:
            while not self._stop.is_set():
                try:
                    batch = next(it)
                except StopIteration:
                    break
                with _stat.span("prefetch.h2d"):
                    staged = _stage(batch, self._sharding_fn)
                # memory-observatory attribution: per-array weakrefs to
                # the staged leaves — when the consumer drops the batch
                # the tag's bytes fall to zero by themselves
                _mobs.register_arrays(
                    "prefetch",
                    [x.value if isinstance(x, Tensor) else x
                     for x in jax.tree.leaves(staged)
                     if hasattr(x, "nbytes")
                     or isinstance(x, Tensor)])
                if not self._offer(staged):
                    return
                _monitor.gauge("prefetch.depth").set(self._q.qsize())
        except Exception as e:  # surface in the consumer, not a dead thread
            self._offer(_Failure(e))
            return
        self._offer(_END)

    def _offer(self, item):
        """put() that stays responsive to close(); False when stopped."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        _monitor.gauge("prefetch.depth").set(self._q.qsize())
        if item is _END:
            self._stop.set()
            raise StopIteration
        if isinstance(item, _Failure):
            self._stop.set()
            raise item.exc
        return item

    def close(self):
        """Stop the producer and release anything it staged."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        self._stop.set()


def device_prefetch_iterator(source, depth=2, sharding_fn=None):
    """Generator wrapper around DevicePrefetchRing that closes the ring
    when iteration ends OR is abandoned (break / GC) — the form
    DataLoader consumes."""
    ring = DevicePrefetchRing(source, depth=depth, sharding_fn=sharding_fn)
    try:
        for batch in ring:
            yield batch
    finally:
        ring.close()
