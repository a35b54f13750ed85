"""Deferred device scalars — the non-blocking half of the async step loop.

`TrainStep` / `HybridTrainStep` dispatch one fused XLA program per step
and, under JAX's async dispatch, return before the device finishes. The
old `float(loss.item())` in every train loop threw that away: each step
blocked the host on the previous step's result, serializing dispatch
with compute. A `DeferredLoss` keeps the pipeline moving:

- construction starts a device->host copy (`jax.Array.copy_to_host_async`)
  and returns immediately — by the time anyone reads the value, the DMA
  has usually already landed;
- it IS a `Tensor` (drop-in for every existing `loss.item()` /
  `loss.value` call site), so nothing downstream needs to know;
- any host read (`float()`, `.item()`, `.numpy()`) resolves at most
  once, and the time the host actually spent blocked is recorded — the
  `host.block` span and the `host.blocked_s` histogram — so synchronous
  pressure shows up in telemetry instead of hiding inside step time.

The hapi fit loop holds these handles unresolved until a `log_freq`
boundary or epoch end; `tools/check_no_hot_sync.py` lints the hot paths
so a blocking read can't sneak back in.
"""

import numpy as np

from ..framework.core import Tensor
from ..profiler import statistic as _stat
from ..profiler import monitor as _monitor

__all__ = ["DeferredLoss"]


class DeferredLoss(Tensor):
    """A scalar (or small) device array whose host value is fetched
    lazily. See module docstring for the overlap contract."""

    def __init__(self, value):
        arr = value.value if isinstance(value, Tensor) else value
        super().__init__(arr)
        self._resolved = None
        try:
            # start the D2H DMA now; the eventual np.asarray only waits
            # for whatever is still in flight
            arr.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass  # non-jax array (tests) or backend without async copy

    def numpy(self):
        if self._resolved is None:
            _stat.begin_span("host.block")
            try:
                out = np.asarray(self.value)
            finally:
                dt = _stat.end_span()
            _monitor.histogram("host.blocked_s").observe(dt)
            self._resolved = out
        return self._resolved

    def resolve(self):
        """Blocking fetch as a python float (cached)."""
        return float(self.numpy().reshape(()))

    def __format__(self, spec):
        # keep pre-deferred callbacks working: f"{logs['loss'][0]:.4f}"
        # resolves here (the reader opted into a host sync)
        return format(self.resolve(), spec)
