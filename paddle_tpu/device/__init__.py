"""Device management. Parity: python/paddle/device/__init__.py.

The reference dispatches over Places (CPUPlace/CUDAPlace/XPUPlace...,
paddle/fluid/platform/place.h); here the device set is whatever JAX
exposes (TPU chips, or CPU with --xla_force_host_platform_device_count for
sharding tests). There is no per-op placement: XLA owns placement, and
multi-device execution goes through jax.sharding (see distributed/).
"""
import jax

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_rocm",
           "is_compiled_with_xpu", "is_compiled_with_npu",
           "is_compiled_with_tpu", "synchronize", "get_device_properties",
           "cuda", "Stream", "Event",
           "max_memory_allocated", "memory_allocated",
           "max_memory_reserved", "memory_reserved"]

_current = None


def _default_device():
    return jax.devices()[0]


def set_device(device):
    global _current
    if isinstance(device, str):
        name = device.split(":")[0]
        idx = int(device.split(":")[1]) if ":" in device else 0
        if name == "tpu":
            # no silent stand-in: asking for the chip where there is
            # none is an error, not the CPU under another name
            devs = [d for d in jax.devices() if d.platform == "tpu"]
            if not devs:
                raise RuntimeError(
                    'set_device("tpu"): JAX reports no TPU device '
                    f"(devices: {get_all_devices()})")
        elif name in ("gpu", "cuda", "xpu", "npu"):
            devs = jax.devices()
        elif name == "cpu":
            devs = [d for d in jax.devices() if d.platform == "cpu"] or \
                jax.devices("cpu")
        else:
            devs = jax.devices()
        _current = devs[idx % len(devs)]
    else:
        _current = device
    return _current


def get_device():
    d = _current or _default_device()
    return f"{d.platform}:{d.id}"


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_cinn():
    return False


def is_compiled_with_tpu():
    return True


def is_compiled_with_ipu():
    return False


def is_compiled_with_mlu():
    return False


def synchronize(device=None):
    """Block until all queued device work is complete."""
    (jax.device_put(0) + 0).block_until_ready()


def _memory_stats(device=None):
    """jax.Device.memory_stats() for the selected device, {} when the
    backend exposes no allocator stats (CPU). Resolves "kind:idx"
    strings and plain int device ids (the common Paddle convention)
    WITHOUT touching the set_device global."""
    if isinstance(device, (str, int)):
        devs = jax.devices()
        if isinstance(device, int):
            idx = device
        else:
            idx = int(device.split(":")[1]) if ":" in device else 0
        d = devs[idx % len(devs)]
    elif device is not None:
        d = device
    else:
        d = _current or _default_device()
    if hasattr(d, "memory_stats"):
        try:
            return d.memory_stats() or {}
        except Exception:
            return {}
    return {}


def max_memory_allocated(device=None):
    """Peak bytes of device memory held by live buffers since process
    start (parity: paddle.device.cuda.max_memory_allocated). Backed by
    jax.Device.memory_stats()['peak_bytes_in_use'] — on TPU this is the
    HBM high-water mark, the number that proves a donated train step is
    NOT holding a second full copy of the model. The CPU backend exposes
    no allocator stats, so the process peak RSS stands in (keeps the API
    returning sane nonzero values everywhere). Each query lands in the
    telemetry store (a "device.memory" span + the device.peak_bytes
    gauge), so Profiler.summary() carries the memory high-water mark."""
    from ..profiler import statistic as _stat
    from ..profiler import monitor as _monitor
    with _stat.span("device.memory"):
        peak = _memory_stats(device).get("peak_bytes_in_use", 0)
        if not peak:
            import resource
            peak = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024
    _monitor.gauge("device.peak_bytes").set(int(peak))
    return int(peak)


def memory_allocated(device=None):
    """Bytes of device memory currently held by live buffers."""
    cur = int(_memory_stats(device).get("bytes_in_use", 0))
    from ..profiler import monitor as _monitor
    _monitor.gauge("device.bytes_in_use").set(cur)
    return cur


def max_memory_reserved(device=None):
    """Peak bytes the allocator reserved from the device (>= allocated)."""
    stats = _memory_stats(device)
    return int(stats.get("peak_bytes_reserved",
                         stats.get("peak_bytes_in_use", 0)))


def memory_reserved(device=None):
    """Bytes the allocator currently reserves from the device."""
    stats = _memory_stats(device)
    return int(stats.get("bytes_reserved", stats.get("bytes_in_use", 0)))


def get_device_properties(device=None):
    d = _current or _default_device()
    stats = _memory_stats(device)

    class _Props:
        name = str(d)
        major, minor = 0, 0
        total_memory = stats.get("bytes_limit", 0)
        multi_processor_count = 1
    return _Props()


class Stream:
    """XLA orders execution itself; streams are a no-op compatibility shim."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        self._t = None

    def record(self, stream=None):
        # dispatch is async; sync so the timestamp marks completed work
        synchronize()
        import time
        self._t = time.perf_counter()

    def query(self):
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event):
        """Milliseconds between two recorded events (CUDA Event parity)."""
        if self._t is None or end_event._t is None:
            raise RuntimeError("elapsed_time() on un-recorded events")
        return max((end_event._t - self._t) * 1000.0, 0.0)


from . import cuda  # noqa: E402  (real submodule, paddle parity)


# ------------------------------------------------- extra device-type API
# Parity: python/paddle/device/__init__.py (XPU/IPU/MLU places exist as
# types so user code can isinstance-check; all map onto the single TPU
# place — there is no per-op placement under XLA).

def get_cudnn_version():
    return None


class _AltPlace:
    def __init__(self, dev_id=0):
        self.dev_id = dev_id

    def __repr__(self):
        return f"{type(self).__name__}({self.dev_id})"

    def get_device_id(self):
        return self.dev_id


class XPUPlace(_AltPlace):
    pass


class IPUPlace(_AltPlace):
    def __init__(self):
        super().__init__(0)


class MLUPlace(_AltPlace):
    pass


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_device():
    return get_all_devices()


def get_available_custom_device():
    return []


__all__ += ["get_cudnn_version", "XPUPlace", "IPUPlace", "MLUPlace",
            "get_all_device_type", "get_all_custom_device_type",
            "get_available_device", "get_available_custom_device",
            "is_compiled_with_cinn", "is_compiled_with_ipu",
            "is_compiled_with_mlu"]
