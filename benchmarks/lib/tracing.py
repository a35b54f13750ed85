"""The traced window: jax.profiler around the LAST seconds of the
measured window (so that stopping the profiler, which takes a while,
falls outside it), the benchmark's own host spans on the same clock, and
the hand-over to the reduction (xplane.py). With tracing off every call
here is a no-op, so the end-to-end run pays nothing for it."""
import contextlib
import glob
import os
import shutil
import time


class Tracer:
    def __init__(self, out_dir, max_seconds):
        self.dir = out_dir
        self.max_seconds = float(max_seconds)
        self.active = False
        self.window_s = None
        self.t_on = None
        self._start_at = None

    def arm(self, on, seconds):
        """Tracing starts at tick(elapsed >= seconds - max_seconds)."""
        if on:
            self._start_at = max(0.0, seconds - self.max_seconds)

    def tick(self, elapsed):
        if self._start_at is None or elapsed < self._start_at:
            return
        import jax
        self._start_at = None
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # every Python call: 30x the file
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_on = time.perf_counter()
        self.active = True

    def span(self, name):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stop(self):
        if self.active:
            import jax
            self.window_s = time.perf_counter() - self.t_on
            jax.profiler.stop_trace()
            self.active = False

    def reduce(self):
        """The reduced trace (xplane.Trace) of the window, or None with
        tracing off. The raw files are removed: a run writes little."""
        if self.window_s is None:
            return None
        from . import xplane
        pbs = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                        recursive=True)
        if not pbs:
            raise RuntimeError(f"the profiler left no .xplane.pb under "
                               f"{self.dir}")
        try:
            return xplane.Trace.from_file(pbs[0], self.window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
