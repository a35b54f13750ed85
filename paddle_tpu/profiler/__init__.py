"""paddle.profiler. Parity: python/paddle/profiler/ (profiler.py,
profiler_statistic.py, RecordEvent, export_chrome_tracing).

Two layers, like the reference:

- **Device traces** wrap jax.profiler — XLA/TPU-aware timelines (HLO op
  schedules, HBM usage) that open in TensorBoard/Perfetto, strictly more
  detail than the reference's chrome trace.
- **Host statistics** (`statistic.py`): `RecordEvent` records nested
  spans in-process in addition to the trace annotation, every framework
  hot path (jit compile, train step, DataLoader, collectives, memory
  queries) reports into the same store, and `Profiler.summary()` renders
  the aggregated table the reference's profiler_statistic.py prints.
  The metrics registry (`monitor.py`) and the cost-analysis helpers
  (`cost.py`) ride along. See docs/OBSERVABILITY.md.
"""
import json
import os
import time

import jax

from . import flight_recorder
from . import statistic
from . import monitor
from . import cost
from . import trace_export
from . import health
from . import compile_observatory
from . import serve_observatory
from . import dist_observatory
from . import mem_observatory
from .statistic import SortedKeys
from .health import AnomalyDetector

# arm the crash/hang debug-bundle triggers when the operator asked via
# env (PADDLE_TPU_DEBUG_DUMP / PADDLE_TPU_WATCHDOG_S /
# PADDLE_TPU_SIGQUIT_STACKS); otherwise installs nothing
flight_recorder.auto_install()

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "ProfilerResult", "SortedKeys",
           "statistic", "monitor", "cost", "flight_recorder",
           "trace_export", "health", "compile_observatory",
           "serve_observatory", "dist_observatory", "mem_observatory",
           "AnomalyDetector"]


class ProfilerTarget:
    CPU = 0
    GPU = 1
    TPU = 5


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        cycle = closed + ready + record
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """Reference-signature on_trace_ready handler: when the profiler
    stops, write the unified Chrome trace (host spans + counter tracks +
    step/serve records, see trace_export.py) into `dir_name`."""
    def handler(prof):
        prof._export_dir = dir_name
        prof._worker_name = worker_name
    return handler


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._scheduler = scheduler
        self._on_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = None
        self._worker_name = None
        self._dir = os.environ.get("PADDLE_PROFILER_DIR",
                                   "/tmp/paddle_tpu_profile")
        self._active = False
        self._step = 0
        self._step_times = []
        self._t0 = None

    def start(self):
        if not self._timer_only:
            os.makedirs(self._dir, exist_ok=True)
            jax.profiler.start_trace(self._dir)
            self._active = True
        self._t0 = time.perf_counter()

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
        self.export_host_stats()
        if self._on_ready:
            self._on_ready(self)
        if self._export_dir:  # export_chrome_tracing(dir) handler
            try:
                self.export_chrome_tracing(self._export_dir)
            except Exception:
                pass  # telemetry never takes the process down

    def export_chrome_tracing(self, path, worker_name=None):
        """Write the unified Chrome-trace-event JSON (host spans as
        per-thread tracks, metric counter tracks, train-step / serving
        batch tracks, anomaly markers — trace_export.py) to `path` and
        return the file path. `path` may be a directory (reference
        export_chrome_tracing semantics): the file lands there as
        `<worker_name or paddle_tpu_trace.rank<r>>.json`. Opens in
        Perfetto / chrome://tracing; `tools/merge_traces.py` merges
        per-rank files."""
        name = worker_name or getattr(self, "_worker_name", None)
        if os.path.isdir(path) or not path.endswith(".json"):
            fname = f"{name or f'paddle_tpu_trace.rank{monitor.rank()}'}" \
                    f".json"
            path = os.path.join(path, fname)
        return trace_export.write_chrome_trace(
            path, extra={"step_times_s": list(self._step_times)})

    def export_host_stats(self, path=None):
        """Write the aggregated host spans + metrics registry to
        `<PADDLE_PROFILER_DIR>/host_stats.json` (or `path`) — the
        artifact `load_profiler_result` reads back. Non-zero ranks get a
        `host_stats.rank<r>.json` suffix so a shared profiler dir keeps
        every rank's payload instead of last-writer-wins. Returns the
        path, or None when the filesystem refuses (telemetry never
        raises)."""
        if path is None:
            r = monitor.rank()
            name = "host_stats.json" if r == 0 else \
                f"host_stats.rank{r}.json"
            path = os.path.join(self._dir, name)
        payload = {"schema": "paddle_tpu.host_stats.v1",
                   "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime()),
                   "rank": monitor.rank(),
                   "step_times_s": list(self._step_times),
                   "spans": statistic.snapshot(),
                   "metrics": monitor.metrics_snapshot(),
                   "compiles": compile_observatory.ledger(),
                   "collectives": dist_observatory.collectives_tail(),
                   "rankstats": dist_observatory.rankstats_tail(),
                   "memories": mem_observatory.records_tail(),
                   "clock_offset_s": dist_observatory.clock_offset_s()}
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                json.dump(payload, f)
        except (OSError, TypeError, ValueError):
            return None
        return path

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        arr = np.asarray(self._step_times[1:] or self._step_times)
        return (f"avg step {arr.mean()*1000:.2f}ms "
                f"(p50 {np.percentile(arr, 50)*1000:.2f}ms, "
                f"p99 {np.percentile(arr, 99)*1000:.2f}ms)")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated host-span table + metrics registry + derived
        performance accounting (cost-analysis FLOPs / MFU gauges the
        instrumented train steps publish). Prints AND returns the text
        (the reference prints; returning makes it testable/loggable)."""
        parts = [self.step_info(),
                 "",
                 "----- host spans (RecordEvent + framework hot paths) "
                 "-----",
                 statistic.summary_table(sorted_by=sorted_by,
                                         time_unit=time_unit,
                                         thread_sep=thread_sep)]
        metrics = monitor.metrics_snapshot()
        if metrics:
            parts += ["", "----- metrics registry -----"]
            for name, val in metrics.items():
                if isinstance(val, dict):  # histogram stats
                    parts.append(
                        f"{name:<44}  count={val['count']} "
                        f"avg={val['avg']*1e3:.3f}ms "
                        f"max={val['max']*1e3:.3f}ms")
                else:
                    parts.append(f"{name:<44}  {val}")
        flops = metrics.get("train.flops_per_step", 0)
        if flops:
            peak = cost.device_peak_flops()
            parts += ["", "----- cost analysis (XLA) -----",
                      f"train step FLOPs:        {flops:.3e}",
                      f"train step bytes:        "
                      f"{metrics.get('train.bytes_per_step', 0):.3e}",
                      f"device nominal peak:     "
                      f"{peak:.3e} FLOP/s" if peak else
                      "device nominal peak:     unknown (CPU backend)",
                      f"last-step MFU:           "
                      f"{metrics.get('train.mfu', 0):.4f}"]
        if not self._timer_only and self._t0 is not None:
            parts += ["", f"device trace written to {self._dir} (open in "
                          "TensorBoard/Perfetto)"]
        text = "\n".join(parts)
        print(text)
        return text

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class RecordEvent:
    """Named region: one span of the in-process statistics store
    (statistic.begin_span / end_span), which annotates the device trace
    (jax.profiler.TraceAnnotation) AND records the nested host span, so
    `Profiler.summary()` can render real aggregated tables without a
    trace viewer."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._open = False

    def begin(self):
        statistic.begin_span(self.name)
        self._open = True

    def end(self):
        if self._open:
            self._open = False
            statistic.end_span()

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class ProfilerResult:
    """Queryable view over exported telemetry: host-span aggregates
    (`spans`, `get`, `total_s`), per-step metric records (`steps`), the
    metrics registry snapshot (`metrics`), the compilation ledger
    (`compiles` — the raw `kind:"compile"` records; `compile_ledger()`
    rolls them up per executable tag), and the distributed
    observatory's records (`collectives` — sampled `kind:"collective"`
    timing records; `rankstats` — per-rank `kind:"rankstat"` skew
    records), and the memory observatory's periodic device-memory
    ledger records (`memories` — `kind:"memory"`)."""

    def __init__(self, spans=None, metrics=None, steps=None,
                 step_times_s=None, source=None, compiles=None,
                 collectives=None, rankstats=None, memories=None):
        self.span_tree = spans or []
        self.spans = statistic.flatten(self.span_tree)
        self.metrics = metrics or {}
        self.steps = steps or []
        self.step_times_s = step_times_s or []
        self.compiles = compiles or []
        self.collectives = collectives or []
        self.rankstats = rankstats or []
        self.memories = memories or []
        self.source = source

    def get(self, name):
        """All aggregated span records with this name (any nesting)."""
        return [s for s in self.spans if s["name"] == name]

    def total_s(self, name):
        return sum(s["total_s"] for s in self.get(name))

    def compile_ledger(self):
        """{tag: {lower_s, compile_s, cache_hit, signatures,
        fusion_count, bytes_accessed, instructions, ...}} — the
        per-executable rollup of the loaded `kind:"compile"` records
        (compile_observatory.aggregate)."""
        return compile_observatory.aggregate(self.compiles)

    def summary(self):
        names = sorted({s["name"] for s in self.spans})
        return (f"ProfilerResult({self.source}): {len(self.spans)} span "
                f"rows ({', '.join(names[:8])}"
                f"{'...' if len(names) > 8 else ''}), "
                f"{len(self.steps)} step records, "
                f"{len(self.compiles)} compile records, "
                f"{len(self.collectives)} collective records, "
                f"{len(self.rankstats)} rankstat records, "
                f"{len(self.memories)} memory records, "
                f"{len(self.metrics)} metrics")

    def __repr__(self):
        return self.summary()


def load_profiler_result(filename):
    """Load exported telemetry back into a queryable ProfilerResult.

    Accepts: a profiler directory (reads its host_stats.json), the
    host_stats.json itself, or a metrics JSONL file written via
    PADDLE_TPU_METRICS_FILE (one JSON object per line; `kind == "step"`
    records land in `.steps`, `kind == "compile"` in `.compiles`,
    `kind == "collective"` in `.collectives`, `kind == "rankstat"` in
    `.rankstats`, `kind == "memory"` in `.memories`)."""
    path = filename
    if os.path.isdir(path):
        path = os.path.join(path, "host_stats.json")
    with open(path) as f:
        text = f.read()
    try:
        payload = json.loads(text)
    except ValueError:
        payload = None
    if isinstance(payload, dict) and "spans" in payload:
        return ProfilerResult(spans=payload.get("spans"),
                              metrics=payload.get("metrics"),
                              step_times_s=payload.get("step_times_s"),
                              compiles=payload.get("compiles"),
                              collectives=payload.get("collectives"),
                              rankstats=payload.get("rankstats"),
                              memories=payload.get("memories"),
                              source=path)
    # JSONL metrics export: one object per line
    by_kind = {"step": [], "compile": [], "collective": [],
               "rankstat": [], "memory": []}
    other = []
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError as e:
            raise ValueError(
                f"{path}:{lineno}: not a host_stats.json export and not "
                f"valid JSONL ({e})") from None
        by_kind.get(rec.get("kind"), other).append(rec)
    result = ProfilerResult(steps=by_kind["step"],
                            compiles=by_kind["compile"],
                            collectives=by_kind["collective"],
                            rankstats=by_kind["rankstat"],
                            memories=by_kind["memory"], source=path)
    result.records = (by_kind["step"] + by_kind["compile"] +
                      by_kind["collective"] + by_kind["rankstat"] +
                      by_kind["memory"] + other)
    return result
