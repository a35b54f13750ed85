"""Global metrics registry: counters / gauges / histograms + a JSONL
per-step exporter.

Every framework hot path reports here (jit compiles and retraces, train
steps, DataLoader batch waits, collectives, device memory peaks), so a
training process carries its own always-on flight recorder.

Async-pipeline signals (the host-overlap story, docs/PERFORMANCE.md
"Hiding the host"): `host.blocked_s` (histogram — every time the host
actually blocked on a device read, recorded by DeferredLoss; sum via
`host_blocked_s()`), `prefetch.h2d_bytes` (counter — bytes staged onto
the device by the prefetch ring), `prefetch.depth` (gauge — ring fill
level; pinned at 0 means the step loop is data-bound).

Distributed signals (the distributed observatory,
profiler/dist_observatory.py — docs/OBSERVABILITY.md "The distributed
observatory"): `collective.<kind>.calls` / `collective.<kind>.bytes`
counters (every collective call site),
`dist.rankstats` counter (per-rank `kind:"rankstat"` records emitted)
and `dist.stragglers` counter (rank-0 `event:"straggler"` detections).
The sampled per-collective detail (`kind:"collective"`: op, group,
bytes, wall_s, bus-bandwidth GB/s) and the periodic `kind:"rankstat"`
records ride the JSONL exporter below.

Serving signals (the continuous-batching engines, docs/SERVING.md):
`serve.queue_depth` / `serve.shared_pages` / `serve.kv_free_pages` /
`serve.kv_held_pages` / `serve.kv_registered_pages` /
`serve.kv_evictable_pages` / `serve.kv_peak_held_pages` gauges,
`serve.batch_size` / `serve.latency_s` / `serve.ttft_s` /
`serve.tpot_s` histograms, `serve.requests` / `serve.rejected` /
`serve.expired` / `serve.pad_tokens` / `serve.retraces` /
`serve.errors` / `serve.prefix_hits` / `serve.chunked_prefill_tokens` /
`serve.generated_tokens` / `serve.goodput_tokens` /
`serve.wasted_tokens` counters (the kv_*/goodput split is maintained by
profiler/serve_observatory.py, which also emits the per-request
`kind:"request"` and page-pool `kind:"kvcache"` records).
Histograms keep a bounded reservoir of recent observations, so tail
latency is queryable in-process: `histogram("serve.latency_s")
.percentile(99)` — and `snapshot()` carries `p50`/`p99` from the same
reservoir, so `metrics_snapshot()` and `load_report()` serialize tail
latency without callers reaching into `percentile()`.

Registry usage:

    from paddle_tpu.profiler import monitor
    monitor.counter("jit.retraces").inc()
    monitor.gauge("train.mfu").set(0.41)
    monitor.histogram("dataloader.wait_s").observe(dt)
    monitor.metrics_snapshot()   # {name: value-or-stats}

Exporter: with `PADDLE_TPU_METRICS_FILE` set, `export_step(record)`
appends ONE JSON object per line, tagged with a wall-clock `ts`, the
process `rank` (from the launch env), and a `kind`. TrainStep /
HybridTrainStep call it once per optimizer step with the documented step
schema (step, step_time_s, compile_s, cache_hit, peak_bytes, flops, mfu
— validated by tools/check_metrics_schema.py); see docs/OBSERVABILITY.md.

Record kinds riding the exporter (one line each; full field schemas in
tools/check_metrics_schema.py):

    step        one per optimizer step (TrainStep / HybridTrainStep)
    scan        one per scanned-layer-group step (scan-over-layers path)
    serve       one per dispatched serving batch (GenerationEngine)
    health      one per resolved async health vector (health monitor)
    event       structured anomaly/lifecycle events (flight recorder)
    compile     one per AOT-compiled executable signature (aot_warmup)
    warm        one per resolved warm set (aot_warmup manifests)
    lint        one per static-analysis finding (tools/lint/paddlelint)
    seed        one per compile-cache seeding (persistent cache)
    ckpt        one per checkpoint save/restore/GC (checkpointing)
    request     ONE per request at its terminal state (serve observatory;
                outcome "handoff" closes the prefill half of a
                disaggregated request, the decode half re-emits)
    route       ONE per router decision: dispatch / reject / handoff
    kvcache     periodic KV page-pool snapshot (serve observatory)
    collective  sampled per-collective timing (dist observatory)
    rankstat    periodic per-rank skew telemetry (dist observatory)
    journey     ONE per handed-off request at decode-terminal time:
                queue/prefill/handoff-gap/decode phase split
                (profiler/fleet_observatory.py)
    fleet       periodic router-level fleet snapshot: per-engine
                rollup, shared-pool claims, rates, SLO attainment
                (fleet observatory)
    harness     ONE summary per tools/load_harness.py open-loop run
    memory      periodic device-memory attribution: per-tag ledger
                bytes, attributed/unattributed split, pool occupancy
                + fragmentation (mem observatory; train and serve
                cadences both emit it)
"""
import collections
import json
import os
import threading
import time

from . import flight_recorder

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge",
           "histogram", "get_metric", "metrics_snapshot", "reset_metrics",
           "rank", "metrics_file", "export_step", "host_blocked_s",
           "set_clock_offset", "clock_offset"]

# this rank's estimated wall-clock offset vs rank 0 (seconds), set by
# the distributed observatory's coordinator handshake
# (dist_observatory.clock_sync at init_parallel_env); stamped onto
# every exported record when nonzero so tools/merge_traces.py can
# clock-align per-rank artifacts
_clock_offset = [0.0]


def set_clock_offset(offset_s):
    _clock_offset[0] = float(offset_s)


def clock_offset():
    return _clock_offset[0]

_lock = threading.RLock()
_export_lock = threading.Lock()  # file appends only: registry ops must
_registry = {}                   # never stall behind metrics-file I/O


class Counter:
    """Monotonically increasing count (calls, bytes, cache hits)."""
    kind = "counter"

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, v=1):
        with _lock:
            self.value += v
            out = self.value
        flight_recorder.record_sample(self.name, "counter", out)
        return out

    def snapshot(self):
        return self.value


class Gauge:
    """Last-observed value (peak bytes, current MFU)."""
    kind = "gauge"

    def __init__(self, name):
        self.name = name
        self.value = 0

    def set(self, v):
        with _lock:
            self.value = v
        flight_recorder.record_sample(self.name, "gauge", v)
        return v

    def snapshot(self):
        return self.value


class Histogram:
    """Streaming count/sum/min/max/last of observations (durations),
    plus a bounded reservoir of the most recent `RESERVOIR` samples for
    percentile queries (serving tail latency: p50/p99)."""
    kind = "histogram"

    RESERVOIR = 2048  # recent-window size for percentile()

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = 0.0
        self.last = 0.0
        self._samples = collections.deque(maxlen=self.RESERVOIR)

    def observe(self, v):
        v = float(v)
        with _lock:
            self.count += 1
            self.sum += v
            self.last = v
            self._samples.append(v)
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
        flight_recorder.record_sample(self.name, "histogram", v)

    @property
    def avg(self):
        return self.sum / self.count if self.count else 0.0

    @staticmethod
    def _nearest_rank(s, p):
        """Nearest-rank pick from an already-sorted sample list."""
        if not s:
            return 0.0
        idx = min(len(s) - 1,
                  max(0, int(round(float(p) / 100.0 * (len(s) - 1)))))
        return s[idx]

    def percentile(self, p):
        """Nearest-rank percentile (p in [0, 100]) over the reservoir of
        the last RESERVOIR observations — a recent window, not all-time
        (all-time min/max/avg stay exact in the streaming fields)."""
        with _lock:
            s = sorted(self._samples)
        return self._nearest_rank(s, p)

    def snapshot(self):
        # p50/p99 ride along (reservoir window, like percentile()): the
        # serialized forms — metrics_snapshot, host_stats.json, serving
        # load_report — carry tail latency without a percentile() call.
        # ONE sort serves both ranks (metrics_snapshot walks every
        # histogram under the registry lock)
        with _lock:
            s = sorted(self._samples)
            snap = {"count": self.count, "sum": self.sum,
                    "avg": self.avg,
                    "min": self.min if self.count else 0.0,
                    "max": self.max, "last": self.last}
        snap["p50"] = self._nearest_rank(s, 50)
        snap["p99"] = self._nearest_rank(s, 99)
        return snap


def _get_or_create(name, cls):
    with _lock:
        m = _registry.get(name)
        if m is None:
            m = _registry[name] = cls(name)
        elif not isinstance(m, cls):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(m).__name__}, requested {cls.__name__}")
        return m


def counter(name):
    return _get_or_create(name, Counter)


def gauge(name):
    return _get_or_create(name, Gauge)


def histogram(name):
    return _get_or_create(name, Histogram)


def get_metric(name):
    return _registry.get(name)


def metrics_snapshot():
    """{name: scalar (counter/gauge) or stats dict (histogram)} — JSON
    serializable, sorted by name."""
    with _lock:
        return {name: _registry[name].snapshot()
                for name in sorted(_registry)}


def reset_metrics():
    with _lock:
        _registry.clear()


def host_blocked_s():
    """Total seconds the host has spent blocked on device reads (the
    `host.blocked_s` histogram sum) — ~0 in a healthy async step loop,
    where the only blocks are log_freq/epoch boundaries."""
    m = get_metric("host.blocked_s")
    return float(m.sum) if m is not None else 0.0


def rank():
    """This process's rank from the launch env (0 single-controller).
    Read from env, NOT jax.process_index(): telemetry must never force
    backend init."""
    for var in ("PADDLE_TPU_PROCESS_ID", "PADDLE_TRAINER_ID"):
        v = os.environ.get(var)
        if v is not None and v != "":
            try:
                return int(v)
            except ValueError:
                pass
    return 0


def metrics_file():
    """The JSONL export path, or None when export is off."""
    return os.environ.get("PADDLE_TPU_METRICS_FILE") or None


def export_step(record, kind="step", _ring=True):
    """Append one rank-tagged JSON line to PADDLE_TPU_METRICS_FILE.
    The record also lands in the flight-recorder ring (always on, file
    or no file), so a debug bundle carries the recent step/serve/health
    tail even for a process that never configured an export path.
    Returns False when the env var is unset or the write failed; never
    raises — telemetry must not take down a train loop."""
    rec = {"ts": time.time(), "rank": rank(), "kind": kind}
    if _clock_offset[0]:
        rec["clock_offset_s"] = _clock_offset[0]
    rec.update(record)
    if _ring:  # events ring-record themselves (flight_recorder)
        flight_recorder.record_record(rec)
    path = metrics_file()
    if not path:
        return False
    try:
        line = json.dumps(rec)
    except (TypeError, ValueError):
        return False
    try:
        with _export_lock, open(path, "a") as f:
            f.write(line + "\n")
    except OSError:
        return False
    return True
