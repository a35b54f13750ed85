"""Readings of the program's own records over the window's steps: the
stalls it declared, the collector's pauses, and which share of the
device's idle time the thread that calls the step spent in no span.

The ring (paddle_tpu.profiler.statistic.closed_spans) stamps each span
on time.perf_counter_ns (`start_ns`), a clock the profiler's trace is a
constant offset from. Each `bench.step` event of the trace wraps exactly
one `train.step` call, so the trace's k `bench.step` events are paired
with the ring's last k `train.step` spans (no step runs after the
window) and the offset is the median of their start differences; a
pairing whose largest residual exceeds MAX_RESIDUAL_NS is refused.

The window's steps are the ring's last window["steps"] `train.step`
spans, from the first one's start to the last one's end.

what="stall_ms": the program's `kind:"stall"` records (a step interval
  over twice the median of the last 32: paddle_tpu.jit.api
  export_step_metrics) whose interval lies inside the window's steps,
  their excess over the median summed (ms). 0 in a run without a stall;
  untraced steps count too, so it covers the whole window.
what="gc_ms": the `host.gc` spans, on any thread, that start inside
  the window's steps, summed and divided by the steps (ms a step).
what="idle_unspanned": of the fullest device's idle time between the
  trace's first and last `bench.*` event (the head before its first op
  and the tail after its last one included), the share during which the
  step's thread was inside no span of the ring (%).

Each returns None where there is nothing to read: a program without the
ring's `start_ns` (older commits), too few steps in the ring, no
trace or a refused pairing, or no idle time."""
from statistics import median

from ..lib.xplane import overlap_ns, union_ns
from .span_ms import program_spans

STEP = "train.step"
MAX_RESIDUAL_NS = 100_000


def _end_ns(s):
    return s["start_ns"] + round(s["dur_s"] * 1e9)


def steps_of(spans, steps):
    """The ring's last `steps` train.step spans, or [] where the ring
    has no start_ns."""
    st = [s for s in spans if s["name"] == STEP and "start_ns" in s]
    return st[-steps:] if steps > 0 else []


def program_stalls():
    """The program's `kind:"stall"` records still in the flight
    recorder's ring (none in a program that makes none)."""
    from paddle_tpu.profiler import flight_recorder
    return [r for r in flight_recorder.snapshot()["records"]
            if r.get("kind") == "stall"]


def stall_ms(spans, stalls, steps):
    st = steps_of(spans, steps)
    if not st:
        return None
    lo, hi = st[0]["start_ns"], _end_ns(st[-1])
    return 1e3 * sum(r["interval_s"] - r["median_s"] for r in stalls
                     if lo <= r["start_ns"] and r["end_ns"] <= hi)


def gc_ms(spans, steps):
    st = steps_of(spans, steps)
    if not st:
        return None
    lo, hi = st[0]["start_ns"], _end_ns(st[-1])
    pause = sum(s["dur_s"] for s in spans
                if s["name"] == "host.gc" and lo <= s["start_ns"] < hi)
    return 1e3 * pause / len(st)


def pairing(spans, bench_steps):
    """(offset_ns, largest residual ns) mapping the ring's clock onto
    the trace's (trace = ring + offset), from the trace's `bench.step`
    events [(name, start_ns, end_ns)]; None where they cannot be paired
    or the residual exceeds MAX_RESIDUAL_NS."""
    bench = sorted(b[1] for b in bench_steps)
    ring = steps_of(spans, len(bench))
    if not bench or len(ring) < len(bench):
        return None
    diffs = [b - r["start_ns"] for b, r in zip(bench, ring)]
    off = median(diffs)
    worst = max(abs(d - off) for d in diffs)
    return None if worst > MAX_RESIDUAL_NS else (off, worst)


def idle_unspanned(spans, trace):
    if trace is None or not trace.host:
        return None
    mapped = pairing(spans, [h for h in trace.host if h[0] == "bench.step"])
    if mapped is None:
        return None
    off = mapped[0]
    lo = min(h[1] for h in trace.host)
    hi = max(h[2] for h in trace.host)
    idle, t = [], lo
    for s, e in trace.busy_merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    thread = steps_of(spans, 1)[0]["thread"]
    _, spanned = union_ns([(s["start_ns"] + off, _end_ns(s) + off)
                           for s in spans
                           if s["thread"] == thread and "start_ns" in s])
    covered = sum(overlap_ns(iv, spanned) for iv in idle)
    return 100.0 * (total - covered) / total


def read(ctx, what):
    spans = program_spans()
    if spans is None:
        return None
    steps = int(ctx["window"]["steps"])
    if what == "stall_ms":
        return stall_ms(spans, program_stalls(), steps)
    if what == "gc_ms":
        return gc_ms(spans, steps)
    if what == "idle_unspanned":
        return idle_unspanned(spans, ctx.get("trace"))
    raise ValueError(f"unknown reading {what!r}")
