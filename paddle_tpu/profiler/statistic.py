"""Host-side span/event statistics — the in-process half of the profiler.

Parity: python/paddle/profiler/profiler_statistic.py (the RecordEvent
summary tables). The reference aggregates C++ HostTraceLevel events into
nested per-name tables; here `RecordEvent` (and every instrumented
framework hot path — jit compile, train step, DataLoader, collectives,
memory queries) reports into this module's in-process recorder, and
`Profiler.summary()` renders the aggregated table. The device-side story
stays with jax.profiler (XLA op timelines in TensorBoard/Perfetto), and
every span opened here is also a `jax.profiler.TraceAnnotation`: in any
jax.profiler trace it appears in the `/host:CPU` plane, on the thread
that did the work and on the clock the device ops are on, so an idle
gap of the device can be put down to the span that covers it. With no
profiler session the annotation is jax's own no-op.

The ring of closed spans (`closed_spans()`, the flight recorder's) is on
another clock: `time.perf_counter_ns()`, stamped where the span opens
(`start_ns`; `start_s` is the same stamp in seconds). A trace's events
carry the profiler session's own clock, a constant offset away. The
offset is found from pairs: where the trace holds a host event that
wraps exactly one ring span (the benchmark's `bench.step` around each
`train.step` call), pair the trace's k such events with the ring's last
k spans of that name; the offset is the median of their start
differences, and a pairing whose largest residual from that median
exceeds 100 us is refused (benchmarks/readers/host_spans.py). Mapped
so, every span in the ring lies on the device ops' timeline, also those
that closed before the trace started.

Garbage collections are recorded too: a `gc.callbacks` hook, installed
when this module is imported, counts every collection in
`profiler.monitor` (`gc.collections.gen<k>`, `gc.pause_s.gen<k>`) and
puts it in the ring as a `host.gc` span carrying its generation, on the
thread that collected, at that thread's depth. It is not an annotation
and not in the aggregate tree: a collection can start inside any
allocation, also one this module makes while it walks the tree.

Spans nest: a span that begins while another is open on the same thread
becomes its child, and the summary table indents children under their
parent with per-node call counts, total/avg/max wall time, and the share
of all recorded top-level time. Threads merge into one tree (a node
remembers which threads hit it); `thread_sep=True` renders one tree per
thread.
"""
import gc
import threading
import time

from . import flight_recorder
from . import monitor

__all__ = ["SpanNode", "span", "begin_span", "end_span", "closed_spans",
           "coverage", "gc_pause_s", "reset_statistics", "snapshot",
           "summary_table", "get_events", "SortedKeys"]


class SortedKeys:
    """Parity: paddle.profiler.SortedKeys (subset: host-side orders)."""
    CPUTotal = "total"
    CPUAvg = "avg"
    CPUMax = "max"
    Calls = "calls"


class SpanNode:
    """One aggregated named span at one position in the nesting tree."""
    __slots__ = ("name", "count", "total", "max", "min", "threads",
                 "children")

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.min = float("inf")
        self.threads = set()
        self.children = {}

    def add(self, seconds, thread_ident):
        self.count += 1
        self.total += seconds
        if seconds > self.max:
            self.max = seconds
        if seconds < self.min:
            self.min = seconds
        self.threads.add(thread_ident)

    def child(self, name):
        node = self.children.get(name)
        if node is None:
            node = SpanNode(name)
            self.children[name] = node
        return node

    def to_dict(self):
        return {"name": self.name, "count": self.count,
                "total_s": self.total, "max_s": self.max,
                "min_s": self.min if self.count else 0.0,
                "avg_s": self.total / self.count if self.count else 0.0,
                "threads": sorted(self.threads),
                "children": [c.to_dict()
                             for c in self.children.values()]}


_lock = threading.RLock()
_root = SpanNode("<root>")
_tls = threading.local()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def begin_span(name, **args):
    """Open a span on this thread; nested begins become children. The
    span is also entered as a jax.profiler.TraceAnnotation carrying
    `args` (a StepTraceAnnotation where `step_num` is among them), held
    on the thread's stack until end_span leaves it."""
    import jax  # on first use, not when this module is imported
    ann = (jax.profiler.StepTraceAnnotation if "step_num" in args
           else jax.profiler.TraceAnnotation)(name, **args)
    ann.__enter__()
    _stack().append((name, time.perf_counter_ns(), ann))


def end_span():
    """Close the innermost open span on this thread and record it;
    returns its duration in seconds."""
    st = _stack()
    if not st:
        return 0.0
    name, t0, ann = st.pop()
    dur = time.perf_counter_ns() - t0
    ann.__exit__(None, None, None)
    ident = threading.get_ident()
    with _lock:
        node = _root
        for e in st:
            node = node.child(e[0])
        node.child(name).add(dur * 1e-9, ident)
    # raw event tail for the timeline view (trace_export.py): the
    # aggregation above answers "how much", the flight-recorder ring
    # answers "when" — a bounded deque append, negligible per span
    flight_recorder.record_span_event(name, t0, dur, ident, len(st))
    return dur * 1e-9


def closed_spans():
    """The recorder's tail of closed spans, in the order they closed:
    dicts of name, start_ns (time.perf_counter_ns clock), start_s (the
    same in seconds), dur_s, thread (ident), depth (open spans above it
    on its thread) and, for the spans that carry them, args. A parent
    follows its children; the ring holds the newest
    flight_recorder.SPAN_RING."""
    out = []
    for n, t0, d, tid, depth, args in flight_recorder.span_events():
        e = {"name": n, "start_ns": t0, "start_s": t0 * 1e-9,
             "dur_s": d * 1e-9, "thread": tid, "depth": depth}
        if args is not None:
            e["args"] = args
        out.append(e)
    return out


# the ring is in closing order only to within the time a thread takes
# from its span's end stamp to the append (a thread switch at most, in
# practice): a span that closed this long before an interval ends the
# walk back through the ring
_RING_ORDER_NS = 10_000_000


def coverage(t0_ns, t1_ns):
    """What the ring says the process did in [t0_ns, t1_ns]
    (time.perf_counter_ns clock): ({name: seconds} of the spans on this
    thread that overlap it, open ones included; the same on every other
    thread; the seconds of the interval that no span on this thread
    covers). Nested spans each count under their own name. Walks the
    ring back from its newest span to the first one that closed well
    before t0_ns."""
    mine, others, cover = {}, {}, []
    me = threading.get_ident()
    spans = [(n, s, t1_ns, me) for n, s, _ in _stack()]
    for n, s, d, tid, _, _ in reversed(flight_recorder.span_events()):
        if s + d < t0_ns - _RING_ORDER_NS:
            break
        spans.append((n, s, s + d, tid))
    for name, s, e, tid in spans:
        lo, hi = max(s, t0_ns), min(e, t1_ns)
        if hi <= lo:
            continue
        into = mine if tid == me else others
        into[name] = into.get(name, 0.0) + (hi - lo) * 1e-9
        if tid == me:
            cover.append((lo, hi))
    covered, end = 0, t0_ns
    for lo, hi in sorted(cover):
        if hi > end:
            covered += hi - max(lo, end)
            end = hi
    return mine, others, (t1_ns - t0_ns - covered) * 1e-9


# -- garbage collections -------------------------------------------------
_gc_t0 = [0]
_gc_pause_ns = [0]


def gc_pause_s():
    """Seconds of collector pause since the process started, on every
    thread."""
    return _gc_pause_ns[0] * 1e-9


def _on_gc(phase, info):
    # collections never overlap (the collector holds the GIL and does
    # not nest), so one start slot serves every thread
    now = time.perf_counter_ns()
    if phase == "start":
        _gc_t0[0] = now
        return
    dur = now - _gc_t0[0]
    gen = info["generation"]
    _gc_pause_ns[0] += dur
    try:  # a hook that raises is reported on stderr at every collection
        monitor.counter(f"gc.collections.gen{gen}").inc()
        monitor.counter(f"gc.pause_s.gen{gen}").inc(dur * 1e-9)
        flight_recorder.record_span_event(
            "host.gc", _gc_t0[0], dur, threading.get_ident(),
            len(_stack()), {"gen": gen})
    except Exception:
        pass


if not any(getattr(cb, "__qualname__", "") == _on_gc.__qualname__
           and getattr(cb, "__module__", "") == __name__
           for cb in gc.callbacks):
    gc.callbacks.append(_on_gc)


class span:
    """Context manager: `with statistic.span("phase"): ...`"""

    def __init__(self, name, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        begin_span(self.name, **self.args)
        return self

    def __exit__(self, *exc):
        end_span()
        return False


def reset_statistics():
    """Drop all aggregated spans (open spans keep timing and will record
    into the fresh tree when they close)."""
    global _root
    with _lock:
        _root = SpanNode("<root>")


def snapshot():
    """The aggregated span tree as plain dicts (JSON-serializable)."""
    with _lock:
        return [c.to_dict() for c in _root.children.values()]


def get_events(name=None):
    """Flat list of aggregated span records ({path, name, count, total_s,
    avg_s, max_s}); filtered to `name` when given. The queryable form
    load_profiler_result also returns."""
    return flatten(snapshot(), name)


def flatten(tree, name=None, _prefix=""):
    out = []
    for node in tree:
        path = f"{_prefix}/{node['name']}" if _prefix else node["name"]
        rec = {k: node[k] for k in ("name", "count", "total_s", "avg_s",
                                    "max_s", "min_s")}
        rec["path"] = path
        if name is None or node["name"] == name:
            out.append(rec)
        out.extend(flatten(node["children"], name, path))
    return out


_UNIT = {"s": 1.0, "ms": 1e3, "us": 1e6}


def _sort_key(sorted_by):
    return {"total": lambda n: n["total_s"],
            "avg": lambda n: n["avg_s"],
            "max": lambda n: n["max_s"],
            "calls": lambda n: n["count"]}.get(sorted_by or "total",
                                               lambda n: n["total_s"])


def summary_table(sorted_by="total", time_unit="ms", thread_sep=False):
    """Render the aggregated host-span table (parity: the reference's
    profiler_statistic summary). Children indent under their parent;
    Ratio is each node's share of the summed top-level wall time."""
    tree = snapshot()
    if not tree:
        return "no host spans recorded"
    scale = _UNIT.get(time_unit, 1e3)
    unit = time_unit if time_unit in _UNIT else "ms"
    grand = sum(n["total_s"] for n in tree) or 1.0
    widths = (44, 8, 12, 12, 12, 8)
    header = ("Name", "Calls", f"Total({unit})", f"Avg({unit})",
              f"Max({unit})", "Ratio")
    sep = "  ".join("-" * w for w in widths)

    def fmt_row(cols):
        name, rest = cols[0], cols[1:]
        cells = [name[:widths[0]].ljust(widths[0])]
        cells += [str(c).rjust(w) for c, w in zip(rest, widths[1:])]
        return "  ".join(cells)

    lines = [sep, fmt_row(header), sep]
    key = _sort_key(sorted_by)

    def emit(nodes, depth):
        for n in sorted(nodes, key=key, reverse=True):
            lines.append(fmt_row((
                "  " * depth + n["name"], n["count"],
                f"{n['total_s'] * scale:.3f}",
                f"{n['avg_s'] * scale:.3f}",
                f"{n['max_s'] * scale:.3f}",
                f"{n['total_s'] / grand * 100:.1f}%")))
            emit(n["children"], depth + 1)

    # thread_sep: the recorder aggregates threads in place (a node keeps
    # the set of thread idents that hit it); exact per-thread splits
    # would need raw event retention, so the merged view is rendered
    # either way and `snapshot()` carries the thread sets.
    emit(tree, 0)
    lines.append(sep)
    return "\n".join(lines)
