"""Reduction of a profiler trace (.xplane.pb) to the numbers the
per-layer readers use: busy and idle time of the fullest device, device
time per kernel by name pattern, collective time that no compute hides,
the device ops that took most time, and the longest idle gaps with what
the host was doing in them.

Read with jax.profiler.ProfileData alone. A trace of this runtime
holds one plane per chip ("/device:TPU:<n>") whose "XLA Ops" line has
one event per executed HLO op or custom call, with start and duration in
nanoseconds, and one "/host:CPU" plane with a line per host thread on
which jax.profiler.TraceAnnotation spans appear under their own names.
benchmarks/tests/test_xplane.py pins all of this on a recorded trace."""
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
HOST_SPAN = re.compile(r"^bench\.")
# control-flow ops span the ops inside them, which the line lists too
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(event_name):
    """An "XLA Ops" event is named by the op's whole HLO line
    ("%ssm_scan.73 = (f32[...]) custom-call(...)"): the op's own name is
    what precedes " = ", so that a consumer of %ssm_scan.73 does not
    count as the kernel."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union_ns(intervals):
    """Total length of the union of (start, end) intervals, and the
    merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def overlap_ns(a, merged_b):
    """Length of interval a=(s, e) covered by the merged intervals."""
    s, e = a
    return sum(max(0, min(e, y) - max(s, x)) for x, y in merged_b
               if y > s and x < e)


class Trace:
    """devices: {plane name: [(name, start_ns, end_ns)]} of device ops;
    modules: the same for whole-program executions; host: [(name,
    start_ns, end_ns)] of the benchmark's own spans."""

    def __init__(self, devices, modules, host, window_s):
        self.devices = devices
        self.modules = modules
        self.host = host
        self.window_s = float(window_s)
        if not devices or not any(devices.values()):
            raise RuntimeError("the trace holds no device operation")
        busy = {d: union_ns([(s, e) for _, s, e in ev])
                for d, ev in devices.items()}
        # the fullest device is the one whose idle share bounds the run
        self.fullest = max(busy, key=lambda d: busy[d][0])
        self.busy_merged = busy[self.fullest][1]
        self.busy_s = sum(b[0] for b in busy.values()) / len(busy) / 1e9
        self.busy_fullest_s = busy[self.fullest][0] / 1e9

    @classmethod
    def from_file(cls, path, window_s):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, modules, host = {}, {}, []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        devices[plane.name] = [
                            (op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
                    elif line.name == MODULES_LINE:
                        modules[plane.name] = [
                            (e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if HOST_SPAN.match(e.name)]
        return cls(devices, modules, host, window_s)

    # ---- what the readers ask ----------------------------------------
    def idle_share(self):
        return 1.0 - self.busy_fullest_s / self.window_s

    def kernel_seconds(self, pattern):
        """(seconds, calls) of the fullest device's ops whose name
        matches `pattern`."""
        rx = re.compile(pattern)
        ev = [(s, e) for n, s, e in self.devices[self.fullest]
              if rx.search(n)]
        return sum(e - s for s, e in ev) / 1e9, len(ev)

    def module_runs(self, pattern):
        rx = re.compile(pattern)
        return sum(1 for n, _, _ in self.modules.get(self.fullest, ())
                   if rx.search(n))

    def exposed_collective_seconds(self):
        """Time of the fullest device's collective ops during which no
        other op runs on that device."""
        ev = self.devices[self.fullest]
        coll = [(s, e) for n, s, e in ev if COLLECTIVE.search(n)]
        _, compute = union_ns([(s, e) for n, s, e in ev
                               if not COLLECTIVE.search(n)])
        total, merged = union_ns(coll)
        hidden = sum(overlap_ns(iv, compute) for iv in merged)
        return (total - hidden) / 1e9

    def top_ops(self, n=10):
        acc = {}
        for name, s, e in self.devices[self.fullest]:
            if not CONTAINER.match(name):
                acc[name] = acc.get(name, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n=10, longest=512):
        """The `longest` gaps between device ops of the fullest device,
        summed by the benchmark's host span that covers most of each
        gap ("(no span)" where none does). Host and device clocks agree
        to a millisecond or two, so short gaps are not attributed."""
        import numpy as np
        m = self.busy_merged
        gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                       in zip(m, m[1:])), reverse=True)[:longest]
        hs = np.array([h[1] for h in self.host], np.float64)
        he = np.array([h[2] for h in self.host], np.float64)
        acc = {}
        for length, e0, s1 in gaps:
            name = "(no span)"
            if len(hs):
                cover = np.minimum(he, s1) - np.maximum(hs, e0)
                i = int(cover.argmax())
                if cover[i] > 0:
                    name = self.host[i][0]
            acc[name] = acc.get(name, 0) + length
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
