"""Always-on flight recorder: bounded rings of recent telemetry + a
crash/hang debug-bundle dump.

Parity inspiration: the reference framework's `nan_inf_utils` debug hooks
and the operational reality of PAPER.md's north star — at production
scale the questions that matter are *what was the process doing on a
timeline when it got slow* and *what state was it in when it crashed or
hung*. The span store (`statistic.py`) and metrics registry
(`monitor.py`) aggregate; this module additionally keeps the RAW tail:

- **spans** — every closed host span (name, start and duration in
  `time.perf_counter_ns()` nanoseconds, thread, nesting depth, and the
  args of the few spans that carry them: `host.gc`'s generation), the
  events `trace_export.py` renders into a Perfetto timeline;
- **samples** — every counter/gauge/histogram update (the counter
  tracks of the timeline: queue depth, prefetch depth, host.blocked_s);
- **records** — the per-step / per-batch JSONL records
  (`monitor.export_step`), kept even when no metrics file is configured;
- **events** — structured anomalies (`kind:"event"`: NaN detections,
  loss spikes, watchdog expiries, scheduler crashes).

All rings are `collections.deque(maxlen=...)`: appends are O(1),
lock-free (CPython deque appends are atomic), and steady-state cost is
negligible — the recorder is ON by default.

Debug bundles: with `PADDLE_TPU_DEBUG_DUMP=<dir>` set, `auto_install()`
(called at `import paddle_tpu`) arms three dump triggers —

- **uncaught exception** (`sys.excepthook` + `threading.excepthook`,
  chained to the previous hooks),
- **watchdog expiry** (`PADDLE_TPU_WATCHDOG_S=<n>`: no train-step
  heartbeat for n seconds → all-thread stack dump + bundle, process
  keeps running),
- **SIGQUIT** (dump and keep running — the hang-diagnosis signal) and
  **SIGTERM** (dump, then the previous/default handling proceeds).

Each trigger writes `<dir>/<reason>/` containing `MANIFEST.json`,
`ring.json` (the rings above), `metrics_tail.jsonl` (tail of
`PADDLE_TPU_METRICS_FILE`), `hlo/<tag>.txt` + `<tag>.cost.json` (HLO and
XLA cost analysis of every registered AOT executable — `jit/api.py`
registers each train-step/serving compile), `requests_tail.jsonl` +
`serve_state.json` (the serving observatory's recent terminal request
records and every live engine's load_report/pool_stats —
`serve_observatory.py`), one `<name>.json` per registered state
provider (e.g. `ckpt_state.json` — the checkpoint manager's
committed/in-flight view, `distributed/checkpoint.py`), `env.json`
(argv/versions/PADDLE*/JAX* env),
and `stacks.txt` (faulthandler all-thread stacks). Writing never
raises: a dump is diagnostics, not a second crash. See
docs/OBSERVABILITY.md "The flight recorder".

`paddle_tpu.distributed.launch` propagates `PADDLE_TPU_DEBUG_DUMP` with
a per-rank subdirectory and sets `PADDLE_TPU_SIGQUIT_STACKS=1` so a
multi-process hang is debuggable rank by rank (`kill -QUIT <pid>`).
"""
import collections
import faulthandler
import json
import os
import signal
import sys
import threading
import time
import traceback
import weakref

__all__ = ["record_span_event", "span_events", "record_sample",
           "record_record", "record_event", "register_executable",
           "register_state_provider", "heartbeat",
           "snapshot", "reset", "dump", "install", "auto_install",
           "Watchdog", "perf_to_wall"]

# ring sizes: enough tail to reconstruct the last ~minutes of a step
# loop, small enough that a full snapshot serializes in milliseconds
SPAN_RING = 4096
SAMPLE_RING = 4096
RECORD_RING = 1024
EVENT_RING = 256
EXEC_REGISTRY = 8
_HLO_CAP = 4 << 20  # bytes of HLO text kept per executable in a bundle

# wall-clock anchor for the perf_counter timestamps spans carry:
# wall = perf + _PERF_TO_WALL (one process-wide offset; good enough to
# merge per-rank traces recorded on the same host)
_PERF_TO_WALL = time.time() - time.perf_counter()

_spans = collections.deque(maxlen=SPAN_RING)
_samples = collections.deque(maxlen=SAMPLE_RING)
_records = collections.deque(maxlen=RECORD_RING)
_events = collections.deque(maxlen=EVENT_RING)
_execs = collections.OrderedDict()  # tag -> weakref-or-strong compiled
_exec_lock = threading.Lock()

_beat = {"ts": None, "step": None, "count": 0}
_installed = {"hooks": False}
_watchdog = [None]
# name -> list of weakref-wrapped zero-arg callables returning a
# JSON-serializable payload; a debug bundle writes each name as
# <name>.json from the NEWEST LIVE provider (e.g. the checkpoint
# manager's ckpt_state.json — distributed/checkpoint.py registers it).
# Weak references: registration must not keep a dead manager (a
# bench/gate throwaway) alive, and once it's collected the previously
# registered live one shows through again.
_state_providers = {}


def perf_to_wall(t_perf):
    """Map a time.perf_counter() stamp onto unix seconds."""
    return t_perf + _PERF_TO_WALL


def record_span_event(name, t0_ns, dur_ns, thread_ident, depth=0,
                      args=None):
    """One CLOSED span (called by statistic.py when a span ends or a
    garbage collection stops). t0_ns and dur_ns are on the
    time.perf_counter_ns() clock."""
    _spans.append((name, t0_ns, dur_ns, thread_ident, depth, args))


def span_events():
    """The span ring, in the order the spans closed: (name, t0_ns,
    dur_ns, thread_ident, depth, args) of each closed span it still
    holds."""
    return list(_spans)


def record_sample(name, kind, value):
    """One metric update (counter running total / gauge value /
    histogram observation) — a point on that metric's counter track."""
    try:
        _samples.append((time.time(), name, kind, float(value)))
    except (TypeError, ValueError):
        pass


def record_record(rec):
    """One exported JSONL record (step/scan/serve/health) — kept in the
    ring whether or not PADDLE_TPU_METRICS_FILE is set."""
    _records.append(rec)


def record_event(event, **fields):
    """One structured anomaly/lifecycle event. Lands in the events ring
    AND (when configured) the metrics JSONL as a `kind:"event"` record.
    Returns the record. Never raises."""
    rec = {"ts": time.time(), "event": str(event)}
    rec.update(fields)
    _events.append(rec)
    try:
        from . import monitor as _monitor
        _monitor.counter("flight.events").inc()
        _monitor.export_step({k: v for k, v in rec.items() if k != "ts"},
                             kind="event", _ring=False)
    except Exception:
        pass
    return rec


def register_executable(tag, compiled):
    """Remember a compiled XLA executable so a debug bundle can dump its
    HLO + cost analysis. Bounded (oldest evicted); holds a weakref when
    the object supports it so the registry never extends a dead train
    step's device memory."""
    try:
        ref = weakref.ref(compiled)
    except TypeError:
        ref = compiled  # strong fallback: owners cache these anyway
    with _exec_lock:
        _execs.pop(tag, None)
        _execs[tag] = ref
        while len(_execs) > EXEC_REGISTRY:
            _execs.popitem(last=False)


def register_state_provider(name, fn):
    """Register a zero-arg callable whose JSON-serializable return
    value a debug bundle writes as `<name>.json` (e.g. "ckpt_state" →
    the checkpoint manager's committed/queued/last-error view). Held
    via weakref (a bound method pins neither its owner nor the
    registry); per name the newest LIVE registration wins, and dead
    ones are pruned at dump time. Providers must never raise for the
    bundle to matter, but dump() guards them anyway."""
    try:
        ref = weakref.WeakMethod(fn)
    except TypeError:  # plain function/lambda: module-lived, hold it
        ref = (lambda f=fn: f)
    lst = _state_providers.setdefault(str(name), [])
    lst.append(ref)
    del lst[:-8]  # bounded per name


def _resolve_state_providers():
    """{name: newest live provider}, pruning dead weakrefs."""
    out = {}
    for name, lst in list(_state_providers.items()):
        lst[:] = [r for r in lst if r() is not None]
        if lst:
            out[name] = lst[-1]()
        else:
            _state_providers.pop(name, None)
    return out


def _live_executables():
    out = []
    with _exec_lock:
        items = list(_execs.items())
    for tag, ref in items:
        obj = ref() if isinstance(ref, weakref.ref) else ref
        if obj is not None:
            out.append((tag, obj))
    return out


def heartbeat(step=None):
    """Train-step liveness pulse (called once per dispatched step — a
    monotonic read and two stores; the watchdog measures hang time as
    the age of the last pulse)."""
    _beat["ts"] = time.monotonic()
    if step is not None:
        _beat["step"] = step
    _beat["count"] += 1


def snapshot():
    """The rings as plain JSON-serializable dicts (spans carry wall ts)."""
    spans = [{"name": n, "ts": perf_to_wall(t0 * 1e-9), "dur_s": d * 1e-9,
              "tid": tid, "depth": depth}
             for (n, t0, d, tid, depth, _) in span_events()]
    samples = [{"ts": ts, "name": n, "kind": k, "value": v}
               for (ts, n, k, v) in list(_samples)]
    return {"spans": spans, "samples": samples,
            "records": list(_records), "events": list(_events),
            "heartbeat": dict(_beat),
            "executables": [tag for tag, _ in _live_executables()]}


def reset():
    """Drop ring contents (tests); handlers/registry stay installed."""
    _spans.clear()
    _samples.clear()
    _records.clear()
    _events.clear()
    _beat.update({"ts": None, "step": None, "count": 0})


# -- debug bundle --------------------------------------------------------

def _dump_dir():
    return os.environ.get("PADDLE_TPU_DEBUG_DUMP") or None


def _write_json(path, payload):
    try:
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        return True
    except Exception:
        return False


def dump(reason="manual", exc=None, base_dir=None):
    """Write a debug bundle into `<base>/<reason>/`; returns the bundle
    path or None when no dump dir is configured. NEVER raises — a dump
    runs inside excepthooks and signal handlers."""
    try:
        base = base_dir or _dump_dir()
        if not base:
            return None
        d = os.path.join(base, str(reason))
        os.makedirs(os.path.join(d, "hlo"), exist_ok=True)

        try:
            from . import monitor as _monitor
            rank = _monitor.rank()
            mfile = _monitor.metrics_file()
        except Exception:
            rank, mfile = 0, None

        manifest = {"schema": "paddle_tpu.debug_bundle.v1",
                    "reason": str(reason),
                    "ts": time.time(),
                    "recorded_utc": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                    "rank": rank, "pid": os.getpid(),
                    "heartbeat": dict(_beat)}
        if exc is not None:
            manifest["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc)[:2000],
                "traceback": "".join(traceback.format_exception(
                    type(exc), exc, exc.__traceback__))[-8000:]}

        # ring tail first — it is the part no other artifact carries
        _write_json(os.path.join(d, "ring.json"), snapshot())

        # all-thread stacks (faulthandler: signal-safe C-level dump)
        try:
            with open(os.path.join(d, "stacks.txt"), "w") as f:
                faulthandler.dump_traceback(file=f, all_threads=True)
        except Exception:
            pass

        # metrics JSONL tail
        if mfile:
            try:
                with open(mfile, errors="replace") as f:
                    tail = f.readlines()[-200:]
                with open(os.path.join(d, "metrics_tail.jsonl"), "w") as f:
                    f.writelines(tail)
            except Exception:
                pass

        # HLO + cost analysis of every registered AOT executable
        hlo_tags = []
        for tag, compiled in _live_executables():
            safe = "".join(c if c.isalnum() or c in "._-" else "_"
                           for c in tag)[:120]
            try:
                text = compiled.as_text()[:_HLO_CAP]
                with open(os.path.join(d, "hlo", safe + ".txt"), "w") as f:
                    f.write(text)
                hlo_tags.append(tag)
            except Exception:
                continue
            try:
                from . import cost as _cost
                _write_json(os.path.join(d, "hlo", safe + ".cost.json"),
                            _cost.cost_analysis(compiled))
            except Exception:
                pass
        manifest["hlo"] = hlo_tags

        # the compilation ledger: every compile this process ran, with
        # per-tag rollups — WHERE the compile seconds went, which
        # executables were cache hits, and the fusion/bytes-accessed
        # numbers the ratchet gates compare (compile_observatory.py)
        try:
            from . import compile_observatory as _obs
            recs = _obs.ledger()
            if recs:
                _write_json(os.path.join(d, "compile_ledger.json"),
                            {"records": recs,
                             "by_tag": _obs.aggregate(recs)})
                manifest["compile_records"] = len(recs)
        except Exception:
            pass

        # the serving observatory: recent terminal request records +
        # per-engine admission/pool state — a hung serving loop names
        # the requests in flight (docs/SERVING.md)
        try:
            from . import serve_observatory as _serve
            tail = _serve.requests_tail()
            if tail:
                with open(os.path.join(d, "requests_tail.jsonl"),
                          "w") as f:
                    for rec in tail:
                        f.write(json.dumps(rec, default=str) + "\n")
                manifest["request_records"] = len(tail)
            payload = _serve.debug_payload()
            if payload.get("engines") or tail:
                _write_json(os.path.join(d, "serve_state.json"), payload)
        except Exception:
            pass

        # the memory observatory: the full tag ledger, attribution
        # split, per-pool pool_stats, per-executable memory_analysis
        # peaks, and — after an OOM routed through oom_error — the
        # parsed request context. Written unconditionally when anything
        # is registered: an OOM post-mortem's first question is WHO
        # held the bytes (docs/OBSERVABILITY.md)
        try:
            from . import mem_observatory as _mem
            if _mem.registered_tags() or _mem.records_tail():
                _write_json(os.path.join(d, "mem_state.json"),
                            _mem.mem_state())
                manifest["mem_state"] = True
        except Exception:
            pass

        # registered state providers (ckpt_state.json, ...): subsystem
        # snapshots a post-mortem needs that no ring carries — e.g.
        # which checkpoints are committed vs in-flight when a wedged
        # step gets SIGTERMed (distributed/elastic.py watchdog)
        provided = []
        for name, fn in _resolve_state_providers().items():
            safe = "".join(c if c.isalnum() or c in "._-" else "_"
                           for c in name)[:80]
            try:
                if _write_json(os.path.join(d, safe + ".json"), fn()):
                    provided.append(name)
            except Exception:
                continue
        if provided:
            manifest["state_providers"] = provided

        # env / versions / argv
        envkeys = ("PADDLE", "JAX", "XLA", "TPU", "FLAGS_")
        env = {k: v for k, v in os.environ.items()
               if any(k.startswith(p) for p in envkeys)}
        versions = {"python": sys.version}
        for mod in ("jax", "jaxlib", "numpy"):
            m = sys.modules.get(mod)
            if m is not None:
                versions[mod] = getattr(m, "__version__", "?")
        pt = sys.modules.get("paddle_tpu")
        if pt is not None:
            versions["paddle_tpu"] = getattr(pt, "__version__", "?")
        _write_json(os.path.join(d, "env.json"),
                    {"argv": list(sys.argv), "cwd": os.getcwd(),
                     "env": env, "versions": versions, "rank": rank})

        _write_json(os.path.join(d, "MANIFEST.json"), manifest)
        record_event("debug_dump", reason=str(reason), path=d)
        return d
    except Exception:
        return None


# -- triggers ------------------------------------------------------------

class Watchdog:
    """Background hang detector: when no train-step heartbeat lands for
    `timeout_s`, write ONE debug bundle (reason "watchdog", all-thread
    stacks included) and keep the process running — the dump is the
    diagnosis, killing is the supervisor's call. The countdown starts at
    `start()` (so a hang *before* the first step — e.g. a wedged compile
    or backend init — still dumps) and resets on every heartbeat."""

    def __init__(self, timeout_s, base_dir=None):
        self.timeout_s = float(timeout_s)
        self.base_dir = base_dir
        self.fired = False
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        heartbeat()  # arm: countdown measured from now
        self._thread = threading.Thread(target=self._loop,
                                        name="flight-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()

    def _loop(self):
        poll = max(0.05, min(1.0, self.timeout_s / 4.0))
        while not self._stop.wait(poll):
            last = _beat["ts"]
            if last is None:
                continue
            age = time.monotonic() - last
            if age >= self.timeout_s:
                record_event("watchdog_expired", hang_s=round(age, 3),
                             step=_beat["step"], timeout_s=self.timeout_s)
                dump("watchdog", base_dir=self.base_dir)
                self.fired = True  # after the dump: fired == bundle done
                return  # one-shot: no dump storms


def _chain_excepthook():
    prev = sys.excepthook

    def hook(etype, value, tb):
        if not issubclass(etype, (KeyboardInterrupt, SystemExit)):
            record_event("uncaught_exception", type=etype.__name__,
                         message=str(value)[:400])
            dump("exception", exc=value)
        prev(etype, value, tb)

    sys.excepthook = hook

    t_prev = getattr(threading, "excepthook", None)
    if t_prev is not None:
        def t_hook(args):
            if args.exc_type is not SystemExit:
                record_event("uncaught_thread_exception",
                             type=args.exc_type.__name__,
                             message=str(args.exc_value)[:400],
                             thread=getattr(args.thread, "name", "?"))
                dump("exception", exc=args.exc_value)
            t_prev(args)
        threading.excepthook = t_hook


def _install_signal_dumps():
    """SIGQUIT: dump and keep running (hang diagnosis). SIGTERM: dump,
    then hand the signal to whatever handling was there before (default
    = die), preserving launch/driver kill semantics."""
    try:
        def on_quit(signum, frame):
            record_event("sigquit")
            dump("sigquit")
        signal.signal(signal.SIGQUIT, on_quit)
    except (ValueError, OSError, AttributeError):
        pass  # non-main thread or platform without SIGQUIT

    try:
        prev_term = signal.getsignal(signal.SIGTERM)

        def on_term(signum, frame):
            record_event("sigterm")
            dump("sigterm")
            if prev_term is signal.SIG_IGN:
                return  # the process deliberately ignores SIGTERM:
                        # dump, but do NOT turn ignored into fatal
            if callable(prev_term):
                prev_term(signum, frame)
            else:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signum)
        signal.signal(signal.SIGTERM, on_term)
    except (ValueError, OSError):
        pass


def install(base_dir=None, watchdog_s=None):
    """Arm the dump triggers (idempotent for the hook set). `base_dir`
    overrides PADDLE_TPU_DEBUG_DUMP; `watchdog_s` starts a Watchdog."""
    if base_dir:
        os.environ["PADDLE_TPU_DEBUG_DUMP"] = base_dir
    if not _installed["hooks"]:
        _installed["hooks"] = True
        _chain_excepthook()
        _install_signal_dumps()
    if watchdog_s and _watchdog[0] is None:
        _watchdog[0] = Watchdog(watchdog_s).start()
    return _watchdog[0]


def auto_install():
    """Called at `import paddle_tpu`: arm dumps when the operator asked
    for them via env — otherwise install NOTHING (no signal handlers, no
    threads; the rings alone are always on and cost nothing to arm)."""
    if _dump_dir():
        wd = os.environ.get("PADDLE_TPU_WATCHDOG_S")
        try:
            wd_s = float(wd) if wd else None
        except ValueError:
            wd_s = None
        install(watchdog_s=wd_s)
    elif os.environ.get("PADDLE_TPU_SIGQUIT_STACKS"):
        # launch.py workers: `kill -QUIT <pid>` dumps all-thread stacks
        # to stderr (the per-rank workerlog) without dying
        try:
            faulthandler.register(signal.SIGQUIT, all_threads=True,
                                  chain=True)
        except (ValueError, OSError, AttributeError):
            pass
