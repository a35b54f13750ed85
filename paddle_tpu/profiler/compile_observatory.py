"""The compilation observatory: a per-executable compile/HLO ledger and
retrace forensics, fed from the ONE choke point every AOT compile flows
through (`jit/api.aot_compile` — the TrainStep / HybridTrainStep /
run_steps / accumulate / serving-bucket dispatch paths all use it).

Why this exists: the repo's standing failure mode is the compile-time
wall (ROADMAP open item 3 — five bench rounds dead at "stage: compile"
with no evidence of *which* executable ate the budget or *why* a step
retraced). Aggregated counters (`jit.retraces`, `jit.compile_s`) say how
much; this module keeps the per-executable WHAT:

- **one `kind:"compile"` record per (tag, signature)** — lower_s /
  compile_s split, persistent-cache hit vs cold compile, the abstract
  argument signature, and HLO-derived stats from the compiled
  executable itself: instruction counts by op kind, fusion count, bytes
  accessed + FLOPs (`cost_analysis()`, per *Operator Fusion in XLA*,
  arxiv 2301.13062 — XLA's own analysis is the fusion-accounting source
  of truth), and a peak-memory estimate (`memory_analysis()`). Records
  land in the flight-recorder ring (always) and the metrics JSONL
  (when `PADDLE_TPU_METRICS_FILE` is set; schema enforced by
  tools/check_metrics_schema.py).

- **retrace forensics** — when a tag that already compiled sees a NEW
  abstract signature, the observatory diffs it against the cached
  signatures *before* the expensive recompile starts and emits a
  structured `kind:"event"` (`event: "retrace"`) naming exactly which
  argument changed and how (shape / dtype / sharding / static value),
  so a retrace storm is a one-line diagnosis instead of archaeology.

- **the ratchet feedstock** — `tools/check_compile_budget.py` and
  `tools/check_fusion.py` compare ledger records against the checked-in
  `BASELINE_HLO.json` and fail CI on compile-seconds / fusion-count /
  bytes-accessed regressions (the *Neptune*-style locality/fusion cost
  framing, arxiv 2510.08726).

Listeners (`add_listener`) observe compile start/done live.

See docs/OBSERVABILITY.md "The compilation observatory".
"""
import collections
import hashlib
import re
import threading

__all__ = ["abstract_signature", "signature_key", "signature_str",
           "diff_signatures", "compile_started", "record_compile",
           "hlo_stats", "peak_memory_bytes", "ledger", "ledger_by_tag",
           "ledger_signatures", "aggregate", "add_listener",
           "remove_listener", "reset", "LEDGER_RING"]

LEDGER_RING = 256   # compile records kept in process (a debug bundle
                    # carries them all; steady jobs compile a handful)
TAG_SIGS = 32       # distinct signatures remembered per tag
MAX_TAGS = 64       # tags tracked for forensics
MAX_CHANGES = 8     # changes named per retrace event

_lock = threading.RLock()
_ledger = collections.deque(maxlen=LEDGER_RING)
_tag_sigs = collections.OrderedDict()   # tag -> OrderedDict(key -> sig)
_listeners = []


# -- abstract signatures -------------------------------------------------

def _leaf_desc(path, leaf):
    """One leaf of an argument as a hashable descriptor. Arrays (and
    ShapeDtypeStructs) keep shape/dtype/sharding — the things a retrace
    can hinge on; Python scalars keep only their type, mirroring jax's
    weak-typed aval semantics (a new VALUE of a traced Python int does
    NOT retrace, so it must not change the signature either)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        sh = getattr(leaf, "sharding", None)
        return (path, "array", tuple(int(d) for d in shape), str(dtype),
                str(sh) if sh is not None else None)
    return (path, "py", type(leaf).__name__)


def abstract_signature(args, static=None):
    """The (args_part, static_part) signature of one compile: per
    positional argument a tuple of leaf descriptors (pytrees flattened
    with paths), plus the caller-declared STATIC values that are baked
    into the traced program rather than passed as arrays (e.g.
    run_steps' segment length `n` — invisible in `args`, decisive for
    the executable)."""
    import jax
    arg_descs = []
    for a in args:
        flat, _ = jax.tree_util.tree_flatten_with_path(a)
        arg_descs.append(tuple(
            _leaf_desc(jax.tree_util.keystr(kp), leaf)
            for kp, leaf in flat))
    static_part = tuple(sorted(
        (str(k), repr(v)) for k, v in (static or {}).items()))
    return (tuple(arg_descs), static_part)


def signature_key(sig):
    """Stable short id of a signature (the `signature` field of the
    compile record — grep it across JSONL / traces / bundles)."""
    return hashlib.sha1(repr(sig).encode()).hexdigest()[:12]


def _arg_name(arg_names, i):
    if arg_names and i < len(arg_names):
        return str(arg_names[i])
    return f"arg{i}"


def signature_str(sig, arg_names=None, limit=400):
    """Compact human rendering: single-array args as `name=dtype[shape]`,
    pytrees as leaf counts, static values verbatim."""
    args_part, static_part = sig
    parts = []
    for i, leaves in enumerate(args_part):
        name = _arg_name(arg_names, i)
        if len(leaves) == 1 and not leaves[0][0]:
            d = leaves[0]
            if d[1] == "array":
                parts.append(f"{name}={d[3]}{list(d[2])}")
            else:
                parts.append(f"{name}:{d[2]}")
        else:
            parts.append(f"{name}={{{len(leaves)} leaves}}")
    for k, v in static_part:
        parts.append(f"{k}={v}")
    out = ", ".join(parts)
    return out if len(out) <= limit else out[:limit - 3] + "..."


def _render_leaf(d):
    if d is None:
        return "<absent>"
    if d[1] == "array":
        return f"{d[3]}{list(d[2])}"
    return d[2]


def diff_signatures(old, new, arg_names=None):
    """What changed between two signatures of one tag: a list of
    {"arg", "change", "from", "to"} dicts, `change` one of
    static / shape / dtype / sharding / structure / type / arity.
    Empty list = identical signatures."""
    changes = []
    old_args, old_static = old
    new_args, new_static = new
    os_, ns_ = dict(old_static), dict(new_static)
    for k in sorted(set(os_) | set(ns_)):
        if os_.get(k) != ns_.get(k):
            changes.append({"arg": k, "change": "static",
                            "from": os_.get(k, "<absent>"),
                            "to": ns_.get(k, "<absent>")})
    for i in range(max(len(old_args), len(new_args))):
        name = _arg_name(arg_names, i)
        if i >= len(old_args) or i >= len(new_args):
            changes.append({
                "arg": name, "change": "arity",
                "from": "<absent>" if i >= len(old_args) else "present",
                "to": "<absent>" if i >= len(new_args) else "present"})
            continue
        ol = {d[0]: d for d in old_args[i]}
        nl = {d[0]: d for d in new_args[i]}
        for path in sorted(set(ol) | set(nl)):
            o, n = ol.get(path), nl.get(path)
            label = f"{name}{path}" if path else name
            if o == n:
                continue
            if o is None or n is None:
                changes.append({"arg": label, "change": "structure",
                                "from": _render_leaf(o),
                                "to": _render_leaf(n)})
            elif o[1] != n[1]:
                changes.append({"arg": label, "change": "type",
                                "from": _render_leaf(o),
                                "to": _render_leaf(n)})
            elif o[1] == "py":
                changes.append({"arg": label, "change": "type",
                                "from": o[2], "to": n[2]})
            else:
                if o[2] != n[2]:
                    changes.append({"arg": label, "change": "shape",
                                    "from": str(list(o[2])),
                                    "to": str(list(n[2]))})
                if o[3] != n[3]:
                    changes.append({"arg": label, "change": "dtype",
                                    "from": o[3], "to": n[3]})
                if o[4] != n[4]:
                    changes.append({"arg": label, "change": "sharding",
                                    "from": str(o[4]), "to": str(n[4])})
    return changes


# -- HLO-derived stats ---------------------------------------------------

# an HLO instruction line is `%name = <type> <opcode>(...)`; opcodes are
# lowercase (add, fusion, all-reduce, custom-call...), which is what
# keeps TPU layout/tiling annotations like `{1,0:T(8,128)}` from
# miscounting as ops. Anchored to line start (MULTILINE) so finditer
# counts at most one opcode per line in a single C-level pass — the
# first `... = <type> opcode(` per line, same as a per-line search.
_OPCODE_RE = re.compile(r"^[^\n]*? = [^\n]*?([a-z][a-z0-9_-]*)\(",
                        re.MULTILINE)


# a Pallas kernel in the compiled text: the custom call's op_name ends
# ".../<innermost named scope>/<kernel name>/pallas_call"
_KERNEL_RE = re.compile(
    r'tpu_custom_call[^\n]*?op_name="[^"\n]*?([^/"\n]+/[^/"\n]+)/pallas_call')


def hlo_stats(compiled):
    """Instruction counts by op kind + fusion count from the compiled
    executable's optimized HLO text, and its Pallas kernels by
    "<innermost scope>/<kernel name>" (so "flash.direct/
    flash_attention_fwd" says which layout the flash kernels got).
    {} -shaped zeros when the backend exposes no text — stats must
    never fail a compile."""
    try:
        text = compiled.as_text()
    except Exception:
        return {"instructions": 0, "fusion_count": 0, "op_counts": {},
                "kernels": {}}
    kernels = {}
    for m in _KERNEL_RE.finditer(text):
        kernels[m.group(1)] = kernels.get(m.group(1), 0) + 1
    counts = {}
    for m in _OPCODE_RE.finditer(text):
        op = m.group(1)
        counts[op] = counts.get(op, 0) + 1
    top = dict(sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:32])
    return {"instructions": sum(counts.values()),
            "fusion_count": counts.get("fusion", 0),
            "op_counts": top, "kernels": kernels}


def peak_memory_bytes(compiled):
    """Compile-time peak-memory estimate: arguments + outputs + temps
    minus aliased (donated) bytes, from the executable's own memory
    analysis. 0.0 when the backend exposes none."""
    try:
        ma = compiled.memory_analysis()
        total = 0.0
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
            total += float(getattr(ma, k, 0) or 0)
        total -= float(getattr(ma, "alias_size_in_bytes", 0) or 0)
        return max(total, 0.0)
    except Exception:
        return 0.0


# -- the ledger ----------------------------------------------------------

def compile_started(tag, sig, arg_names=None):
    """Register a compile ABOUT to run (called before lowering, so the
    forensics land even when the compile itself then hangs past a
    timeout). When `tag` has compiled before under a different
    signature, diff against the closest cached one and emit the
    structured retrace event. Returns (signature key, changes)."""
    key = signature_key(sig)
    with _lock:
        sigs = _tag_sigs.get(tag)
        first = sigs is None
        if first:
            sigs = _tag_sigs[tag] = collections.OrderedDict()
            while len(_tag_sigs) > MAX_TAGS:
                _tag_sigs.popitem(last=False)
        known = key in sigs
        cached = [] if known else list(sigs.values())
        if not known:
            sigs[key] = sig
            while len(sigs) > TAG_SIGS:
                sigs.popitem(last=False)
    retrace = bool(cached)  # a NEW signature for an already-seen tag
    changes = []
    if retrace:
        # closest cached signature = fewest differences: the event
        # names the MINIMAL change that forced this recompile
        best = None
        for old in cached:
            d = diff_signatures(old, sig, arg_names=arg_names)
            if best is None or len(d) < len(best):
                best = d
        changes = (best or [])[:MAX_CHANGES]
        summary = "; ".join(
            f"{c['arg']}: {c['change']} {c['from']} -> {c['to']}"
            for c in changes) or "signature changed"
        try:
            from . import flight_recorder as _flight
            from . import monitor as _monitor
            _flight.record_event(
                "retrace", tag=str(tag), signature=key,
                n_signatures=len(cached) + 1, changes=changes,
                summary=summary[:400])
            _monitor.counter("jit.retrace_events").inc()
        except Exception:
            pass
    _notify({"phase": "start", "tag": str(tag), "signature": key,
             "retrace": retrace, "changes": changes})
    return key, changes


def record_compile(tag, sig, sig_key, lower_s, compile_s, cache_hit,
                   compiled, cost=None, arg_names=None,
                   cache_entries_added=0):
    """One finished compile -> one ledger entry + one `kind:"compile"`
    record (flight-recorder ring always; metrics JSONL when configured).
    Returns the record. Never raises — the ledger is telemetry."""
    try:
        stats = hlo_stats(compiled)
        cost = cost or {}
        rec = {
            "tag": str(tag),
            "signature": sig_key,
            "args": signature_str(sig, arg_names=arg_names),
            "lower_s": round(max(float(lower_s), 0.0), 6),
            "compile_s": round(max(float(compile_s), 0.0), 6),
            "cache_hit": bool(cache_hit),
            "instructions": int(stats["instructions"]),
            "fusion_count": int(stats["fusion_count"]),
            "op_counts": stats["op_counts"],
            "kernels": stats["kernels"],
            # cost_analysis can answer -1 for "unknown"; the schema (and
            # the ratchet math) want "unknown" as 0
            "flops": max(float(cost.get("flops", 0.0)), 0.0),
            "bytes_accessed": max(
                float(cost.get("bytes accessed", 0.0)), 0.0),
            "peak_memory_bytes": peak_memory_bytes(compiled),
            "cache_entries_added": int(cache_entries_added),
        }
        with _lock:
            _ledger.append(dict(rec))
        from . import monitor as _monitor
        _monitor.export_step(rec, kind="compile")
        _notify({"phase": "done", "tag": str(tag), "record": rec})
        return rec
    except Exception:
        return None


def ledger():
    """All compile records this process holds (ring-bounded), oldest
    first — the table a debug bundle renders."""
    with _lock:
        return [dict(r) for r in _ledger]


def ledger_by_tag():
    """{tag: [records]} view of the ledger."""
    out = {}
    for r in ledger():
        out.setdefault(r["tag"], []).append(r)
    return out


def ledger_signatures():
    """The set of (tag, signature-key) pairs compiled so far — the
    executable-sharing warmup contract's comparand: snapshot after
    `warm()`/`jit.warm.join`, snapshot again after steady-state traffic,
    and an EQUAL set proves warming added zero executables beyond the
    steady-state set (tests/test_warm_pipeline.py asserts exactly
    this; tools/_gate_common.py enforces it on the canonical
    workload)."""
    with _lock:
        return {(r["tag"], r["signature"]) for r in _ledger}


def aggregate(records=None):
    """Per-tag rollup of compile records (`ledger()` when None):
    lower_s/compile_s sums across the tag's signatures, cache_hit only
    when EVERY compile hit, max fusion/bytes/instructions (the gate
    comparands — with one signature per tag, max == the value)."""
    out = {}
    for r in (ledger() if records is None else records):
        if r.get("kind", "compile") != "compile":
            continue
        t = out.setdefault(r.get("tag", "?"), {
            "lower_s": 0.0, "compile_s": 0.0, "cache_hit": True,
            "signatures": 0, "fusion_count": 0, "bytes_accessed": 0.0,
            "instructions": 0, "peak_memory_bytes": 0.0})
        t["lower_s"] += float(r.get("lower_s", 0.0))
        t["compile_s"] += float(r.get("compile_s", 0.0))
        t["cache_hit"] = t["cache_hit"] and bool(r.get("cache_hit"))
        t["signatures"] += 1
        t["fusion_count"] = max(t["fusion_count"],
                                int(r.get("fusion_count", 0)))
        t["bytes_accessed"] = max(t["bytes_accessed"],
                                  float(r.get("bytes_accessed", 0.0)))
        t["instructions"] = max(t["instructions"],
                                int(r.get("instructions", 0)))
        t["peak_memory_bytes"] = max(t["peak_memory_bytes"],
                                     float(r.get("peak_memory_bytes",
                                                 0.0)))
    return out


# -- listeners -----------------------------------------------------------

def add_listener(fn):
    """Observe compiles live: fn(event) with event["phase"] "start"
    ({tag, signature, retrace, changes}) or "done" ({tag, record}).
    Listener exceptions are swallowed — telemetry consumers must not
    break compiles."""
    with _lock:
        if fn not in _listeners:
            _listeners.append(fn)
    return fn


def remove_listener(fn):
    with _lock:
        if fn in _listeners:
            _listeners.remove(fn)


def _notify(event):
    with _lock:
        fns = list(_listeners)
    for fn in fns:
        try:
            fn(event)
        except Exception:
            pass


def reset():
    """Drop the ledger + forensic state (tests). Listeners persist."""
    with _lock:
        _ledger.clear()
        _tag_sigs.clear()
