"""Persistent XLA compilation cache — framework-level wiring.

The cold XLA compile of a real training step (60 s+ for the GPT-medium
bench config; minutes at 1.3B) dominates every short-lived process:
benchmarks, preemption restarts, eval jobs, CI. JAX ships a persistent
on-disk compilation cache keyed by the HLO fingerprint; this module turns
it on for the WHOLE framework at import time, so every
`paddle_tpu.jit`/`static.Executor`/`HybridTrainStep` compile in any
process is written to (and reloaded from) disk. A warm process skips the
cold compile entirely.

ONE rule says where the cache lives (`resolve_cache_dir`, jax-free):

  1. `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and the
     framework writes `jax_compilation_cache_dir` NOWHERE — whoever runs
     the program (a chip tool that carries the cache home, a CI job)
     places the cache from outside.
  2. else `PADDLE_TPU_COMPILE_CACHE`: a directory, or "0"/"off"/"none"
     to disable (the test suite's setting).
  3. else `<checkout>/.xla_cache` (in .gitignore) — a fixed path inside
     the tree, never the home directory, a temporary name, a pid or a
     time: the path must not move between runs for a second run to
     start warm.

  PADDLE_TPU_CACHE_MIN_COMPILE_SECS  only cache compiles slower than this
                                  (default 0: cache everything — a bench
                                  or trainer wants every entry warm)
  PADDLE_TPU_CACHE_MIN_ENTRY_BYTES   skip entries smaller than this
                                  (default 0)

A cache directory that cannot be created is reported once (a warning
naming the path) and the process runs with cold compiles.

Beyond the on-at-import wiring, this module owns two more cache
concerns:

- **per-compile hit/miss attribution** (`observe_compile`): jax emits
  `/jax/compilation_cache/cache_hits` / `cache_misses` monitoring
  events ON THE COMPILING THREAD, so a thread-local listener attributes
  a hit to exactly the compile that got it — correct even when the
  background warm executor (jit/warm.py) overlaps many compiles, where
  the old entry-set diff around each compile could blame one compile's
  new on-disk entry on another's window.

- **pack / seed** (`pack`, `seed_from`, tools/seed_compile_cache.py):
  a compiled cache directory is a portable artifact — pack one on any
  machine that has paid the cold compile, seed it into a fresh
  machine/process, and the first train step loads instead of compiling
  (the warm-start-across-processes reuse of arxiv 2412.14374).
"""
import json
import os
import shutil
import threading
import time
import warnings

# jax is imported inside the functions that configure it: the path rule
# (resolve_cache_dir) and the file helpers stay importable by a process
# that must not touch jax (tools/seed_compile_cache)

__all__ = ["enable_compile_cache", "disable_compile_cache", "cache_dir",
           "resolve_cache_dir", "DEFAULT_CACHE_DIR", "pack", "seed_from",
           "observe_compile", "PACK_SCHEMA"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")

_OFF_VALUES = ("0", "off", "none", "false", "disabled")

_state = {"dir": None}


def cache_dir():
    """The active cache directory, or None when the cache is disabled."""
    return _state["dir"]


def _is_off(value):
    return str(value).strip().lower() in _OFF_VALUES


def resolve_cache_dir(path=None):
    """The cache directory under the module's ONE rule (see the module
    doc), or None when disabled. `path` is a caller's explicit choice;
    like PADDLE_TPU_COMPILE_CACHE it yields to JAX_COMPILATION_CACHE_DIR
    (only an explicit "off" beats the variable). Touches neither jax
    nor the filesystem."""
    if path is None:
        path = os.environ.get("PADDLE_TPU_COMPILE_CACHE") or None
    if path is not None and _is_off(path):
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or path \
        or DEFAULT_CACHE_DIR
    return os.path.abspath(os.path.expanduser(str(path)))


def enable_compile_cache(path=None):
    """Turn JAX's persistent compilation cache on at
    `resolve_cache_dir(path)`. Idempotent; safe to call before or after
    backend init (the config is consulted at compile time). Returns the
    active directory, or None when disabled or when the directory
    cannot be created (warned once, naming the path)."""
    import jax
    path = resolve_cache_dir(path)
    if path is None:
        disable_compile_cache()
        return None
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        warnings.warn(
            f"compile cache directory {path!r} cannot be created ({e}); "
            "running with cold compiles", RuntimeWarning, stacklevel=2)
        disable_compile_cache()
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the ONE writer of the directory; where the variable is set,
        # jax has read it itself and nothing here overrides it
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("PADDLE_TPU_CACHE_MIN_COMPILE_SECS", "0")))
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(os.environ.get("PADDLE_TPU_CACHE_MIN_ENTRY_BYTES", "0")))
    # Make cache keys independent of the cache DIRECTORY PATH, so a
    # packed artifact seeds any machine and a checkout that moves still
    # hits. jax plants GPU-oriented sub-caches
    # (xla_gpu_per_fusion_autotune_cache_dir, ...) INSIDE the cache dir
    # and leaves those debug options in the key, so the key hashes the
    # absolute path (re-checked on jax 0.9.0, PR 21: the same program
    # under two directories still gets two keys by default and one key
    # with "none"). The sub-caches do nothing on TPU/CPU, so default
    # them OFF; PADDLE_TPU_CACHE_XLA_CACHES overrides (jax's values:
    # "all", "none", or a comma list of the flag names).
    jax.config.update(
        "jax_persistent_cache_enable_xla_caches",
        os.environ.get("PADDLE_TPU_CACHE_XLA_CACHES", "none"))
    _state["dir"] = path
    return path


def disable_compile_cache():
    """Turn the persistent cache off for this process (the switch, not
    the directory: `jax_compilation_cache_dir` is left as it is)."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    _state["dir"] = None


def cache_entry_count():
    """Number of entries currently on disk (0 when disabled/empty)."""
    return len(cache_entry_names())


def cache_entry_names():
    """The on-disk entry names as a frozenset (empty when disabled).
    Per-compile hit/miss attribution goes through `observe_compile`
    below (thread-local jax cache events + a claimed-entries ledger —
    exact under the background warm executor); this raw set remains the
    building block and the whole-process view tests diff."""
    d = _state["dir"]
    if not d or not os.path.isdir(d):
        return frozenset()
    try:
        return frozenset(n for n in os.listdir(d)
                         if not n.startswith(".")
                         and n not in _NON_ENTRY_FILES)
    except OSError:
        return frozenset()


# files that may live in a cache dir without being cache entries
_NON_ENTRY_FILES = frozenset(["MANIFEST.json"])


# -- per-compile hit/miss attribution ------------------------------------
#
# jax's compiler emits monitoring events on the thread running the
# compile; a thread-local slot therefore attributes hits/misses to
# exactly one compile even when the warm executor overlaps many.
# The on-disk entry-name diff stays as the `cache_entries_added` count,
# made overlap-safe by a claimed-entries ledger: each new entry is
# counted by at most one compile, and a compile the events called a HIT
# never claims (it wrote nothing — any entry in its window belongs to a
# concurrent miss).

_tls = threading.local()
_attr_lock = threading.Lock()
_claimed = set()           # entry names already attributed to a compile
_listener_state = {"installed": False, "ok": False}

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_cache_event(event, **kwargs):
    slot = getattr(_tls, "slot", None)
    if slot is None:
        return
    if event == _HIT_EVENT:
        slot["hit"] = True
        slot["seen"] = True
    elif event == _MISS_EVENT:
        slot["seen"] = True


def _install_listener():
    if _listener_state["installed"]:
        return _listener_state["ok"]
    with _attr_lock:
        if _listener_state["installed"]:
            return _listener_state["ok"]
        try:
            from jax._src import monitoring as _mon
            _mon.register_event_listener(_on_cache_event)
            _listener_state["ok"] = True
        except Exception:
            _listener_state["ok"] = False
        _listener_state["installed"] = True
    return _listener_state["ok"]


class _CompileObservation:
    """Result slot of one `observe_compile()` window: `cache_hit`
    (exact, event-attributed when the listener is available) and
    `entries_added` (names this compile may claim; counts shift between
    overlapping misses only, totals stay exact, hits always claim 0)."""

    def __init__(self):
        self.cache_on = False
        self.cache_hit = False
        self.entries_added = frozenset()


class observe_compile:
    """Context manager wrapping ONE compile on the current thread:

        with observe_compile() as obs:
            compiled = lowered.compile()
        obs.cache_hit, obs.entries_added

    Hit/miss comes from jax's own per-thread cache events (exact under
    the background warm executor); the entry diff is serialized through
    a claimed-set so two overlapping compiles never double-count (or
    cross-claim after a hit) the entries they add. Nested use attributes
    to the innermost window. Never raises: with no listener and no
    cache dir it degrades to a no-op observation.

    Known limit of the NO-LISTENER fallback (a future jax renaming the
    events): hit/miss reverts to the window diff, which under
    overlapping compiles can let a hit whose window swallowed a
    concurrent miss's entry claim it — flipping both labels. The
    listener path (every jax this repo supports today) has no such
    race; the fallback only ever regresses to the pre-pipeline
    behavior, never worse."""

    def __enter__(self):
        self.obs = _CompileObservation()
        self.obs.cache_on = cache_dir() is not None
        self._listener = _install_listener() if self.obs.cache_on \
            else False
        self._before = cache_entry_names() if self.obs.cache_on \
            else frozenset()
        self._slot = {"hit": False, "seen": False}
        self._prev = getattr(_tls, "slot", None)
        _tls.slot = self._slot
        return self.obs

    def __exit__(self, exc_type, exc, tb):
        _tls.slot = self._prev
        if not self.obs.cache_on:
            return False
        after = cache_entry_names()
        with _attr_lock:
            added = after - self._before - frozenset(_claimed)
            if self._listener and self._slot["hit"]:
                added = frozenset()  # a hit wrote nothing; leave any
                # window entries for the concurrent miss that did
            else:
                _claimed.update(added)
        self.obs.entries_added = added
        if self._listener and self._slot["seen"]:
            self.obs.cache_hit = self._slot["hit"]
        else:
            # listener unavailable (future jax) or cache never consulted
            # (e.g. a sub-jaxpr compile path): fall back to the diff
            self.obs.cache_hit = not added
        return False


# -- pack / seed ---------------------------------------------------------

PACK_SCHEMA = "paddle_tpu.compile_cache_pack.v1"


def pack(dest, source=None):
    """Copy the cache's entries into `dest` as a portable seed artifact
    (entry files + MANIFEST.json naming them). `source` defaults to the
    active cache dir. Returns {"path", "entries", "bytes"}; raises
    ValueError when there is no cache to pack — packing is an explicit
    operator action (tools/seed_compile_cache.py), not best-effort
    telemetry."""
    src = source or cache_dir()
    if not src or not os.path.isdir(src):
        raise ValueError(
            "no compile cache to pack — enable_compile_cache() first or "
            f"pass source= (got {src!r})")
    dest = os.path.abspath(os.path.expanduser(str(dest)))
    os.makedirs(dest, exist_ok=True)
    names, total = [], 0
    for n in sorted(os.listdir(src)):
        if n.startswith(".") or n in _NON_ENTRY_FILES:
            continue
        p = os.path.join(src, n)
        if not os.path.isfile(p):
            continue
        shutil.copy2(p, os.path.join(dest, n))
        names.append(n)
        total += os.path.getsize(p)
    import jax
    manifest = {"schema": PACK_SCHEMA, "entries": names,
                "total_bytes": total, "jax": jax.__version__,
                "packed_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime())}
    with open(os.path.join(dest, "MANIFEST.json"), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.write("\n")
    return {"path": dest, "entries": len(names), "bytes": total}


def copy_seed_entries(source, dest):
    """The pure-file half of seeding (no jax/framework state): copy the
    cache entries of `source` (a pack() artifact or a raw cache dir)
    into `dest`, skipping entries already present. Returns
    (seeded, skipped)."""
    os.makedirs(dest, exist_ok=True)
    seeded = skipped = 0
    for n in sorted(os.listdir(source)):
        if n.startswith(".") or n in _NON_ENTRY_FILES:
            continue
        sp = os.path.join(source, n)
        if not os.path.isfile(sp):
            continue
        dp = os.path.join(dest, n)
        if os.path.exists(dp):
            skipped += 1
            continue
        shutil.copy2(sp, dp)
        seeded += 1
    return seeded, skipped


def seed_from(source, dest=None):
    """Pre-populate the persistent cache from a donated artifact dir (a
    `pack()` output or any raw cache dir): every entry not already
    present is copied in, so the process's first compiles load instead
    of compiling. Enables the cache (at `dest` when given) if it is not
    already on. Emits one `kind:"seed"` metrics record + the
    `warm.seeded_entries` counter. Returns {"source", "cache_dir",
    "seeded", "skipped"}; raises ValueError on a missing source —
    a requested seed that silently does nothing would fake a warm
    start."""
    source = os.path.abspath(os.path.expanduser(str(source)))
    if not os.path.isdir(source):
        raise ValueError(f"seed source {source!r} is not a directory")
    d = cache_dir()
    if dest is not None or d is None:
        d = enable_compile_cache(dest)
    if d is None:
        raise ValueError("persistent compile cache unavailable — "
                         "cannot seed")
    seeded, skipped = copy_seed_entries(source, d)
    rec = {"source": source, "cache_dir": d, "entries_seeded": seeded,
           "entries_skipped": skipped}
    try:  # telemetry never blocks seeding
        from ..profiler import monitor as _monitor
        _monitor.counter("warm.seeded_entries").inc(seeded)
        _monitor.export_step(dict(rec), kind="seed")
    except Exception:
        pass
    return rec
