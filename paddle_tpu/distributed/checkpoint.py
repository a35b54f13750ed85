"""Fault-tolerant checkpointing: snapshot-then-write, atomic commit.

The TPU failure model (a preempted/evicted host kills the whole SPMD
program) makes restart-from-checkpoint the dominant recovery path, so
three properties are load-bearing (docs/FAULT_TOLERANCE.md):

1. **Latency off the critical path** — `CheckpointManager.save` first
   SNAPSHOTS params/opt-state/scaler/step as cheap on-device buffer
   copies (`TrainStep.snapshot_state`, jit/api.py: the per-leaf views
   copied before the next dispatch can donate their buffers), then
   returns; a background writer thread streams the shards to disk
   while training keeps stepping.
2. **Atomicity** — every checkpoint is written into a hidden
   `.tmp-step_*` directory (shards + `MANIFEST.json` with per-leaf
   shape/dtype/sharding/crc32 + a `COMMIT` marker, all fsynced) and
   becomes visible ONLY via one atomic `os.replace` to `step_NNNNNNNN`.
   A writer killed mid-save leaves a temp dir resume skips and GCs —
   never a half-readable checkpoint. In a multi-process (multi-host)
   program publication is SINGLE-WRITER: process 0 alone serializes
   and renames, so no rank can publish early and no jax collective
   ever runs on the background writer thread (a collective there
   could deadlock against the main thread's train-step collectives);
   true multi-host sharded layouts go through the orbax interchange
   path below.
3. **Verified resume** — `restore` scans newest→oldest, verifies the
   manifest (COMMIT present, files sized right, checksums match)
   BEFORE touching the train step, and falls back past partial/corrupt
   checkpoints. Arrays land directly in their dp/mp placement
   (`jax.device_put` onto each live leaf's sharding, then
   `set_tree_state`) — no gather-to-one-host.

Observability: every save/restore/GC emits a `kind:"ckpt"` metrics
record (phase seconds for snapshot/serialize/write/commit, bytes,
verified flag — schema enforced by tools/check_metrics_schema.py),
`ckpt.*` counters/histograms, host spans that render on the Perfetto
"checkpoint" track (profiler/trace_export.py), and a `ckpt_state.json`
artifact in every flight-recorder debug bundle. Fault sites
(`ckpt.snapshot` / `ckpt.serialize` / `ckpt.write` / `ckpt.commit`)
are instrumented for framework/fault_injection.py, so kill/EIO/
truncate/corrupt drills exercise exactly this code.

The orbax-backed `save_sharded`/`load_sharded`/`save_train_state`/
`load_train_state` functions remain as the interchange-format path
(multi-host orbax layouts); `CheckpointManager` is the production
fault-tolerance subsystem `ElasticController` and `Model.fit(resume=)`
drive.
"""
import json
import os
import queue
import re
import shutil
import threading
import time
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from ..framework import fault_injection as _fault
from ..profiler import monitor as _monitor
from ..profiler import statistic as _stat
from ..profiler import flight_recorder as _flight
from ..profiler import mem_observatory as _mobs

__all__ = ["CheckpointManager", "AsyncSaveHandle",
           "CorruptCheckpointError",
           "save_sharded", "load_sharded", "save_train_state",
           "load_train_state"]


class CorruptCheckpointError(Exception):
    """A committed-looking checkpoint failed an integrity check at
    read time (checksum mismatch) — restore falls back past it."""

MANIFEST_NAME = "MANIFEST.json"
COMMIT_NAME = "COMMIT"
MANIFEST_SCHEMA = "paddle_tpu.ckpt.v1"
_TMP_PREFIX = ".tmp-"
_STEP_RE = re.compile(r"^step_(\d+)$")


def _step_dirname(step):
    return f"step_{int(step):08d}"


def _np_dtype(name):
    """np.dtype for a manifest dtype string, including the ml_dtypes
    extension types (bfloat16, float8_*) numpy doesn't know natively."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _fsync_dir(path):
    """fsync a directory so a rename into it survives power loss."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _sharding_str(leaf):
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return None
    spec = getattr(sh, "spec", None)
    return str(spec) if spec is not None else str(sh)


class AsyncSaveHandle:
    """Future for one background checkpoint write. `result()` blocks
    until the checkpoint is COMMITTED (or re-raises the writer's
    failure); `done()` never blocks. `wait_until_finished()` aliases
    `result()` for orbax-handle API compatibility."""

    def __init__(self, step):
        self.step = int(step)
        self.path = None       # committed directory (None until done)
        self.record = None     # the kind:"ckpt" record of this save
        self.error = None
        self._done = threading.Event()

    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"checkpoint save of step {self.step} did not finish "
                f"within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.path

    def wait_until_finished(self, timeout=None):
        return self.result(timeout)

    def _resolve(self, path=None, record=None, error=None):
        self.path = path  # lint-ok[unlocked-shared-state]: published before _done.set(); result() reads only after _done.wait() — Event happens-before
        self.record = record
        self.error = error  # lint-ok[unlocked-shared-state]: same Event happens-before as path: set before _done.set(), read after wait()
        self._done.set()


class CheckpointManager:
    """Snapshot-then-write async checkpointing with atomic commits,
    verified resume, and retention GC. See the module docstring.

        mgr = CheckpointManager(dir, keep_last=3, keep_every=1000)
        start = mgr.restore(step) or 0       # newest verified ckpt
        ...
        handle = mgr.save(step)              # returns immediately
        ...
        mgr.wait()                           # drain pending writes

    `keep_last` committed checkpoints are retained (newest), plus every
    checkpoint whose step is a multiple of `keep_every` (archival
    anchors). One background writer thread serializes writes, so
    overlapping saves queue instead of blocking the step loop.
    """

    def __init__(self, directory, keep_last=3, keep_every=None):
        self.directory = os.path.abspath(directory)
        self.keep_last = max(1, int(keep_last))
        self.keep_every = int(keep_every) if keep_every else None
        self._queue = queue.Queue()
        self._writer = None
        self._writer_gate = threading.Lock()
        self._writing = False
        # queued + in-flight saves; incremented at enqueue, decremented
        # when the write resolves — busy()/wait() read THIS, not the
        # queue, so the window between a queue pop and the write start
        # can't read as idle
        self._pending = 0
        self.last_save_record = None
        self.last_restore_record = None
        self.last_error = None
        # the debug-bundle artifact: a wedged/killed process dumps this
        # manager's view of the checkpoint state as ckpt_state.json
        _flight.register_state_provider("ckpt_state", self.debug_state)

    # -- save (hot path: must never block on the device or the disk) ----
    def save(self, step_obj, step=None, skip_if_busy=False):
        """Snapshot `step_obj`'s training state on device and enqueue
        the background write; returns an `AsyncSaveHandle` immediately.
        `step_obj` is a TrainStep/HybridTrainStep (anything with
        `snapshot_state()`/`tree_state()`), or a plain pytree of
        arrays. `skip_if_busy=True` returns None when a write is
        already QUEUED behind the in-flight one (bounds live snapshot
        copies to two when the save cadence outruns the disk; one save
        may always overlap the current write)."""
        if skip_if_busy and not self._queue.empty():
            _monitor.counter("ckpt.skipped_busy").inc()
            _flight.record_event("ckpt_skipped_busy",
                                 step=int(step or 0))
            return None
        t0 = time.perf_counter()
        if step is None:
            step = int(getattr(step_obj, "_step_i", 0))
        _fault.fire("ckpt.snapshot")
        _stat.begin_span("ckpt.snapshot")
        try:
            try:
                tree = self._snapshot(step_obj)
            except RuntimeError as e:
                if _mobs.is_oom(e):
                    # the snapshot's HBM copies are the classic
                    # tip-over allocation: dump the attribution ledger
                    # before surfacing who already held the bytes
                    raise _mobs.oom_error(e, site="ckpt.snapshot") \
                        from e
                raise
        finally:
            snapshot_s = _stat.end_span()
        # memory-observatory attribution: per-array weakrefs — the tag
        # empties itself when the writer drops the snapshot
        _mobs.register_arrays(
            "ckpt_snapshot",
            [x for x in jax.tree.leaves(tree)
             if getattr(x, "nbytes", None) is not None])
        _monitor.histogram("ckpt.snapshot_s").observe(snapshot_s)
        handle = AsyncSaveHandle(step)
        with self._writer_gate:
            self._pending += 1
        self._queue.put((tree, int(step), t0, snapshot_s, handle))
        self._ensure_writer()
        return handle

    @staticmethod
    def _snapshot(step_obj):
        """On-device buffer copies of the training state — cheap HBM
        copies that detach the snapshot from the donated buffers the
        NEXT dispatch will invalidate. Dispatching the copies is
        host-async; the blocking device read happens on the writer."""
        if hasattr(step_obj, "snapshot_state"):
            return step_obj.snapshot_state()
        if isinstance(step_obj, dict):
            return jax.tree.map(jnp.copy, step_obj)
        raise TypeError(
            f"cannot checkpoint {type(step_obj).__name__}: expected a "
            "train step with snapshot_state()/tree_state() or a pytree "
            "of arrays")

    def busy(self):
        """True while the writer has queued or in-flight work."""
        return self._pending > 0  # lint-ok[unlocked-shared-state]: GIL-atomic int read of a gate-guarded counter; busy()/wait() poll, staleness only extends the poll by one tick

    def wait(self, timeout=None):
        """Block until every queued write has committed (or failed).
        Errors stay on their handles; `last_error` keeps the newest."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.busy():
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError("checkpoint writer did not drain")
            time.sleep(0.005)

    def close(self):
        """Drain and stop the writer thread."""
        self.wait()
        if self._writer is not None and self._writer.is_alive():
            self._queue.put(None)
            self._writer.join(timeout=5)
        self._writer = None

    # -- background writer ---------------------------------------------
    def _ensure_writer(self):
        with self._writer_gate:
            if self._writer is None or not self._writer.is_alive():
                self._writer = threading.Thread(
                    target=self._writer_loop, name="ckpt-writer",
                    daemon=True)
                self._writer.start()

    def _writer_loop(self):
        while True:
            job = self._queue.get()
            if job is None:
                return
            self._writing = True
            try:
                self._write_one(*job)
            except BaseException:  # _write_one reports its own errors
                pass
            finally:
                self._writing = False
                with self._writer_gate:
                    self._pending -= 1  # lint-ok[unlocked-shared-state]: busy()/wait() read _pending WITHOUT the gate on purpose — they sit on the step loop's hot path (hot-sync fenced) and a GIL-atomic int read tolerates staleness; writes stay serialized under the gate

    def _write_one(self, tree, step, t0, snapshot_s, handle):
        from jax.tree_util import tree_flatten_with_path, keystr
        serialize_s = write_s = commit_s = 0.0
        total_bytes = 0
        n_leaves = 0
        tmp = None
        _stat.begin_span("ckpt.save_async")
        try:
            # single-writer publish: in a multi-process (multi-host)
            # program only process 0 serializes and publishes — no jax
            # collective ever runs on this background thread (a
            # collective here could deadlock against the main thread's
            # train-step collectives, and per-rank skip_if_busy
            # decisions diverge). True multi-host SHARDED layouts (each
            # host writing only its addressable shards) go through the
            # orbax interchange path (save_train_state(use_async=True)).
            if jax.process_count() > 1 and jax.process_index() != 0:
                handle._resolve(
                    path=os.path.join(self.directory,
                                      _step_dirname(step)))
                return
            os.makedirs(self.directory, exist_ok=True)
            tmp = os.path.join(
                self.directory,
                f"{_TMP_PREFIX}{_step_dirname(step)}-{os.getpid()}-"
                f"{threading.get_ident() & 0xffff:x}-{time.time_ns() & 0xffffff:x}")
            os.makedirs(tmp, exist_ok=True)

            # serialize: the ONE deliberate blocking device read of the
            # checkpoint path — on the writer thread, never the step loop
            _stat.begin_span("ckpt.serialize")
            try:
                _fault.fire("ckpt.serialize")
                path_leaves, _ = tree_flatten_with_path(tree)
                host = [(keystr(p), _sharding_str(leaf),
                         jax.device_get(leaf))
                        for p, leaf in path_leaves]
            finally:
                serialize_s = _stat.end_span()
            n_leaves = len(host)

            _stat.begin_span("ckpt.write")
            try:
                entries = []
                for i, (key, shard_str, arr) in enumerate(host):
                    arr = np.asarray(arr)
                    data = arr.tobytes()
                    fname = f"shard_{i:05d}.bin"
                    fpath = os.path.join(tmp, fname)
                    with open(fpath, "wb") as f:
                        f.write(data)
                        f.flush()
                        os.fsync(f.fileno())
                    # fault site fires AFTER the bytes land so
                    # truncate/corrupt can tear a real file and a kill
                    # leaves a genuinely partial temp dir
                    _fault.fire("ckpt.write", path=fpath)
                    entries.append({
                        "key": key, "file": fname,
                        "shape": list(arr.shape),
                        "dtype": str(arr.dtype),
                        "nbytes": len(data),
                        "crc32": zlib.crc32(data),
                        "sharding": shard_str})
                    total_bytes += len(data)
                manifest = {
                    "schema": MANIFEST_SCHEMA,
                    "step": int(step),
                    "ts": time.time(),
                    "rank": _monitor.rank(),
                    "nbytes": total_bytes,
                    "n_leaves": n_leaves,
                    "leaves": entries,
                }
                mpath = os.path.join(tmp, MANIFEST_NAME)
                with open(mpath, "w") as f:
                    json.dump(manifest, f, indent=1)
                    f.flush()
                    os.fsync(f.fileno())
            finally:
                write_s = _stat.end_span()

            _stat.begin_span("ckpt.commit")
            try:
                _fault.fire("ckpt.commit", path=mpath)
                # COMMIT marker: written last inside the temp dir, so a
                # directory that somehow carries the final name without
                # it (non-atomic copy, cosmic rename) still fails
                # verification
                cpath = os.path.join(tmp, COMMIT_NAME)
                with open(cpath, "w") as f:
                    json.dump({"step": int(step), "nbytes": total_bytes,
                               "n_leaves": n_leaves}, f)
                    f.flush()
                    os.fsync(f.fileno())
                final = os.path.join(self.directory, _step_dirname(step))
                if os.path.isdir(final):
                    # re-save of an already-committed step (resume
                    # exactly on a save boundary): replace it
                    shutil.rmtree(final, ignore_errors=True)
                os.replace(tmp, final)
                _fsync_dir(self.directory)
            finally:
                commit_s = _stat.end_span()

            total_s = time.perf_counter() - t0
            rec = {"op": "save", "step": int(step),
                   "dir": self.directory, "path": final,
                   "snapshot_s": round(snapshot_s, 6),
                   "serialize_s": round(serialize_s, 6),
                   "write_s": round(write_s, 6),
                   "commit_s": round(commit_s, 6),
                   "total_s": round(total_s, 6),
                   "bytes": int(total_bytes),
                   "n_leaves": int(n_leaves),
                   "committed": True}
            self.last_save_record = rec  # lint-ok[unlocked-shared-state]: atomic reference publish of a fresh dict; debug_state is the watchdog's diagnosis path and must never wait on the writer's locks
            _monitor.export_step(rec, kind="ckpt")
            _monitor.counter("ckpt.saves").inc()
            _monitor.counter("ckpt.bytes").inc(int(total_bytes))
            _monitor.histogram("ckpt.write_s").observe(write_s)
            _monitor.histogram("ckpt.total_s").observe(total_s)
            _monitor.gauge("ckpt.last_step").set(int(step))
            self._gc(step)
            handle._resolve(path=final, record=rec)
        except BaseException as e:
            if tmp:
                shutil.rmtree(tmp, ignore_errors=True)
            self.last_error = e  # lint-ok[unlocked-shared-state]: atomic reference publish, never cleared; the lock-free debug_state read sees the old or the new error, both valid
            rec = {"op": "save", "step": int(step),
                   "dir": self.directory, "path": tmp or self.directory,
                   "snapshot_s": round(snapshot_s, 6),
                   "serialize_s": round(serialize_s, 6),
                   "write_s": round(write_s, 6),
                   "commit_s": round(commit_s, 6),
                   "total_s": round(time.perf_counter() - t0, 6),
                   "bytes": int(total_bytes),
                   "n_leaves": int(n_leaves),
                   "committed": False,
                   "error": f"{type(e).__name__}: {e}"[:300]}
            self.last_save_record = rec  # lint-ok[unlocked-shared-state]: atomic reference publish of a fresh dict (failure branch), same as the success-path publish above
            _monitor.export_step(rec, kind="ckpt")
            _monitor.counter("ckpt.save_failures").inc()
            _flight.record_event("ckpt_save_failed", step=int(step),
                                 error=f"{type(e).__name__}: {e}"[:300])
            handle._resolve(record=rec, error=e)
        finally:
            _stat.end_span()  # ckpt.save_async

    # -- scan / verify --------------------------------------------------
    def all_steps(self):
        """Committed checkpoint steps, ascending. Non-conforming names
        (stray files, `.tmp-*` partials, `step_12.tmp`) are ignored —
        a malformed dir entry must never crash resume."""
        if not os.path.isdir(self.directory):
            return []
        out = []
        for d in os.listdir(self.directory):
            m = _STEP_RE.match(d)
            if m and os.path.isdir(os.path.join(self.directory, d)):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest(self):
        """Path of the newest committed checkpoint dir, or None."""
        steps = self.all_steps()
        if not steps:
            return None
        return os.path.join(self.directory, _step_dirname(steps[-1]))

    def verify(self, path, check_crc=True):
        """(ok, problem, manifest) integrity check of one checkpoint
        dir: COMMIT marker present, manifest parses, every shard file
        exists with the recorded size — and, with `check_crc`, the
        recorded crc32 (a full read; restore() passes False and
        checks crcs on the ONE read `_apply` does anyway, so recovery
        never reads a multi-GB checkpoint twice). Never raises."""
        try:
            if not os.path.isfile(os.path.join(path, COMMIT_NAME)):
                return False, "no COMMIT marker (uncommitted/partial)", \
                    None
            with open(os.path.join(path, MANIFEST_NAME)) as f:
                manifest = json.load(f)
            if manifest.get("schema") != MANIFEST_SCHEMA or \
                    not isinstance(manifest.get("leaves"), list):
                return False, "manifest schema mismatch", None
            for e in manifest["leaves"]:
                fpath = os.path.join(path, e["file"])
                if not os.path.isfile(fpath):
                    return False, f"missing shard {e['file']}", None
                if os.path.getsize(fpath) != e["nbytes"]:
                    return False, (f"shard {e['file']} truncated: "
                                   f"{os.path.getsize(fpath)} != "
                                   f"{e['nbytes']} bytes"), None
                if check_crc:
                    with open(fpath, "rb") as f:
                        if zlib.crc32(f.read()) != e["crc32"]:
                            return False, \
                                f"shard {e['file']} checksum mismatch", \
                                None
            return True, None, manifest
        except (OSError, ValueError, KeyError, TypeError) as e:
            return False, f"{type(e).__name__}: {e}", None

    # -- restore ---------------------------------------------------------
    def restore(self, step_obj):
        """Restore the newest VERIFIED checkpoint into `step_obj`
        (through its layout-aware `set_tree_state`, arrays placed
        directly onto each live leaf's sharding). Falls back past
        partial/corrupt checkpoints; GCs dead `.tmp-*` partials.
        Returns the restored step, or None when nothing restorable."""
        t0 = time.perf_counter()
        self._gc_partials()
        fell_back = 0
        for step in reversed(self.all_steps()):
            path = os.path.join(self.directory, _step_dirname(step))
            # structural verify here; checksums ride _apply's single
            # read (no double read of a multi-GB checkpoint)
            ok, problem, manifest = self.verify(path, check_crc=False)
            if ok:
                try:
                    nbytes = self._apply(step_obj, path, manifest)
                except CorruptCheckpointError as e:
                    ok, problem = False, str(e)
            if not ok:
                fell_back += 1
                _monitor.counter("ckpt.fallbacks").inc()
                _flight.record_event("ckpt_fallback", step=int(step),
                                     path=path, problem=str(problem))
                continue
            rec = {"op": "restore", "step": int(step),
                   "dir": self.directory, "path": path,
                   "verified": True, "fell_back": int(fell_back),
                   "bytes": int(nbytes),
                   "total_s": round(time.perf_counter() - t0, 6)}
            self.last_restore_record = rec
            _monitor.export_step(rec, kind="ckpt")
            _monitor.counter("ckpt.restores").inc()
            return int(step)
        if fell_back:
            rec = {"op": "restore", "step": 0, "dir": self.directory,
                   "path": self.directory, "verified": False,
                   "fell_back": int(fell_back), "bytes": 0,
                   "total_s": round(time.perf_counter() - t0, 6)}
            self.last_restore_record = rec
            _monitor.export_step(rec, kind="ckpt")
        return None

    def _apply(self, step_obj, path, manifest):
        """Load one structurally-verified checkpoint into the step
        object (or, for a plain dict tree, back into the dict in
        place). Checksums are validated on THIS read — a mismatch
        raises CorruptCheckpointError (restore falls back) BEFORE any
        state is touched; every leaf loads first, then the state
        installs atomically. Structure or shape mismatch vs the live
        target raises ValueError — that is an incompatible checkpoint
        (wrong model/config), not corruption, and falling back to an
        older one would not fix it."""
        from jax.tree_util import tree_flatten_with_path, keystr, \
            tree_unflatten
        has_tree_state = hasattr(step_obj, "tree_state")
        if not has_tree_state and not isinstance(step_obj, dict):
            raise TypeError(
                f"cannot restore into {type(step_obj).__name__}: "
                "expected a train step with tree_state()/set_tree_state "
                "or a plain dict pytree")
        target = step_obj.tree_state() if has_tree_state else step_obj
        path_leaves, treedef = tree_flatten_with_path(target)
        by_key = {e["key"]: e for e in manifest["leaves"]}
        want = [keystr(p) for p, _ in path_leaves]
        if set(want) != set(by_key):
            missing = sorted(set(want) - set(by_key))[:3]
            extra = sorted(set(by_key) - set(want))[:3]
            raise ValueError(
                f"checkpoint {path} does not match this train step's "
                f"state tree (missing {missing}, unexpected {extra}) — "
                "same model/optimizer/scaler config required to resume")
        new_leaves = []
        nbytes = 0
        for (p, cur), key in zip(path_leaves, want):
            e = by_key[key]
            if tuple(e["shape"]) != tuple(np.shape(cur)):
                raise ValueError(
                    f"checkpoint leaf {key} shape {tuple(e['shape'])} "
                    f"!= live shape {tuple(np.shape(cur))}")
            with open(os.path.join(path, e["file"]), "rb") as f:
                data = f.read()
            if zlib.crc32(data) != e["crc32"]:
                raise CorruptCheckpointError(
                    f"shard {e['file']} checksum mismatch")
            nbytes += len(data)
            arr = np.frombuffer(data, dtype=_np_dtype(e["dtype"]))
            arr = arr.reshape(tuple(e["shape"]))
            sh = getattr(cur, "sharding", None)
            # direct placement: each restored array lands with the
            # live leaf's sharding (dp/mp/ZeRO placement preserved —
            # no host-0 materialization of the full tree)
            new_leaves.append(jax.device_put(arr, sh) if sh is not None
                              else jnp.asarray(arr))
        new_tree = tree_unflatten(treedef, new_leaves)
        if has_tree_state:
            step_obj.set_tree_state(new_tree.get("params"),
                                    new_tree.get("opt_state"))
            scaler = new_tree.get("scaler_state")
            if scaler:
                step_obj.scaler_state = scaler
            step_obj._step_i = int(manifest["step"])
        else:  # plain dict tree: restore in place
            step_obj.clear()
            step_obj.update(new_tree)
        return nbytes

    # -- retention -------------------------------------------------------
    def _gc(self, current_step):
        """Retention: keep the newest `keep_last` committed checkpoints
        plus every step divisible by `keep_every`; remove the rest."""
        steps = self.all_steps()
        keep = set(steps[-self.keep_last:])
        if self.keep_every:
            keep.update(s for s in steps
                        if s and s % self.keep_every == 0)
        removed = [s for s in steps if s not in keep]
        for s in removed:
            shutil.rmtree(os.path.join(self.directory, _step_dirname(s)),
                          ignore_errors=True)
        if removed:
            _monitor.counter("ckpt.gc_removed").inc(len(removed))
            _monitor.export_step(
                {"op": "gc", "step": int(current_step),
                 "dir": self.directory, "removed": len(removed),
                 "removed_steps": removed}, kind="ckpt")

    def _gc_partials(self):
        """Remove dead `.tmp-*` partial dirs (a writer killed mid-save;
        a LIVE writer would be this process's own, and restore runs
        before training starts saving)."""
        if not os.path.isdir(self.directory):
            return
        for d in os.listdir(self.directory):
            if d.startswith(_TMP_PREFIX):
                shutil.rmtree(os.path.join(self.directory, d),
                              ignore_errors=True)
                _flight.record_event("ckpt_partial_gc", path=d)

    # -- diagnostics -----------------------------------------------------
    def debug_state(self):
        """The flight-recorder bundle artifact (ckpt_state.json)."""
        return {
            "directory": self.directory,
            "committed_steps": self.all_steps(),
            "queued_writes": self._queue.qsize(),
            "writing": self._writing,
            "keep_last": self.keep_last,
            "keep_every": self.keep_every,
            "last_save": self.last_save_record,
            "last_restore": self.last_restore_record,
            "last_error": str(self.last_error) if self.last_error
            else None,
        }


# ---------------------------------------------------------------------
# orbax-backed interchange format (multi-host sharded layouts). Kept as
# the compatibility path; CheckpointManager above is the production
# fault-tolerance subsystem.
# ---------------------------------------------------------------------

def _checkpointer(use_async=False):
    import orbax.checkpoint as ocp
    if use_async:
        return ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    return ocp.Checkpointer(ocp.StandardCheckpointHandler())


def save_sharded(tree, path, use_async=False):
    """Save a pytree of (possibly sharded) jax arrays via orbax."""
    path = os.path.abspath(path)
    ckptr = _checkpointer(use_async)
    ckptr.save(path, tree, force=True)
    if use_async:
        return ckptr  # caller may .wait_until_finished()
    return None


def load_sharded(path, target_tree=None, shardings=None):
    """Restore; when `shardings` (matching pytree of NamedSharding) is
    given, arrays land directly in their distributed placement."""
    import orbax.checkpoint as ocp
    path = os.path.abspath(path)
    ckptr = _checkpointer()
    if target_tree is None and shardings is None:
        return ckptr.restore(path)
    if shardings is not None:
        abstract = jax.tree.map(
            lambda arr, sh: jax.ShapeDtypeStruct(np.shape(arr),
                                                 np.asarray(arr).dtype
                                                 if not hasattr(arr, "dtype")
                                                 else arr.dtype,
                                                 sharding=sh),
            target_tree, shardings,
            is_leaf=lambda x: hasattr(x, "dtype") or np.isscalar(x))
        return ckptr.restore(path, args=ocp.args.StandardRestore(abstract))
    return ckptr.restore(path, args=ocp.args.StandardRestore(target_tree))


def save_train_state(step_obj, path, use_async=False):
    """Checkpoint a HybridTrainStep / TrainStep (params + opt state)
    in the orbax interchange format."""
    tree = {"params": step_obj.params,
            "opt_state": jax.tree.map(
                lambda x: x, step_obj.opt_state,
                is_leaf=lambda x: hasattr(x, "dtype")),
            "step": np.asarray(step_obj._step_i)}
    return save_sharded(tree, path, use_async)


def load_train_state(step_obj, path):
    """Restore an orbax interchange checkpoint into a train step. On a
    hybrid (meshed) step every array is restored DIRECTLY into its live
    dp/mp/ZeRO sharding — the shardings tree is passed through to
    orbax, so no rank materializes the full unsharded state."""
    target = {"params": step_obj.params,
              "opt_state": dict(step_obj.opt_state),
              "step": np.asarray(step_obj._step_i)}
    shardings = None
    if hasattr(step_obj, "mesh"):
        from jax.sharding import NamedSharding, PartitionSpec as P
        replicated = NamedSharding(step_obj.mesh, P())
        shardings = jax.tree.map(
            lambda arr: getattr(arr, "sharding", replicated),
            target, is_leaf=lambda x: hasattr(x, "dtype"))
    restored = load_sharded(path, target, shardings)
    opt_state = jax.tree.map(
        lambda cur, new: new, target["opt_state"], restored["opt_state"],
        is_leaf=lambda x: hasattr(x, "dtype"))
    if hasattr(step_obj, "set_tree_state"):
        # params/opt_state are per-leaf VIEWS (the donated truth may be
        # the fused epilogue's flat stores, or the hybrid step's sharded
        # dicts) — restore through the layout-aware setter
        step_obj.set_tree_state(restored["params"], opt_state)
    else:
        step_obj.params = restored["params"]
        step_obj.opt_state = opt_state
    step_obj._step_i = int(restored["step"])
    return step_obj
