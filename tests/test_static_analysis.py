"""paddlelint — the unified concurrency + tracing-safety static
analyzer (ISSUE 14, tools/lint/ + tools/paddlelint.py,
docs/STATIC_ANALYSIS.md).

Proof points:
- every pass is GREEN on HEAD (zero unsuppressed findings over the
  real fileset) and RED on its known-bad fixture corpus
  (tools/lint/fixtures/<pass>/), naming file:line and the violated
  rule;
- the suppression engine: `# lint-ok[pass]: <why>` suppresses exactly
  its line/pass, a marker WITHOUT a reason is itself a finding, and
  suppressed findings still reach the kind:"lint" ledger with their
  reasons;
- the baseline ratchet refuses to loosen: suppressed-count growth
  fails the gate, `--update` only ever writes counts DOWN;
- `tools/check_no_hot_sync.py` stays a byte-compatible shim over the
  hot-sync pass (same verdict strings, same exit codes — the
  pre-existing lint tests in test_async_pipeline.py and friends run
  unchanged on top);
- `kind:"lint"` records validate against tools/check_metrics_schema.py
  (pass from the known set, file:line present, severity enum,
  suppressed => non-empty reason) and the schema tool's pass set never
  drifts from the framework's;
- tools/obs_report.py renders the findings section.

All host-side source analysis — no device work; runs in tier-1.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
FIXTURES = os.path.join(TOOLS, "lint", "fixtures")

if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import paddlelint  # noqa: E402
from lint import ALL_PASSES, KNOWN_PASS_NAMES, PASS_NAMES, core  # noqa: E402


def _load_tool(name):
    path = os.path.join(TOOLS, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_session_ledger(monkeypatch):
    """The driver appends findings to PADDLE_TPU_METRICS_FILE when set
    (the canonical-workload contract) — keep these runs out of
    whatever ledger the surrounding test session configured."""
    monkeypatch.delenv("PADDLE_TPU_METRICS_FILE", raising=False)


@pytest.fixture(scope="module")
def head_findings():
    """ONE full-analysis run over HEAD shared by the read-only tests
    (a run is ~3.5 s; tier-1's budget prefers one to a dozen)."""
    findings, _ = paddlelint.run_passes()
    return findings


def _ctx_from_source(src, rel="m.py"):
    """ProjectContext over one synthetic file."""
    d = tempfile.mkdtemp(prefix="lint_test_")
    path = os.path.join(d, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(src))
    return core.ProjectContext(d, [rel]), d


FIXTURE_DIRS = {
    "lock-order": "lock_order",
    "blocking-under-lock": "blocking_under_lock",
    "unlocked-shared-state": "unlocked_shared_state",
    "use-after-donate": "use_after_donate",
    "hot-sync": "hot_sync",
}

# the rule each corpus MUST trip (red is necessary; red on the RIGHT
# rule is the proof the pass still understands its bug class)
FIXTURE_EXPECT = {
    "lock-order": {"lock-cycle", "lock-self-cycle"},
    "blocking-under-lock": {"file-io-under-lock", "wait-under-lock",
                            "unbounded-acquire"},
    "unlocked-shared-state": {"unlocked-shared-write"},
    "use-after-donate": {"use-after-donate"},
    "hot-sync": {"sync-in-hot-region"},
}


# ---------------------------------------------------------------- HEAD

def test_paddlelint_green_on_head(head_findings):
    """The acceptance gate: zero unsuppressed findings at HEAD, every
    suppression carrying a reason, exit code 0."""
    unsup = [f for f in head_findings if not f.suppressed]
    assert unsup == [], "\n".join(f.render() for f in unsup)
    for f in head_findings:
        assert f.reason and f.reason.strip(), f.render()
    assert paddlelint.main([]) == 0


def test_each_pass_green_on_head_individually(head_findings):
    """Per-pass green, from the shared run (the passes are
    independent: a full-run finding carries its pass name); hot-sync
    additionally proves a standalone --select run below."""
    for name in PASS_NAMES:
        bad = [f for f in head_findings
               if f.pass_name == name and not f.suppressed]
        assert bad == [], f"{name}: " + "\n".join(
            f.render() for f in bad)


# ------------------------------------------------------------ fixtures

@pytest.mark.parametrize("name", sorted(FIXTURE_DIRS))
def test_pass_red_on_fixture_corpus(name):
    root = os.path.join(FIXTURES, FIXTURE_DIRS[name])
    findings, _ = paddlelint.run_passes(root=root, select=[name])
    live = [f for f in findings
            if not f.suppressed and f.pass_name == name]
    assert live, f"{name} corpus produced no findings"
    rules = {f.rule for f in live}
    missing = FIXTURE_EXPECT[name] - rules
    assert not missing, \
        f"{name} corpus missed expected rule(s) {missing}; got {rules}"
    # every finding names file:line and the violated rule
    for f in live:
        assert f.file and f.line >= 0 and f.rule, f.render()
    # and the CLI exits 1 on the corpus
    rc = paddlelint.main([root, "--select", name])
    assert rc == 1


def test_symlinked_repo_root_gets_curated_fileset(tmp_path):
    """Any repo-SHAPED root — a symlinked spelling, a worktree, a CI
    copy — must resolve to the curated fileset (fixtures excluded),
    not corpus mode: else a second checkout lints the known-bad
    corpora as real findings."""
    link = str(tmp_path / "repolink")
    os.symlink(REPO, link)
    findings, ctx = paddlelint.run_passes(root=link)
    assert not any("fixtures" in sf.rel for sf in ctx.files)
    assert [f for f in findings if not f.suppressed] == []
    # a partial copy with the repo layout: curated mode, no fixtures
    copy = tmp_path / "checkout"
    for rel in ("paddle_tpu/__init__.py", "tools/lint/__init__.py",
                "tools/lint/fixtures/lock_order/deadlock.py"):
        dst = copy / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    _, ctx2 = paddlelint.run_passes(root=str(copy))
    assert not any("fixtures" in sf.rel for sf in ctx2.files)


def test_fixtures_excluded_from_default_fileset():
    rels = core.default_fileset(REPO)
    assert not any("fixtures" in r for r in rels)
    assert "paddle_tpu/inference/serving.py" in rels
    assert "tools/paddlelint.py" in rels


# ------------------------------------------------- targeted bug shapes

def test_lock_order_cycle_and_reentrant_exemption():
    ctx, d = _ctx_from_source("""
        import threading
        _a = threading.Lock()
        _b = threading.Lock()
        _r = threading.RLock()

        def one():
            with _a:
                with _b:
                    pass

        def two():
            with _b:
                with _a:
                    pass

        def reentrant_ok():
            with _r:
                with _r:
                    pass
        """)
    try:
        from lint.lock_order import LockOrderPass
        fs = LockOrderPass().run(ctx)
        assert any(f.rule == "lock-cycle" for f in fs)
        # the RLock self-nest is exempt by construction
        assert not any(f.rule == "lock-self-cycle" for f in fs)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_blocking_under_lock_via_call_chain():
    """The PR 10 trace.finish() shape: the blocking op is one call hop
    away from the lock."""
    ctx, d = _ctx_from_source("""
        import threading
        _lock = threading.Lock()

        def _emit(path):
            with open(path, "a") as f:
                f.write("x")

        def close(path):
            with _lock:
                _emit(path)
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = BlockingUnderLockPass().run(ctx)
        hits = [f for f in fs if f.rule == "file-io-under-lock"]
        assert any("via _emit" in f.message for f in hits), \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_blocking_event_wait_under_lock_flagged():
    """Event.wait blocks while HOLDING enclosing locks (unlike
    Condition.wait, which releases its own) — under a lock it is the
    hang class the pass exists to catch."""
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._done_event = threading.Event()
                self._cv = threading.Condition()

            def bad(self):
                with self._lock:
                    self._done_event.wait()

            def fine(self):
                with self._cv:
                    self._cv.wait(timeout=1.0)
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = [f for f in BlockingUnderLockPass().run(ctx)
              if f.rule == "wait-under-lock"]
        assert len(fs) == 1 and "_done_event" in fs[0].message, \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_blocking_allowed_inner_lock_does_not_mask_outer():
    """An ALLOWED inner lock must not suppress blocking work that ALSO
    runs under a disallowed outer lock (the PR 10 class, nested)."""
    ctx, d = _ctx_from_source("""
        import threading

        class monitorlike:
            pass

        class Engine:
            def __init__(self):
                self._cv = threading.Condition()

            def close(self, path):
                with self._cv:
                    with _export_lock:
                        with open(path, "a") as f:
                            f.write("x")

        _export_lock = threading.Lock()
        """, rel="paddle_tpu/profiler/monitor.py")
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = [f for f in BlockingUnderLockPass().run(ctx)
              if f.rule == "file-io-under-lock"]
        # the file's _export_lock IS the allowed identity, but the
        # engine's condition lock is held too -> unsuppressed
        assert fs and not any(f.suppressed for f in fs), \
            [f.render() for f in fs]
        assert any("_cv" in f.message for f in fs)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_blocking_pass_str_join_not_flagged():
    ctx, d = _ctx_from_source("""
        import threading
        _lock = threading.Lock()

        def render(parts, sep):
            with _lock:
                a = ", ".join(parts)
                b = sep.join(parts)
                import os
                c = os.path.join("a", "b")
            return a, b, c
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = [f for f in BlockingUnderLockPass().run(ctx)
              if not f.suppressed]
        assert fs == [], [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_lock_discipline_is_green():
    """The same engine shape with the lock held on both sides: green —
    the pass flags missing locks, not threads."""
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._stats = {}
                self._thread = threading.Thread(target=self._loop)
                self._thread.start()

            def _loop(self):
                with self._lock:
                    self._stats["n"] = self._stats.get("n", 0) + 1

            def report(self):
                with self._lock:
                    return dict(self._stats)
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        fs = UnlockedSharedStatePass().run(ctx)
        assert fs == [], [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_stop_flag_exempt():
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self._stop = False
                self._thread = threading.Thread(target=self._loop)
                self._thread.start()

            def _loop(self):
                while not self._stop:
                    pass

            def shutdown(self):
                self._stop = True
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        fs = UnlockedSharedStatePass().run(ctx)
        assert fs == [], [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_use_after_donate_multiline_call_args_not_flagged():
    """A donating call wrapped across lines reads its own arguments
    BEFORE the donation takes effect — reformatting the correct idiom
    must not go red (the taint anchors at the call's END line)."""
    ctx, d = _ctx_from_source("""
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update(pool, x):
            return pool + x

        def wrapped(pool, x):
            out = update(
                pool,
                x)
            return out

        def still_bad(pool, x):
            out = update(
                pool,
                x)
            return out + pool
        """)
    try:
        from lint.use_after_donate import UseAfterDonatePass
        fs = UseAfterDonatePass().run(ctx)
        assert len(fs) == 1, [f.render() for f in fs]
        assert fs[0].line > 0 and "still_bad" not in fs[0].message
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_use_after_donate_rebind_is_clean():
    ctx, d = _ctx_from_source("""
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update(pool, x):
            return pool + x

        def good(pool, x):
            pool = update(pool, x)
            return pool * 2

        def bad(pool, x):
            out = update(pool, x)
            return out + pool
        """)
    try:
        from lint.use_after_donate import UseAfterDonatePass
        fs = UseAfterDonatePass().run(ctx)
        assert len(fs) == 1 and fs[0].rule == "use-after-donate"
        assert "pool" in fs[0].message
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_use_after_donate_annotated_rebind_is_clean():
    """`pool: Pool = step(pool, x)` is the same correct idiom as the
    unannotated spelling — ast.AnnAssign must clear the taint (and an
    annotated jit binding must register as a donating callable)."""
    ctx, d = _ctx_from_source("""
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update(pool, x):
            return pool + x

        step = jax.jit(lambda p, x: p + x, donate_argnums=(0,))

        def good_annotated(pool, x):
            pool: object = update(pool, x)
            return pool * 2

        def annotated_binding(pool, x):
            fn: object = jax.jit(lambda p, y: p, donate_argnums=(0,))
            fn(pool, x)
            return pool.sum()

        def bad(pool, x):
            out = update(pool, x)
            return out + pool
        """)
    try:
        from lint.use_after_donate import UseAfterDonatePass
        fs = UseAfterDonatePass().run(ctx)
        msgs = [f.render() for f in fs]
        assert len(fs) == 2, msgs
        assert not any("good_annotated" in m for m in msgs)
        # the annotated local jit binding still registers: its
        # un-rebound use IS a finding
        assert any("fn()" in f.message for f in fs), msgs
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_annotated_write_flagged():
    """`self._count: int = ...` in a thread context is the same
    unlocked write as the unannotated spelling — ast.AnnAssign must
    not be invisible to the pass."""
    ctx, d = _ctx_from_source("""
        import threading

        class Eng:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                threading.Thread(target=self._loop).start()

            def _loop(self):
                while True:
                    self._count: int = self._count + 1

            def report(self):
                return self._count
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        fs = UnlockedSharedStatePass().run(ctx)
        assert any(f.rule == "unlocked-shared-write" and
                   "_count" in f.message for f in fs), \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_reports_every_write_site():
    """One finding PER distinct unprotected write site: a line-scoped
    suppression on one site must not grant the whole attribute
    immunity — the second, unjustified mutation still goes red."""
    ctx, d = _ctx_from_source("""
        import threading

        class Eng:
            def __init__(self):
                self._lock = threading.Lock()
                self._stats = {}
                threading.Thread(target=self._loop).start()
                threading.Thread(target=self._gc).start()

            def _loop(self):
                self._stats["n"] = 1  # lint-ok[unlocked-shared-state]: justified here

            def _gc(self):
                self._stats.clear()

            def report(self):
                return dict(self._stats)
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        from lint.core import apply_suppressions
        fs = apply_suppressions(ctx, UnlockedSharedStatePass().run(ctx))
        stats = [f for f in fs if "_stats" in f.message]
        assert len(stats) == 2, [f.render() for f in fs]
        unsup = [f for f in stats if not f.suppressed]
        assert len(unsup) == 1 and "_gc" in unsup[0].message, \
            [f.render() for f in stats]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_thread_entry_never_locked_context():
    """A lock-held intra-file call site of a thread-entry method must
    NOT exempt it: the Thread start is a lock-free call site the scan
    cannot see."""
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._stats = {}
                self._thread = threading.Thread(target=self._run)
                self._thread.start()

            def _run(self):
                self._stats["n"] = 1

            def kick(self):
                with self._lock:
                    self._run()

            def report(self):
                return dict(self._stats)
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        fs = UnlockedSharedStatePass().run(ctx)
        assert any(f.rule == "unlocked-shared-write" for f in fs), \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_use_after_donate_exclusive_branches_not_flagged():
    """A donate in one arm of an if cannot reach a read in the other
    arm; sibling ifs (both can run) still propagate."""
    ctx, d = _ctx_from_source("""
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update(pool, x):
            return pool + x

        def exclusive_ok(pool, x, cond):
            if cond:
                return update(pool, x)
            else:
                return pool * 2

        def sibling_bad(pool, x, cond):
            if cond:
                out = update(pool, x)
            if x is not None:
                return pool + 1
            return out
        """)
    try:
        from lint.use_after_donate import UseAfterDonatePass
        fs = UseAfterDonatePass().run(ctx)
        assert len(fs) == 1, [f.render() for f in fs]
        assert fs[0].line > 0
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unbounded_acquire_blocking_true_flagged():
    """acquire(True) / acquire(blocking=True) ARE the unbounded form;
    timeout=, blocking=False and (blocking, timeout) are bounded."""
    ctx, d = _ctx_from_source("""
        import threading
        _l = threading.Lock()

        def a():
            _l.acquire(blocking=True)   # unbounded, spelled out

        def b():
            _l.acquire(True)            # unbounded, spelled out

        def c():
            _l.acquire(timeout=1.0)     # bounded

        def e():
            _l.acquire(blocking=False)  # non-blocking probe

        def f():
            _l.acquire(True, 5)         # bounded (timeout slot)

        def g():
            _l.acquire(1)               # truthy int: unbounded too

        def h():
            _l.acquire(blocking=True, timeout=2.0)  # bounded: timeout
            _l.acquire(timeout=-1)      # -1 = wait forever: unbounded
            _l.acquire(True, -1.0)      # same, positional slot
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = [f for f in BlockingUnderLockPass().run(ctx)
              if f.rule == "unbounded-acquire"]
        assert sorted(f.line for f in fs) == [6, 9, 21, 25, 26], \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_lock_param_does_not_resolve_to_class_field():
    """A parameter that merely shares a class lock field's name must
    not resolve to it — else clean code reports a fake self-cycle."""
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self.lock = threading.Lock()

            def helper(self, lock):
                with lock:
                    with self.lock:
                        return 1
        """)
    try:
        from lint.lock_order import LockOrderPass
        fs = LockOrderPass().run(ctx)
        assert fs == [], [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_scoped_lint_ok_hot_sync_honored_by_both_gates():
    """`# lint-ok[hot-sync]: <why>` must silence the pass AND the
    legacy check_source — the two tier-1 gates may never disagree on
    a line. An unscoped lint-ok silences neither."""
    from lint.hot_sync import check_source
    marked = "\n".join([
        "class TrainStep:",
        "    def __call__(self, *batch):",
        "        loss = self._jitted(*batch)",
        "        return loss.item()  # lint-ok[hot-sync]: test reason",
    ])
    assert check_source(marked, ["TrainStep.__call__"], "x.py") == []
    unscoped = marked.replace("lint-ok[hot-sync]: test reason",
                              "lint-ok: generic")
    assert check_source(unscoped, ["TrainStep.__call__"], "x.py")
    # and the framework side: the unscoped marker does not suppress
    # a hot-sync finding
    ctx, d = _ctx_from_source(unscoped,
                              rel="paddle_tpu/jit/api.py")
    try:
        from lint.hot_sync import HotSyncPass
        fs = core.apply_suppressions(ctx, HotSyncPass().run(ctx))
        assert any(f.rule == "sync-in-hot-region" and not f.suppressed
                   for f in fs)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_unlocked_shared_state_disjoint_locks_still_race():
    """Writer under lock A, reader under lock B: the same race as no
    lock at all — identity matters, not the mere presence of a lock."""
    ctx, d = _ctx_from_source("""
        import threading

        class Engine:
            def __init__(self):
                self._stats_lock = threading.Lock()
                self._export_lock = threading.Lock()
                self._stats = {}
                self._thread = threading.Thread(target=self._loop)
                self._thread.start()

            def _loop(self):
                with self._stats_lock:
                    self._stats["n"] = 1

            def report(self):
                with self._export_lock:
                    return dict(self._stats)
        """)
    try:
        from lint.unlocked_shared_state import UnlockedSharedStatePass
        fs = UnlockedSharedStatePass().run(ctx)
        assert any(f.rule == "unlocked-shared-write" and
                   "DIFFERENT locks" in f.message for f in fs), \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


# --------------------------------------------------------- suppression

def test_suppression_scoped_marker_suppresses_and_reaches_ledger():
    ctx, d = _ctx_from_source("""
        import threading
        _lock = threading.Lock()

        def export(path):
            with _lock:
                with open(path, "a") as f:  # lint-ok[blocking-under-lock]: bounded 1-line append, callers tolerate the stall
                    f.write("x")
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = core.apply_suppressions(ctx, BlockingUnderLockPass().run(ctx))
        hits = [f for f in fs if f.rule == "file-io-under-lock"]
        assert hits and all(f.suppressed for f in hits)
        assert "bounded 1-line append" in hits[0].reason
        rec = hits[0].record()
        assert rec["kind"] == "lint" and rec["suppressed"] is True
        assert rec["reason"]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_suppression_wrong_scope_does_not_suppress():
    ctx, d = _ctx_from_source("""
        import threading
        _lock = threading.Lock()

        def export(path):
            with _lock:
                with open(path, "a") as f:  # lint-ok[hot-sync]: wrong pass scope
                    f.write("x")
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = core.apply_suppressions(ctx, BlockingUnderLockPass().run(ctx))
        hits = [f for f in fs if f.rule == "file-io-under-lock"]
        assert hits and not any(f.suppressed for f in hits)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_suppression_requires_reason():
    """A reasonless lint-ok (or hot-sync-ok) marker is itself a
    finding — never an exemption."""
    ctx, d = _ctx_from_source("""
        import threading
        _lock = threading.Lock()

        def export(path):
            with _lock:
                with open(path, "a") as f:  # lint-ok:
                    f.write("x")
        """)
    try:
        from lint.blocking_under_lock import BlockingUnderLockPass
        fs = core.apply_suppressions(ctx, BlockingUnderLockPass().run(ctx))
        assert any(f.rule == "file-io-under-lock" and not f.suppressed
                   for f in fs)
        assert any(f.rule == "suppression-needs-reason" for f in fs)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_reasonless_hot_sync_ok_marker_is_flagged():
    ctx, d = _ctx_from_source("""
        def f(x):
            return x  # hot-sync-ok:
        """)
    try:
        fs = core.apply_suppressions(ctx, [])
        assert any(f.rule == "suppression-needs-reason" for f in fs)
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------- ratchet

def test_baseline_ratchet_refuses_to_loosen(tmp_path):
    from lint.core import (check_baseline, load_baseline,
                           update_baseline)
    path = str(tmp_path / "LINT_BASELINE.json")
    with open(path, "w") as f:
        json.dump({"schema": core.BASELINE_SCHEMA,
                   "passes": {"hot-sync": {"suppressed": 2}}}, f)
    bl = load_baseline(path)
    # growth fails
    errs = check_baseline(bl, {"hot-sync": 3}, ["hot-sync"])
    assert errs and "exceeds the baseline" in errs[0]
    # --update refuses to raise and leaves the file untouched
    wrote, refused = update_baseline(path, load_baseline(path),
                                     {"hot-sync": 3}, ["hot-sync"])
    assert refused == ["hot-sync"] and not wrote
    assert load_baseline(path)["passes"]["hot-sync"]["suppressed"] == 2
    # shrink ratchets down
    wrote, refused = update_baseline(path, load_baseline(path),
                                     {"hot-sync": 1}, ["hot-sync"])
    assert wrote and not refused
    assert load_baseline(path)["passes"]["hot-sync"]["suppressed"] == 1
    # equal count is clean
    assert check_baseline(load_baseline(path), {"hot-sync": 1},
                          ["hot-sync"]) == []
    # --update never CREATES a missing entry (hand edit, in the diff)
    wrote, refused = update_baseline(path, load_baseline(path),
                                     {"lock-order": 0}, ["lock-order"])
    assert refused == ["lock-order"] and not wrote
    assert "lock-order" not in load_baseline(path)["passes"]


def test_corrupt_baseline_fails_closed(tmp_path):
    """A PRESENT but unreadable baseline must exit 1, not silently
    disable the ratchet."""
    root = tmp_path / "mini"
    (root / "paddle_tpu").mkdir(parents=True)
    (root / "tools" / "lint").mkdir(parents=True)
    (root / "paddle_tpu" / "__init__.py").write_text("x = 1\n")
    (root / "LINT_BASELINE.json").write_text("{broken")
    assert paddlelint.main([str(root)]) == 1


def test_unparseable_hot_file_gets_its_own_rule():
    """A syntax error in a fenced file is a parse failure, not a
    renamed region — the ledger must not send triage to HOT_REGIONS."""
    d = tempfile.mkdtemp(prefix="lint_test_")
    try:
        rel = "paddle_tpu/inference/serving.py"  # a fenced path
        path = os.path.join(d, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write("def broken(:\n")
        ctx = core.ProjectContext(d, [rel])
        from lint.hot_sync import HotSyncPass
        fs = [f for f in HotSyncPass().run(ctx) if f.file == rel]
        assert any(f.rule == "hot-file-unparseable" for f in fs), \
            [f.render() for f in fs]
        assert not any(f.rule == "hot-region-missing" for f in fs), \
            [f.render() for f in fs]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_missing_explicit_baseline_fails_closed(tmp_path):
    """An explicitly requested --baseline that does not exist must
    exit 1 — a typo'd CI flag must not silently disable the ratchet.
    A missing DEFAULT baseline (fixture-corpus roots) stays fine."""
    root = tmp_path / "mini"
    (root / "paddle_tpu").mkdir(parents=True)
    (root / "tools" / "lint").mkdir(parents=True)
    (root / "paddle_tpu" / "__init__.py").write_text("x = 1\n")
    missing = str(tmp_path / "no_such_baseline.json")
    assert paddlelint.main([str(root), "--baseline", missing]) == 1
    # no baseline anywhere, none requested: clean run, no ratchet
    # (hot-sync excluded: the mini root legitimately lacks hot files)
    assert paddlelint.main([str(root), "--select", "lock-order"]) == 0


def test_repo_baseline_matches_head_counts(head_findings):
    """LINT_BASELINE.json is in sync: every pass entry present and the
    gate (main with the real baseline) green."""
    bl = core.load_baseline(os.path.join(REPO, "LINT_BASELINE.json"))
    assert bl is not None and bl.get("schema") == core.BASELINE_SCHEMA
    for name in PASS_NAMES:
        assert name in bl["passes"], name
    counts = core.suppressed_counts(head_findings)
    for name in PASS_NAMES:
        assert counts.get(name, 0) <= \
            bl["passes"][name]["suppressed"], name


def test_cli_ratchet_failure_exit_code(tmp_path):
    """A baseline tighter than reality fails the CLI with exit 1."""
    bl_path = str(tmp_path / "bl.json")
    with open(bl_path, "w") as f:
        json.dump({"schema": core.BASELINE_SCHEMA,
                   "passes": {name: {"suppressed": 0}
                              for name in PASS_NAMES}}, f)
    # hot-sync has real suppressions at HEAD -> ratchet error
    rc = paddlelint.main([REPO, "--baseline", bl_path])
    assert rc == 1


# ------------------------------------------------------- hot-sync shim

def test_shim_cli_behavior_unchanged():
    tool = _load_tool("check_no_hot_sync")
    # the legacy public surface survives
    for attr in ("HOT_REGIONS", "PATTERNS", "ALLOW_MARKER",
                 "check_source", "check_repo", "main"):
        assert hasattr(tool, attr), attr
    assert tool.main([REPO]) == 0
    # identical verdict strings on a planted violation
    src = "\n".join([
        "class TrainStep:",
        "    def __call__(self, *batch):",
        "        loss = self._jitted(*batch)",
        "        return " + "float(loss.item())",
    ])
    errs = tool.check_source(src, ["TrainStep.__call__"], "x.py")
    assert len(errs) == 2
    assert all(e.startswith("x.py:4: ") for e in errs)
    # region-gone is a violation naming the legacy table location
    assert tool.check_source(src, ["TrainStep.gone"], "x.py")


def test_shim_subprocess_stdout_and_exit():
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "check_no_hot_sync.py"),
         REPO], capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.strip() == \
        f"OK: {len(_load_tool('check_no_hot_sync').HOT_REGIONS)} " \
        "hot file(s) clean"


def test_shim_and_pass_agree_on_repo():
    tool = _load_tool("check_no_hot_sync")
    assert tool.check_repo(REPO) == []
    findings, _ = paddlelint.run_passes(select=["hot-sync"])
    assert [f for f in findings if not f.suppressed] == []


# ------------------------------------------------------- lint schema

def test_lint_schema_valid_and_violations():
    cms = _load_tool("check_metrics_schema")
    base = {"ts": 1.0, "rank": 0, "kind": "lint",
            "pass": "lock-order", "rule": "lock-cycle",
            "file": "paddle_tpu/x.py", "line": 12,
            "severity": "error", "message": "cycle a->b->a",
            "suppressed": False}
    assert cms.validate_line(json.dumps(base)) == []
    sup = dict(base, suppressed=True, reason="proven single-threaded")
    assert cms.validate_line(json.dumps(sup)) == []
    # suppressed without reason
    bad = dict(base, suppressed=True)
    assert cms.validate_line(json.dumps(bad))
    bad = dict(base, suppressed=True, reason="  ")
    assert cms.validate_line(json.dumps(bad))
    # unknown pass name
    bad = dict(base)
    bad["pass"] = "made-up"
    assert cms.validate_line(json.dumps(bad))
    # bad severity / negative line / empty file / missing keys
    assert cms.validate_line(json.dumps(dict(base, severity="meh")))
    assert cms.validate_line(json.dumps(dict(base, line=-1)))
    assert cms.validate_line(json.dumps(dict(base, file="")))
    gone = dict(base)
    del gone["rule"]
    assert cms.validate_line(json.dumps(gone))


def test_schema_pass_set_matches_framework():
    cms = _load_tool("check_metrics_schema")
    assert cms.LINT_PASSES == set(KNOWN_PASS_NAMES)


def test_findings_jsonl_roundtrip_validates(tmp_path, head_findings):
    cms = _load_tool("check_metrics_schema")
    out = str(tmp_path / "lint.jsonl")
    assert head_findings, "HEAD carries suppressed findings (hot-sync)"
    paddlelint.write_jsonl(out, head_findings)
    assert cms.validate_file(out) == []


# ---------------------------------------------------------- obs_report

def test_obs_report_renders_lint_section(tmp_path):
    obs = _load_tool("obs_report")
    recs = [
        {"ts": 1.0, "rank": 0, "kind": "lint", "pass": "hot-sync",
         "rule": "sync-in-hot-region", "file": "a.py", "line": 3,
         "severity": "error", "message": "device_get in decode loop",
         "suppressed": True, "reason": "the one deliberate sync"},
        {"ts": 1.0, "rank": 0, "kind": "lint", "pass": "lock-order",
         "rule": "lock-cycle", "file": "b.py", "line": 9,
         "severity": "error", "message": "cycle a->b->a",
         "suppressed": False},
    ]
    text = obs.render(recs)
    assert "== lint ==" in text
    assert "1 finding(s), 1 suppressed" in text
    assert "lock-order/lock-cycle" in text and "b.py:9" in text
    assert "hot-sync=1" in text
    # no lint records -> no section
    assert "== lint ==" not in obs.render(
        [{"ts": 1.0, "rank": 0, "kind": "event", "event": "x"}])


# ------------------------------------------------------------- driver

def test_driver_list_and_unknown_pass():
    assert paddlelint.main(["--list"]) == 0
    assert paddlelint.main([REPO, "--select", "nope"]) == 2


def test_driver_writes_env_metrics_file(tmp_path, monkeypatch):
    cms = _load_tool("check_metrics_schema")
    out = str(tmp_path / "m.jsonl")
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", out)
    assert paddlelint.main([REPO, "--select", "hot-sync"]) == 0
    assert os.path.exists(out)
    recs = [json.loads(x) for x in open(out) if x.strip()]
    assert recs and all(r["kind"] == "lint" for r in recs)
    assert cms.validate_file(out) == []
