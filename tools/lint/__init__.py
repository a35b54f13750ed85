"""paddlelint — the repo's concurrency + tracing-safety static
analyzer (driver: tools/paddlelint.py, docs: docs/STATIC_ANALYSIS.md).

Five passes over `paddle_tpu/` + `tools/`, each
mechanizing a bug class the PR 8-12 review-hardening logs kept
finding by hand:

  lock-order             static deadlock detector: cycles in the
                         cross-module lock-acquisition graph
  blocking-under-lock    file I/O / device reads / waits / JSONL
                         export while holding a lock; unbounded
                         explicit acquire()
  unlocked-shared-state  fields mutated on a background thread and
                         read elsewhere with no lock in scope
  use-after-donate       reads of a binding after its buffer was
                         donated to a dispatch
  hot-sync               host syncs inside designated hot regions
                         (tools/check_no_hot_sync.py, migrated — the
                         old CLI is a shim over lint.hot_sync)

Shared engine: tools/lint/core.py (project model, suppression
grammar, baseline ratchet). Known-bad fixture corpora:
tools/lint/fixtures/<pass>/ — each pass must go RED on its own
corpus (tests/test_static_analysis.py enforces it).
"""
from .blocking_under_lock import BlockingUnderLockPass
from .hot_sync import HotSyncPass
from .lock_order import LockOrderPass
from .unlocked_shared_state import UnlockedSharedStatePass
from .use_after_donate import UseAfterDonatePass

#: registration order is report order. blocking-under-lock runs FIRST
#: on purpose: it builds the shared function summaries WITH its effect
#: extractor, and core.build_summaries memoizes that superset for the
#: extractor-less passes behind it — one summary walk per run, not two
ALL_PASSES = (BlockingUnderLockPass, LockOrderPass,
              UnlockedSharedStatePass, UseAfterDonatePass, HotSyncPass)

PASS_NAMES = tuple(p.name for p in ALL_PASSES)

#: the known set a `kind:"lint"` record's `pass` key must come from —
#: the five passes plus the shared suppression engine's meta-pass
#: (core.apply_suppressions emits `suppression-needs-reason` under it)
KNOWN_PASS_NAMES = PASS_NAMES + ("suppression",)


def get_pass(name):
    for cls in ALL_PASSES:
        if cls.name == name:
            return cls()
    raise KeyError(f"unknown lint pass {name!r} (known: "
                   f"{', '.join(PASS_NAMES)})")
