"""hot-sync pass: no host synchronization in the designated hot-loop
regions — `tools/check_no_hot_sync.py` migrated into the paddlelint
framework.

The async step pipeline (device prefetch ring, deferred loss handles,
scanned accumulation — docs/PERFORMANCE.md "Hiding the host") and the
serving scheduler only work while the steady-state loops never block
the host on the device. This pass is the regression fence: it fails
when a blocking read — `.item()`, `float(`, `.numpy()`,
`block_until_ready`, `np.asarray(`, `device_get(` — appears inside a
designated hot region.

The region table, patterns, allowlist marker (`# hot-sync-ok: <why>`)
and `check_source`/`check_repo` semantics are EXACTLY the historical
tool's — tools/check_no_hot_sync.py is now a thin shim over this
module, and its CLI stdout/exit behavior is unchanged (proven by the
pre-existing tests/test_async_pipeline.py lint tests running
untouched). The region table is documented in
docs/STATIC_ANALYSIS.md "Hot regions".

On top of the legacy semantics, the framework adds the ledger view:
allow-marked lines that DO match a sync pattern are emitted as
SUPPRESSED findings (the marker's <why> is the reason), so the
`kind:"lint"` JSONL and the baseline ratchet account for every
deliberate sync; a reasonless marker is flagged by the shared
suppression engine (core.apply_suppressions).
"""
import ast
import os
import re

from .core import Finding, HOT_SYNC_OK_RE, string_mask

PASS_NAME = "hot-sync"

HOT_REGIONS = {
    "paddle_tpu/jit/api.py": [
        "TrainStep.__call__", "TrainStep._prep", "TrainStep._dispatch",
        "TrainStep.accumulate", "TrainStep.run_steps",
        # the checkpoint snapshot hook: on-device buffer copies only —
        # the blocking device read belongs to the background writer
        # (distributed/checkpoint.py _write_one), never the step loop
        "CheckpointSnapshotMixin.tree_state",
        "CheckpointSnapshotMixin.snapshot_state"],
    "paddle_tpu/hapi/model.py": [
        "Model.fit", "Model._fit_epochs", "Model._dispatch_micro"],
    "paddle_tpu/distributed/fleet/hybrid_train.py": [
        "HybridTrainStep.__call__", "HybridTrainStep._prep",
        "HybridTrainStep._dispatch"],
    # the async checkpoint enqueue path: save() snapshots on device and
    # hands off to the writer thread — any host<->device sync here
    # would put checkpointing back on the step loop's critical path.
    # (_write_one / the writer loop are deliberately NOT fenced: the
    # writer thread's whole job is the blocking device_get + file IO.)
    "paddle_tpu/distributed/checkpoint.py": [
        "CheckpointManager.save", "CheckpointManager._snapshot",
        "CheckpointManager.busy", "AsyncSaveHandle.done"],
    "paddle_tpu/distributed/elastic.py": [
        "ElasticController.on_step"],
    # fault sites fire inside train-step dispatch: pure host dict math
    "paddle_tpu/framework/fault_injection.py": ["fire", "active"],
    "paddle_tpu/io/device_prefetch.py": ["*"],
    # the serving engine's scheduler core: the only legitimate blocks
    # are the queue wait and the ONE device read per dispatched batch /
    # decode step (marked hot-sync-ok at the result-slicing sync
    # points). Sampling is an on-device argmax collected via an async
    # copy: the prefill path (_admit) and the whole ragged loop carry
    # NO allowlist entry — int()/device_get of b int32s with the copy
    # already in flight, never a [vocab]-sized np.asarray
    "paddle_tpu/inference/serving.py": [
        "_run_scheduler",
        "InferenceEngine._take_batch", "InferenceEngine._scan_matching",
        "InferenceEngine._loop_once", "InferenceEngine._dispatch_batch",
        "InferenceEngine._resolve_batch", "InferenceEngine._fail_batch",
        "InferenceEngine._flush_expired", "InferenceEngine.load_report",
        "GenerationEngine._loop_once", "GenerationEngine._admit",
        "GenerationEngine._decode_step", "GenerationEngine._emit",
        "GenerationEngine._admit_ragged",
        "GenerationEngine._ragged_step",
        "GenerationEngine._pop_doomed_head",
        "GenerationEngine._close_doomed",
        "GenerationEngine._note_kv_step", "GenerationEngine.load_report",
        # the disaggregation paths run on the scheduler threads too:
        # the handoff epilogue, chain adoption, and the cross-engine
        # adopt entry are all host dict/list math — the chain moves
        # page IDS, never page contents
        "GenerationEngine._handoff_seq",
        "GenerationEngine._drain_adopted", "GenerationEngine.adopt",
        # speculative decoding runs entirely on the scheduler thread:
        # draft proposal steps sync k times per iteration (int32s per
        # ready row, marked hot-sync-ok — each feeds the next step's
        # input tokens), the verify verdict reads the per-token lane
        # once, and the rollback/free plumbing is pure host ledger math
        "GenerationEngine._spec_propose",
        "GenerationEngine._spec_rows",
        "GenerationEngine._hist_slice",
        "GenerationEngine._free_draft",
        "GenerationEngine._free_draft_sid",
        "GenerationEngine._release_chain_pair"],
    # speculative decoding config + the acceptance rule: pure host
    # token comparison (the equality contract), no device reads ever
    "paddle_tpu/inference/speculative.py": ["*"],
    # the serving front door: routing decisions and the handoff
    # dispatcher run on caller/scheduler threads against load_report
    # snapshots — pure host scoring, never a device read
    "paddle_tpu/inference/frontdoor.py": ["*"],
    # the serving observatory: request traces mutate on the scheduler
    # hot loop and kvcache snapshots run per step — the whole module
    # must stay pure host arithmetic (no device reads, ever)
    "paddle_tpu/profiler/serve_observatory.py": ["*"],
    # the distributed observatory: collective rollups fold on every
    # collective call and the rankstat cadence check runs per step —
    # the whole module must stay pure host arithmetic (the device-time
    # probe's two deliberate syncs live in jit/api.py, fenced +
    # allowlisted there, NOT here)
    "paddle_tpu/profiler/dist_observatory.py": ["*"],
    # the fleet observatory: journeys complete on the decode
    # scheduler's emit path and fleet snapshots run on submit — the
    # whole module must stay pure host arithmetic (no device reads)
    "paddle_tpu/profiler/fleet_observatory.py": ["*"],
    # the memory observatory: the tag ledger is read on the train-step
    # and decode-scheduler cadences and the OOM forensics run inside
    # dispatch except-blocks — the whole module must stay pure host
    # arithmetic (array .nbytes is metadata, memory_stats() is an
    # allocator query; neither blocks on the device)
    "paddle_tpu/profiler/mem_observatory.py": ["*"],
    # eager collectives are host-visible waits by design, but the
    # instrumentation AROUND them must never add a sync of its own
    "paddle_tpu/distributed/collective.py": [
        "_instrumented", "_payload_bytes", "_any_traced",
        "_group_label"],
    # the pool snapshot is called from the decode loop: dict/len math
    # only, never a device read of the page pools
    "paddle_tpu/ops/paged_attention.py": ["PagedKVCache.pool_stats"],
}

PATTERNS = [
    (re.compile(r"\.item\s*\("), ".item()"),
    (re.compile(r"(?<![\w.])float\s*\("), "float()"),
    (re.compile(r"\.numpy\s*\("), ".numpy()"),
    (re.compile(r"block_until_ready"), "block_until_ready"),
    # np.asarray of a device array is a blocking D2H read — the serving
    # dispatcher idiom (jnp.asarray stays device-side and is NOT matched)
    (re.compile(r"(?<![\w.])np\.asarray\s*\("), "np.asarray()"),
    # jax.device_get is the other blocking D2H idiom (the ragged decode
    # loop's one deliberate sync is marked; anything else is a leak)
    (re.compile(r"device_get\s*\("), "device_get()"),
]

ALLOW_MARKER = "hot-sync-ok"
# the framework grammar's EXPLICITLY-SCOPED spelling of the same
# allowance — both gates (this pass and the shim CLI) honor it, so
# paddlelint and check_no_hot_sync can never disagree on a line. The
# UNSCOPED `# lint-ok:` deliberately does NOT reach the hot-sync
# fence (core.apply_suppressions enforces the same), so a generic
# suppression can't silently blank a sync check.
SCOPED_ALLOW_MARKER = "lint-ok[hot-sync]"


def _named_spans(tree):
    """{qualified name: (first line, last line)} for module-level
    functions and class methods."""
    spans = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            spans[node.name] = (node.lineno, node.end_lineno)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    spans[f"{node.name}.{sub.name}"] = (sub.lineno,
                                                        sub.end_lineno)
    return spans


# the docstring-line mask (multi-line string constants are not code,
# not linted) — one copy, shared with core.SourceFile.string_lines
_string_lines = string_mask


def check_source(src, names, where, tree=None, skip=None):
    """All violations for one file's source text. `names` is the list of
    hot region names ("*" = whole module). Byte-compatible with the
    historical tools/check_no_hot_sync.py check_source; the framework
    pass forwards its already-parsed `tree`/`skip` so a paddlelint run
    does not re-parse the hot files."""
    violations = []
    if tree is None:
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            return [f"{where}: unparseable ({e})"]
    lines = src.splitlines()
    if skip is None:
        skip = _string_lines(tree)
    if "*" in names:
        regions = [("<module>", 1, len(lines))]
    else:
        spans = _named_spans(tree)
        regions = []
        for name in names:
            if name not in spans:
                violations.append(
                    f"{where}: hot region {name!r} not found — update "
                    "tools/check_no_hot_sync.py HOT_REGIONS")
                continue
            regions.append((name, *spans[name]))
    for name, start, end in regions:
        for ln in range(start, min(end, len(lines)) + 1):
            if ln in skip:
                continue
            line = lines[ln - 1]
            if ALLOW_MARKER in line or SCOPED_ALLOW_MARKER in line:
                continue
            code = line.split("#", 1)[0]
            for pat, label in PATTERNS:
                if pat.search(code):
                    violations.append(
                        f"{where}:{ln}: {label} in hot region {name}: "
                        f"{line.strip()}")
    return violations


def check_repo(repo):
    errors = []
    for rel, names in sorted(HOT_REGIONS.items()):
        path = os.path.join(repo, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: hot file missing")
            continue
        with open(path) as f:
            errors.extend(check_source(f.read(), names, rel))
    return errors


# -- the framework pass --------------------------------------------------

_VIOLATION_RE = re.compile(r"^(?P<file>[^:]+):(?P<line>\d+): "
                           r"(?P<label>\S+) in hot region "
                           r"(?P<region>\S+): ")


class HotSyncPass:
    """Framework wrapper: the legacy checker's verdicts as Findings,
    plus suppressed findings for every allow-marked line that actually
    matches a sync pattern (the ledger's account of deliberate syncs)."""

    name = PASS_NAME

    def run(self, ctx):
        findings = []
        by_rel = {sf.rel: sf for sf in ctx.files}
        for rel, names in sorted(HOT_REGIONS.items()):
            sf = by_rel.get(rel)
            if sf is None:
                if ctx.root is not None and os.path.exists(
                        os.path.join(ctx.root, rel)):
                    # analyzed set narrower than the region table
                    # (pass-selection run): fall back to disk
                    with open(os.path.join(ctx.root, rel)) as f:
                        src = f.read()
                    try:
                        tree = ast.parse(src)
                    except SyntaxError:
                        tree = None
                    lines, skip = src.splitlines(), \
                        _string_lines(tree) if tree else set()
                else:
                    findings.append(Finding(
                        self.name, "hot-file-missing", rel, 0,
                        "hot file missing — renaming a fenced file "
                        "must move the fence "
                        "(tools/lint/hot_sync.py HOT_REGIONS)"))
                    continue
            else:
                # reuse the ProjectContext's parse (forwarded into
                # check_source below) — no second ast.parse per file
                src, tree = sf.text, sf.tree
                lines, skip = sf.lines, sf.string_lines()
            if tree is None:  # unparseable file: its own rule — a
                # parse failure must not read as a renamed region and
                # send triage to HOT_REGIONS instead of the broken file
                findings.append(Finding(
                    self.name, "hot-file-unparseable", rel, 0,
                    f"unparseable ({sf.parse_error if sf else '?'})"))
                continue
            for v in check_source(src, names, rel, tree=tree,
                                  skip=skip):
                # a real sync verdict matches the `file:line: <label>
                # in hot region` shape; region-gone/unparseable
                # verdicts have no line prefix (classifying on the
                # SHAPE, not the message text — a hot line that
                # happens to contain "not found" stays a sync finding)
                m = _VIOLATION_RE.match(v)
                if m:
                    line, rule = int(m.group("line")), \
                        "sync-in-hot-region"
                elif v.split(": ", 1)[-1].startswith("unparseable ("):
                    line, rule = 0, "hot-file-unparseable"
                else:
                    line, rule = 0, "hot-region-missing"
                msg = v.split(": ", 1)[-1]
                if rule == "hot-region-missing":
                    # check_source's verdict string stays byte-
                    # identical for the shim CLI; the framework
                    # finding points at where the table lives NOW
                    msg = msg.replace("tools/check_no_hot_sync.py",
                                      "tools/lint/hot_sync.py")
                findings.append(Finding(self.name, rule, rel, line,
                                        msg))
            if tree is not None:
                findings.extend(self._allowed_syncs(
                    rel, lines, tree, skip, names))
        return findings

    def _allowed_syncs(self, rel, lines, tree, skip, names):
        """Suppressed findings for allow-marked lines matching a sync
        pattern inside a hot region — every deliberate sync is in the
        ledger with its hot-sync-ok reason."""
        out = []
        if "*" in names:
            regions = [(1, len(lines))]
        else:
            spans = _named_spans(tree)
            regions = [spans[n] for n in names if n in spans]
        from .core import LINT_OK_RE
        seen = set()
        for start, end in regions:
            for ln in range(start, min(end, len(lines)) + 1):
                if ln in skip or ln in seen:
                    continue
                line = lines[ln - 1]
                if ALLOW_MARKER in line:
                    m = HOT_SYNC_OK_RE.search(line)
                elif SCOPED_ALLOW_MARKER in line:
                    m = LINT_OK_RE.search(line)
                else:
                    continue
                reason = m.group("reason").strip() if m else ""
                code = line.split("#", 1)[0]
                for pat, label in PATTERNS:
                    if pat.search(code):
                        seen.add(ln)
                        out.append(Finding(
                            self.name, "sync-in-hot-region", rel, ln,
                            f"{label} in hot region (allow-marked): "
                            f"{line.strip()[:120]}",
                            suppressed=bool(reason),
                            reason=reason or None))
                        break
        return out
