"""paddlelint core: the shared engine every pass builds on.

The framework mechanizes the bug classes the PR 8-12 review-hardening
logs kept finding by hand (blocking file I/O inside an engine lock,
lock-order inversions across threaded modules, unlocked shared-state
snapshots, donated-buffer use-after-dispatch). One driver
(tools/paddlelint.py) runs pluggable passes over a shared project
model; this module owns everything the passes have in common:

- **ProjectContext** — the parsed fileset (one `ast` tree per file),
  the cross-module LOCK REGISTRY (`threading.Lock/RLock/Condition/
  Semaphore` assignments attributed to class fields, so `self._lock`
  in two engines stays two distinct locks), an import-alias map for
  cross-module call resolution, and per-function summaries
  (acquisition sites, call sites with the lexically-held lock set)
  that the interprocedural passes fixpoint over.
- **Suppression engine** — `# lint-ok: <why>` (any pass) and
  `# lint-ok[pass-name]: <why>` (one pass) line markers, same
  discipline as the established `# hot-sync-ok: <why>`: a marker
  WITHOUT a reason is itself a finding (`suppression-needs-reason`),
  never an exemption. Suppressed findings are still emitted
  (`suppressed: true` + the reason) so the JSONL ledger and the
  baseline ratchet see them.
- **Baseline ratchet** — LINT_BASELINE.json records the per-pass
  SUPPRESSED-finding counts. Unsuppressed findings always fail; a
  suppressed count above the baseline fails too (new suppressions
  must be loosened by hand, visibly, in the diff); `--update` only
  ever ratchets counts DOWN, like the HLO gates.

Plain stdlib only — like the other tools/ gates, the linter must run
as a milliseconds-fast source diff with no framework import.

See docs/STATIC_ANALYSIS.md for the pass catalog and how to add one.
"""
import ast
import json
import os
import re
import time

SEVERITIES = ("error", "warning")

# the lint-ok marker: `# lint-ok: why` or `# lint-ok[pass-name]: why`.
# The colon is REQUIRED: without it, `# lint-okay to revisit` or any
# comment merely containing "lint-ok" would count as a reasoned
# suppression with garbage as the recorded reason
LINT_OK_RE = re.compile(
    r"#\s*lint-ok(?:\[(?P<scope>[\w-]+)\])?\s*:\s*(?P<reason>.*)$")
# the hot-sync pass's historical marker (tools/check_no_hot_sync.py);
# the reason discipline (and the colon requirement) applies to it too
HOT_SYNC_OK_RE = re.compile(r"#\s*hot-sync-ok\s*:\s*(?P<reason>.*)$")

LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                  "BoundedSemaphore"}
REENTRANT_KINDS = {"RLock", "Condition"}  # Condition() wraps an RLock

# receiver-less method names the unique-definition call-resolution
# fallback must NEVER claim: they shadow builtin container / stdlib
# object methods, so `somedict.get(k)` or `somelist.pop()` anywhere in
# the fileset would otherwise resolve to whichever project class
# happens to define the name exactly once
_BUILTIN_METHOD_NAMES = frozenset({
    "get", "pop", "popitem", "clear", "items", "keys", "values",
    "setdefault", "update", "append", "appendleft", "popleft",
    "extend", "insert", "remove", "discard", "add", "sort", "index",
    "count", "copy", "join", "split", "strip", "read", "write",
    "open", "close", "flush", "send", "recv", "put", "start", "run",
    "wait", "result", "submit", "release", "acquire", "notify",
    "notify_all"})


class Finding:
    """One lint finding: pass + rule + file:line + message, plus the
    suppression state the baseline ratchet and the JSONL ledger see."""

    __slots__ = ("pass_name", "rule", "file", "line", "message",
                 "severity", "suppressed", "reason")

    def __init__(self, pass_name, rule, file, line, message,
                 severity="error", suppressed=False, reason=None):
        self.pass_name = pass_name
        self.rule = rule
        self.file = file
        self.line = int(line)
        self.message = message
        self.severity = severity
        self.suppressed = suppressed
        self.reason = reason

    def render(self):
        tail = f"  [suppressed: {self.reason}]" if self.suppressed else ""
        return (f"{self.file}:{self.line}: [{self.pass_name}/"
                f"{self.rule}] {self.message}{tail}")

    def record(self, rank=0):
        """The `kind:"lint"` JSONL record (schema:
        tools/check_metrics_schema.py)."""
        rec = {"ts": time.time(), "rank": rank, "kind": "lint",
               "pass": self.pass_name, "rule": self.rule,
               "file": self.file, "line": self.line,
               "severity": self.severity, "message": self.message,
               "suppressed": bool(self.suppressed)}
        if self.suppressed:
            rec["reason"] = self.reason or ""
        return rec


class SourceFile:
    """One parsed source file: text, lines, AST (None when
    unparseable), docstring line mask, and lint-ok markers by line."""

    def __init__(self, root, rel):
        self.root = root
        self.rel = rel
        self.path = os.path.join(root, rel)
        with open(self.path, encoding="utf-8", errors="replace") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.parse_error = None
        try:
            self.tree = ast.parse(self.text)
        except SyntaxError as e:
            self.tree = None
            self.parse_error = str(e)
        # line -> (scope-or-None, reason) of a lint-ok marker
        self.lint_ok = {}
        for i, line in enumerate(self.lines, 1):
            if "lint-ok" in line and "#" in line:
                m = LINT_OK_RE.search(line)
                if m:
                    self.lint_ok[i] = (m.group("scope"),
                                       m.group("reason").strip())

    def string_lines(self):
        """Lines covered by multi-line string constants (docstrings) —
        not code."""
        if self.tree is None:
            return set()
        return string_mask(self.tree)


class FunctionInfo:
    """Per-function summary the interprocedural passes share.

    acquisitions: [(lock_id, line, via_with, has_timeout,
                    held_locks_at_acquisition)]
    calls:        [(callee_key_or_None, held_lock_tuple, line, label)]
    effects:      [(rule, label, line, held_lock_tuple)] — pass-
                  specific direct effects (filled by the blocking
                  pass's extractor)
    """

    __slots__ = ("key", "file", "qualname", "class_name", "node",
                 "acquisitions", "calls", "effects")

    def __init__(self, key, file, qualname, class_name, node):
        self.key = key
        self.file = file
        self.qualname = qualname
        self.class_name = class_name
        self.node = node
        self.acquisitions = []
        self.calls = []
        self.effects = []


def string_mask(tree):
    """Line numbers covered by MULTI-LINE string constants (docstrings
    and block strings) — not code, not linted. The one copy of the
    docstring-mask rule (SourceFile.string_lines and the hot-sync
    pass both use it)."""
    lines = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str):
            end = getattr(node, "end_lineno", node.lineno)
            if end > node.lineno:
                lines.update(range(node.lineno, end + 1))
    return lines


def _acquire_is_bounded(call):
    """True when an `.acquire(...)` call is BOUNDED: a `timeout=`, a
    falsy blocking flag (the non-blocking probe), or a second
    positional (the timeout slot). The first positional/`blocking=`
    is the BLOCKING flag — any truthy constant (`True`, `1`, even a
    float someone mistook for a timeout) is the unbounded wait the
    rule exists to flag. A non-constant flag is treated as bounded
    (unknowable statically; err against false positives)."""
    def negative_const(node):
        # threading defines timeout=-1 as "wait forever": a statically
        # visible negative timeout is the unbounded wait in disguise
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, (int, float)) and \
                not isinstance(node.value, bool):
            return node.value < 0
        if isinstance(node, ast.UnaryOp) and \
                isinstance(node.op, ast.USub) and \
                isinstance(node.operand, ast.Constant):
            return True  # -<literal>
        return False

    # timeout= wins regardless of keyword ORDER: acquire(blocking=True,
    # timeout=2.0) is bounded — unless the timeout is a negative
    # constant (infinite wait)
    for k in call.keywords:
        if k.arg == "timeout":
            return not negative_const(k.value)
    for k in call.keywords:
        if k.arg == "blocking":
            v = k.value
            if isinstance(v, ast.Constant) and v.value:
                return False  # blocking=<truthy>: unbounded
            return True  # blocking=False/0, or a variable
    if len(call.args) >= 2:
        # acquire(blocking, timeout): bounded unless the timeout slot
        # is a negative constant
        return not negative_const(call.args[1])
    if len(call.args) == 1:
        a = call.args[0]
        if isinstance(a, ast.Constant) and a.value:
            return False  # acquire(True)/acquire(1): unbounded
        return True
    return False  # bare acquire()


def _last_attr(node):
    """Trailing attribute/name of a dotted expression, or None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _dotted(node):
    """Render a Name/Attribute chain as 'a.b.c', or None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ProjectContext:
    """The shared project model: fileset + lock registry + function
    index + import aliases. Built once per driver run; passes read it."""

    def __init__(self, root, rels):
        self.root = root
        self.files = []
        for rel in rels:
            try:
                self.files.append(SourceFile(root, rel))
            except OSError:
                continue
        self.locks = {}        # lock_id -> factory kind ("Lock", ...)
        self._attr_locks = set()   # lock ids that are self.<attr> fields
        self._local_locks = set()  # lock ids that are function locals
        self.functions = {}    # "rel:qualname" -> FunctionInfo
        self._module_locks = {}   # rel -> {name} module-level lock names
        self._basenames = {}      # module basename -> [rel]
        self._aliases = {}        # rel -> {alias: basename}
        self._method_defs = {}    # method name -> [function keys]
        self._class_bases = {}    # rel -> {class name: [base names]}
        # build_summaries memo: None = never built, False = built
        # without an extractor, else the extractor it was built with
        self._summaries_extractor = None
        self._build()

    # -- model construction ------------------------------------------

    def _build(self):
        for sf in self.files:
            base = os.path.splitext(os.path.basename(sf.rel))[0]
            if base == "__init__":
                base = os.path.basename(os.path.dirname(sf.rel)) or base
            self._basenames.setdefault(base, []).append(sf.rel)
        for sf in self.files:
            if sf.tree is None:
                continue
            bases = self._class_bases.setdefault(sf.rel, {})
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ClassDef):
                    bases[node.name] = [b.id for b in node.bases
                                        if isinstance(b, ast.Name)]
        for sf in self.files:
            if sf.tree is None:
                continue
            self._collect_aliases(sf)
            self._collect_locks(sf)
            self._collect_functions(sf)

    def _collect_aliases(self, sf):
        amap = self._aliases.setdefault(sf.rel, {})
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        # `import a.b.c as x`: x IS module c
                        amap[a.asname] = a.name.rsplit(".", 1)[-1]
                    else:
                        # `import a.b.c` binds only the TOP package a
                        top = a.name.split(".")[0]
                        amap[top] = top
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    amap[a.asname or a.name] = a.name

    def _lock_factory(self, call):
        """'Lock'/'RLock'/... when `call` constructs a threading
        primitive, else None."""
        if not isinstance(call, ast.Call):
            return None
        name = _last_attr(call.func)
        return name if name in LOCK_FACTORIES else None

    def _collect_locks(self, sf):
        mod_locks = self._module_locks.setdefault(sf.rel, set())

        def scope_of(stack):
            cls = next((n.name for n in reversed(stack)
                        if isinstance(n, ast.ClassDef)), None)
            fn = next((n.name for n in reversed(stack)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))), None)
            return cls, fn

        def visit(node, stack):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = node.value
                kind = self._lock_factory(value)
                pairs = []
                if kind:
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    pairs = [(t, kind) for t in targets]
                elif isinstance(node, ast.Assign) and \
                        isinstance(value, ast.Tuple) and \
                        len(node.targets) == 1 and \
                        isinstance(node.targets[0], ast.Tuple) and \
                        len(node.targets[0].elts) == len(value.elts):
                    # `lat, lat_lock, errs = [], Lock(), []`
                    pairs = [(t, self._lock_factory(v))
                             for t, v in zip(node.targets[0].elts,
                                             value.elts)
                             if self._lock_factory(v)]
                if pairs:
                    cls, fn = scope_of(stack)
                    for t, k in pairs:
                        lid = self._target_lock_id(sf.rel, t, cls, fn)
                        if lid:
                            self.locks[lid] = k
                            if isinstance(t, ast.Attribute):
                                self._attr_locks.add(lid)
                            elif fn is not None:
                                self._local_locks.add(lid)
                            elif isinstance(t, ast.Name) and not cls:
                                mod_locks.add(t.id)
            for child in ast.iter_child_nodes(node):
                new_stack = stack + [node] if isinstance(
                    node, (ast.ClassDef, ast.FunctionDef,
                           ast.AsyncFunctionDef)) else stack
                visit(child, new_stack)

        visit(sf.tree, [])

    def _class_root(self, rel, cls):
        """Canonical class for `self.<attr>` lock attribution: the
        ROOT of `cls`'s same-file single-inheritance chain. A mixin's
        `with self._cv:` and the subclass __init__ that registered
        the field are ONE lock per instance (serving.py's
        `_SchedulerLifecycle.drain` vs the engines' `_cv`) — without
        the canonical owner they would never meet. Unrelated classes
        (no same-file base) keep their own name, so two engines'
        `self._lock` stay distinct; multiple same-file bases stop the
        walk (no unambiguous root)."""
        bases = self._class_bases.get(rel, {})
        seen = {cls}
        while True:
            same_file = [b for b in bases.get(cls, ()) if b in bases]
            if len(same_file) != 1 or same_file[0] in seen:
                return cls
            cls = same_file[0]
            seen.add(cls)

    def _target_lock_id(self, rel, target, cls, fn):
        if isinstance(target, ast.Name):
            if fn is None and cls is None:
                return f"{rel}:{target.id}"
            return f"{rel}:{cls + '.' if cls else ''}" \
                   f"{fn + '.' if fn else ''}{target.id}"
        if isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self" and cls:
            return f"{rel}:{self._class_root(rel, cls)}.{target.attr}"
        return None

    def _collect_functions(self, sf):
        def visit(node, class_name, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, child.name,
                          f"{prefix}{child.name}.")
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    key = f"{sf.rel}:{qual}"
                    info = FunctionInfo(key, sf, qual, class_name,
                                        child)
                    self.functions[key] = info
                    self._method_defs.setdefault(
                        child.name, []).append(key)
                    # nested defs belong to the enclosing function's
                    # file scope; record them too (thread closures)
                    visit(child, class_name, f"{qual}.")

        visit(sf.tree, None, "")

    # -- lock identity -----------------------------------------------

    def lock_id(self, sf, expr, class_name, func_qualname):
        """The attributed identity of a lock-valued expression, or
        None when `expr` does not resolve to a known lock. `self._x`
        binds to the enclosing class, module globals to the module,
        locals to the enclosing function — two engines' `self._lock`
        stay distinct nodes in the graph."""
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self" and class_name:
            # inheritance: registration canonicalizes self-fields to
            # the class's same-file ROOT ancestor (_class_root), so a
            # mixin's `with self._cv:` and the subclass __init__ that
            # assigned it resolve to the same identity
            root = self._class_root(sf.rel, class_name)
            lid = f"{sf.rel}:{root}.{expr.attr}"
            if lid in self.locks:
                return lid
            lid = f"{sf.rel}:{class_name}.{expr.attr}"
            if lid in self.locks:
                return lid
            suffix = f".{expr.attr}"
            cands = [k for k in self._attr_locks
                     if k.startswith(f"{sf.rel}:") and
                     k.endswith(suffix)]
            return cands[0] if len(cands) == 1 else None
        if isinstance(expr, ast.Name):
            if expr.id in self._module_locks.get(sf.rel, ()):
                return f"{sf.rel}:{expr.id}"
            if func_qualname:
                lid = f"{sf.rel}:{func_qualname}.{expr.id}"
                if lid in self.locks:
                    return lid
                # nested function referring to an ENCLOSING function's
                # local lock (closure): the candidate's owner qualname
                # must be a prefix of ours — a parameter that merely
                # shares a class field's name must NOT resolve
                suffix = f".{expr.id}"
                pre = f"{sf.rel}:"
                cands = []
                for k in self._local_locks:
                    if not (k.startswith(pre) and k.endswith(suffix)):
                        continue
                    owner = k[len(pre):-len(suffix)]
                    if func_qualname == owner or \
                            func_qualname.startswith(owner + "."):
                        cands.append(k)
                if len(cands) == 1:
                    return cands[0]
            return None
        dotted = _dotted(expr)
        if dotted and "." in dotted:
            head, _, tail = dotted.partition(".")
            target = self.resolve_module(sf.rel, head)
            if target:
                lid = f"{target}:{tail}"
                if lid in self.locks:
                    return lid
        return None

    # -- call resolution ---------------------------------------------

    def resolve_module(self, rel, alias):
        """rel-path of the analyzed module an import alias points to,
        when the basename resolves uniquely; else None."""
        base = self._aliases.get(rel, {}).get(alias)
        if not base:
            return None
        cands = self._basenames.get(base, [])
        return cands[0] if len(cands) == 1 else None

    def resolve_call(self, sf, call, class_name):
        """The FunctionInfo key a call lands on, or None.

        Resolution ladder (documented in docs/STATIC_ANALYSIS.md):
        `self.m()` -> same-class method; bare `f()` -> same-module
        function; `alias.f()` -> the aliased in-tree module's
        function; `obj.m()` -> the ONE analyzed method of that name
        when the name is defined exactly once project-wide (the
        receiver's class is statically unknown; a unique definition
        makes the target unambiguous anyway)."""
        func = call.func
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name) and \
                    func.value.id == "self" and class_name:
                key = f"{sf.rel}:{class_name}.{func.attr}"
                if key in self.functions:
                    return key
            dotted = _dotted(func.value)
            if dotted and "." not in dotted:
                target = self.resolve_module(sf.rel, dotted)
                if target:
                    key = f"{target}:{func.attr}"
                    if key in self.functions:
                        return key
            # unique-definition fallback — never for dunders, and
            # never for names shadowing builtin container/stdlib
            # methods: `somedict.get(k)` must not resolve to the one
            # project class that happens to define `get`, fabricating
            # call-graph edges
            if not func.attr.startswith("__") and \
                    func.attr not in _BUILTIN_METHOD_NAMES:
                defs = self._method_defs.get(func.attr, [])
                if len(defs) == 1:
                    return defs[0]
            return None
        if isinstance(func, ast.Name):
            key = f"{sf.rel}:{func.id}"
            if key in self.functions:
                return key
        return None

    # -- per-function lock/call summaries ----------------------------

    def lock_flow(self, sf, node, class_name, qualname):
        """(acquired, released) lock-id sets from EXPLICIT
        `.acquire()` / `.release()` calls in node's subtree (nested
        defs excluded). The sequential complement of `with` tracking:
        a lock .acquire()d in one statement stays held for the REST
        of the suite until a statement .release()s it — the bounded-
        acquire diagnosis idiom (`if lock.acquire(timeout=...):
        try: ... finally: lock.release()`) must not exempt its body
        from every held-lock rule."""
        acq, rel = set(), set()
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)) and n is not node:
                continue  # nested defs run later, not in this flow
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    n.func.attr in ("acquire", "release"):
                lid = self.lock_id(sf, n.func.value, class_name,
                                   qualname)
                if lid is None:
                    recv = _last_attr(n.func.value) or ""
                    if re.search(r"(lock|_cv|cond|gate|sem)", recv,
                                 re.I):
                        lid = f"{sf.rel}:<{recv}>"
                if lid:
                    (acq if n.func.attr == "acquire" else rel).add(lid)
            stack.extend(ast.iter_child_nodes(n))
        return acq, rel

    def build_summaries(self, effect_extractor=None):
        """Fill every FunctionInfo's acquisitions/calls (+ direct
        effects via `effect_extractor(sf, node, held)` returning
        [(rule, label, line)]). Memoized: a summary built WITH an
        extractor is a superset of one built without (the extractor
        only adds `effects`), so repeat calls — the passes share one
        ProjectContext — rebuild only when an extractor arrives after
        an extractor-less build."""
        if self._summaries_extractor is not None and (
                effect_extractor is None or
                effect_extractor is self._summaries_extractor):
            return self.functions
        if self._summaries_extractor is False and \
                effect_extractor is None:
            return self.functions
        for info in self.functions.values():
            info.acquisitions = []
            info.calls = []
            info.effects = []
            self._summarize(info, effect_extractor)
        self._summaries_extractor = effect_extractor \
            if effect_extractor is not None else False
        return self.functions

    def _summarize(self, info, effect_extractor):
        sf = info.file
        # cheap gate: sequential explicit-acquire tracking rescans
        # child subtrees, so skip it for the (vast majority of) files
        # with no explicit .acquire( anywhere
        track_explicit = ".acquire(" in sf.text

        def walk(node, held):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not info.node:
                return  # nested defs summarized as their own functions
            new_held = held
            if isinstance(node, ast.With):
                # items acquire LEFT to RIGHT: `with a, b:` holds a
                # at b's acquisition — the held tuple grows per item
                for item in node.items:
                    lid = self.lock_id(sf, item.context_expr,
                                       info.class_name, info.qualname)
                    if lid:
                        info.acquisitions.append(
                            (lid, item.context_expr.lineno, True,
                             False, new_held))
                        new_held = new_held + (lid,)
            elif isinstance(node, ast.Call):
                last = _last_attr(node.func)
                if last == "acquire" and isinstance(node.func,
                                                   ast.Attribute):
                    lid = self.lock_id(sf, node.func.value,
                                       info.class_name, info.qualname)
                    has_timeout = _acquire_is_bounded(node)
                    if lid is None:
                        # unresolved receiver with a lock-shaped name
                        # (a parameter-passed lock): still subject to
                        # the unbounded-acquire rule
                        recv = _last_attr(node.func.value) or ""
                        if re.search(r"(lock|_cv|cond|gate|sem)",
                                     recv, re.I):
                            lid = f"{sf.rel}:<{recv}>"
                    if lid:
                        info.acquisitions.append(
                            (lid, node.lineno, False, has_timeout,
                             held))
                key = self.resolve_call(sf, node, info.class_name)
                label = _dotted(node.func) or (last or "?")
                info.calls.append((key, held, node.lineno, label))
                if effect_extractor is not None:
                    for rule, lab, line in effect_extractor(
                            sf, node, held) or ():
                        info.effects.append((rule, lab, line, held))
            if effect_extractor is not None and not isinstance(
                    node, ast.Call):
                for rule, lab, line in effect_extractor(
                        sf, node, held) or ():
                    info.effects.append((rule, lab, line, held))
            # children run in source order; an explicit .acquire() in
            # one child holds the lock for the SIBLINGS that follow
            # (until a sibling .release()s it) — `if lock.acquire():`
            # walks the If body with the lock held via the test's
            # acquire, and the try/finally release drops it after
            run = new_held
            for child in ast.iter_child_nodes(node):
                walk(child, run)
                if track_explicit:
                    acq, rel = self.lock_flow(
                        sf, child, info.class_name, info.qualname)
                    if acq or rel:
                        run = tuple(l for l in run if l not in rel) \
                            + tuple(l for l in sorted(acq)
                                    if l not in run and l not in rel)

        walk(info.node, ())

    def held_at_acquisitions(self):
        """[(holder_lock_id, acquired_lock_id, file, line, via)] edges
        from DIRECT lexical nesting — read off the summaries' held
        tuples (one walk, `_summarize`, owns the held-lock
        propagation rules)."""
        self.build_summaries()
        edges = []
        for info in self.functions.values():
            for lid, line, _with, _t, held in info.acquisitions:
                if "<" in lid:
                    continue  # pseudo-id (unresolved receiver)
                for h in held:
                    edges.append((h, lid, info.file.rel, line, None))
        return edges


def transitive_closure(seeds, calls_of, cap=64):
    """Fixpoint expansion of per-function fact sets through the call
    graph: `seeds[key]` grows by every resolvable callee's set until
    stable. Recursion converges (set union is monotonic); `cap` bounds
    a runaway set so pathological generated code cannot wedge the
    linter. Shared by the lock-order and blocking-under-lock passes —
    one copy of the termination/cap behavior."""
    changed = True
    while changed:
        changed = False
        for key, acc in seeds.items():
            if len(acc) >= cap:
                continue
            for callee in calls_of(key):
                if callee is not None and callee in seeds:
                    new = seeds[callee] - acc
                    if new:
                        acc |= new
                        changed = True
    return seeds


# -- suppression engine -------------------------------------------------

def apply_suppressions(ctx, findings):
    """Mark findings suppressed where a scoped/unscoped `# lint-ok:`
    marker with a NON-EMPTY reason sits on the finding's line; emit
    `suppression-needs-reason` findings for reasonless markers (both
    lint-ok and the hot-sync pass's hot-sync-ok). Returns the full
    finding list (suppression findings appended)."""
    by_rel = {sf.rel: sf for sf in ctx.files}
    for f in findings:
        sf = by_rel.get(f.file)
        if sf is None or f.suppressed:
            continue
        mark = sf.lint_ok.get(f.line)
        if mark is None:
            continue
        scope, reason = mark
        if scope is not None and scope != f.pass_name:
            continue
        if scope is None and f.pass_name == "hot-sync":
            # the hot-sync fence accepts only its own markers
            # (hot-sync-ok, or the explicitly scoped lint-ok[hot-sync]
            # the legacy check_source honors too) — an unscoped
            # lint-ok must not blank a sync check the shim CLI would
            # still flag
            continue
        if reason:
            f.suppressed = True
            f.reason = reason
    out = list(findings)
    for sf in ctx.files:
        # marker-free files (the vast majority) skip the AST walk and
        # the line scan entirely
        has_hot_marker = "hot-sync-ok" in sf.text
        if not sf.lint_ok and not has_hot_marker:
            continue
        strings = sf.string_lines()
        for i, (scope, reason) in sorted(sf.lint_ok.items()):
            if not reason and i not in strings:
                out.append(Finding(
                    "suppression", "suppression-needs-reason", sf.rel,
                    i, "lint-ok marker without a reason — a "
                    "suppression must say WHY (# lint-ok: <why>)"))
        if not has_hot_marker:
            continue
        for i, line in enumerate(sf.lines, 1):
            if "hot-sync-ok" in line and i not in strings and \
                    "#" in line:
                m = HOT_SYNC_OK_RE.search(line)
                if m is not None and not m.group("reason").strip():
                    out.append(Finding(
                        "suppression", "suppression-needs-reason",
                        sf.rel, i, "hot-sync-ok marker without a "
                        "reason — a suppression must say WHY "
                        "(# hot-sync-ok: <why>)"))
    return out


# -- baseline ratchet ---------------------------------------------------

BASELINE_SCHEMA = "paddle_tpu.lint_baseline.v1"


def suppressed_counts(findings):
    counts = {}
    for f in findings:
        if f.suppressed:
            counts[f.pass_name] = counts.get(f.pass_name, 0) + 1
    return counts


def load_baseline(path):
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) or \
            not isinstance(payload.get("passes"), dict):
        return None
    return payload


def check_baseline(baseline, counts, selected):
    """Ratchet verdicts: [error strings] for selected passes whose
    CURRENT suppressed count exceeds the baseline. New suppressions
    require a hand edit of LINT_BASELINE.json (visible in review);
    `--update` only ever writes counts that got SMALLER."""
    errors = []
    passes = baseline.get("passes", {})
    for name in selected:
        cur = counts.get(name, 0)
        base = passes.get(name, {}).get("suppressed")
        if base is None:
            errors.append(
                f"LINT_BASELINE.json has no entry for pass {name!r} — "
                f"add one (suppressed: {cur})")
        elif cur > base:
            errors.append(
                f"pass {name!r}: {cur} suppressed finding(s) exceeds "
                f"the baseline {base} — new suppressions must raise "
                "the baseline by hand, in the diff")
    return errors


def update_baseline(path, baseline, counts, selected):
    """Ratchet DOWN only: rewrite entries whose current count is lower
    than the recorded one. Returns (wrote, refused) — `refused` lists
    passes whose counts grew OR whose entry is missing (a new pass's
    entry is added BY HAND, in the diff, like any other loosening —
    --update never creates one)."""
    passes = baseline.get("passes", {})
    wrote, refused = False, []
    for name in selected:
        cur = counts.get(name, 0)
        entry = passes.get(name)
        base = entry.get("suppressed") if entry else None
        if base is None:
            refused.append(name)
        elif cur < base:
            entry["suppressed"] = cur
            wrote = True
        elif cur > base:
            refused.append(name)
    if wrote:
        baseline["schema"] = BASELINE_SCHEMA
        baseline["recorded_utc"] = time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(path, "w") as f:
            json.dump(baseline, f, indent=1, sort_keys=True)
            f.write("\n")
    return wrote, refused


# -- fileset ------------------------------------------------------------

EXCLUDE_DIRS = {"__pycache__", ".git", "fixtures"}


def default_fileset(root):
    """The analyzed set: paddle_tpu/**, tools/** (the linter's own
    fixtures excluded — they are known-bad on purpose)."""
    rels = []
    for top in ("paddle_tpu", "tools"):
        base = os.path.join(root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d not in EXCLUDE_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    rels.append(os.path.relpath(
                        os.path.join(dirpath, fn), root))
    return rels


def walk_fileset(root):
    """Fileset for an arbitrary root (fixture corpora): every .py under
    it."""
    rels = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in ("__pycache__", ".git")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                rels.append(os.path.relpath(os.path.join(dirpath, fn),
                                            root))
    return rels
