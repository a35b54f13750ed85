"""The documents name files and variables that exist.

One case per document (README.md and every file under docs/):

- every back-quoted repo path — a name with a directory part whose first
  component is a directory of the repo or of `paddle_tpu/`, or any name
  ending in `.py` — is a file or directory of the tree (as written from
  the root, or as the tail of a path under it: `jit/api.py` is
  `paddle_tpu/jit/api.py`);
- every variable the document names whose prefix is `BENCH` or
  `PADDLE_TPU` occurs in the code that could read it: `paddle_tpu/`,
  `tools/` or `chip_smoke.py`.

A document that describes a deleted file or a variable nothing reads
fails here, with the names listed.
"""
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", n) for n in os.listdir(os.path.join(REPO, "docs"))
    if n.endswith(".md"))
SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".xla_cache",
             "chiprun_out", ".bench_src", ".smoke_src"}
VARIABLE = re.compile(r"\b(?:BENCH|PADDLE_TPU)_[A-Z0-9_]*[A-Z0-9]\b")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")


@functools.cache
def tree():
    """Every file and directory of the checkout, relative, '/'-joined."""
    out = set()
    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        rel = os.path.relpath(dirpath, REPO).replace(os.sep, "/")
        for n in dirnames + filenames:
            out.add(n if rel == "." else f"{rel}/{n}")
    return out


@functools.cache
def code_text():
    """The text of everything under paddle_tpu/ and tools/, and
    chip_smoke.py."""
    parts = []
    for top in ("paddle_tpu", "tools"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, top)):
            dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
            for n in filenames:
                with open(os.path.join(dirpath, n), errors="ignore") as f:
                    parts.append(f.read())
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        parts.append(f.read())
    return "\n".join(parts)


@functools.cache
def top_dirs():
    """The directories of the repo's root and of paddle_tpu/, by name."""
    return {n for top in (REPO, os.path.join(REPO, "paddle_tpu"))
            for n in os.listdir(top) if os.path.isdir(os.path.join(top, n))}


def repo_paths(text):
    """The back-quoted names of `text` that claim to be repo paths."""
    found = []
    for quoted in re.findall(r"`([^`\n]+)`", text):
        # `tests/test_x.py::test_name`, `tools/x.py --flag`, `api.py:120`
        name = re.split(r"::|\s|:\d", quoted.strip())[0].rstrip("/.,")
        if not name or not PATH.match(name) or name.startswith(("/", ".")):
            continue
        if name.endswith(".py") or \
                ("/" in name and name.split("/")[0] in top_dirs()):
            found.append(name)
    return found


def exists(name):
    """In the tree as written or as the tail of a path; or, for
    `ops/chunked_xent.softmax_xent_logits`, a name in such a module."""
    if name in tree() or any(p.endswith("/" + name) for p in tree()):
        return True
    module, dot, attr = name.rpartition(".")
    return bool(dot) and attr != "py" and exists(module + ".py")


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_real_files_and_variables(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = sorted({p for p in repo_paths(text) if not exists(p)})
    assert not missing, f"{doc} names paths that are not in the tree"
    unread = sorted({v for v in VARIABLE.findall(text) if v not in code_text()})
    assert not unread, f"{doc} names variables that no code reads"


def test_the_extraction_sees_paths_and_variables():
    text = ("`jit/api.py`, `tests/test_x.py::test_y`, `tools/lint/`, "
            "`python tools/t.py --update`, `a/b`, `serve.step.admit`, "
            "`<checkout>/.xla_cache`, `PADDLE_TPU_NO_SUCH=1` PADDLE_TPU_X2")
    assert repo_paths(text) == ["jit/api.py", "tests/test_x.py", "tools/lint"]
    assert exists("jit/api.py") and exists("tools/lint")
    assert not exists("tests/test_x.py") and not exists("no_such.py")
    assert VARIABLE.findall(text) == ["PADDLE_TPU_NO_SUCH", "PADDLE_TPU_X2"]
