"""The span_ms reader: its arithmetic on a hand-built span list, and
what it reads from the program's own recorder after the rehearsal's
tiny train cell and tiny serving cell have run on the CPU."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.readers import span_ms
from benchmarks.tests import tiny
from benchmarks.tests.test_rehearsal import (  # noqa: F401 (fixture)
    SEED, SERVE, TRAIN, cpu_runner)

STEP_METRICS = ["step_prep_ms.train", "step_dispatch_ms.train",
                "step_probe_ms.train", "step_telemetry_ms.train"]
SERVE_CHILDREN = ["admit", "plan", "dispatch", "fetch", "emit",
                  "telemetry"]


def sp(name, start_ms, dur_ms, thread=1, depth=0):
    return {"name": name, "start_s": start_ms / 1e3,
            "dur_s": dur_ms / 1e3, "thread": thread, "depth": depth}


def call(t0, prep, dispatch, probe=None):
    """One `step` call of 10 ms at t0, in closing order: its children,
    then the parent; a `dispatch` of another thread falls inside it."""
    out = [sp("step.prep", t0, prep, depth=1)]
    if probe:
        out.append(sp("step.probe", t0 + prep, probe, depth=1))
    out += [sp("inner", t0 + 5, 1, depth=2),
            sp("step.dispatch", t0 + 5, dispatch, depth=1),
            sp("step.dispatch", t0 + 5, 3, thread=2, depth=0),
            sp("step", t0, 10)]
    return out


RING = call(0, 1, 2) + call(20, 2, 4, probe=1) + call(40, 3, 3)


@pytest.mark.parametrize("span,calls,want", [
    ("step.prep", 3, 2.0),            # (1 + 2 + 3) / 3
    ("step.dispatch", 3, 3.0),        # the other thread's is not counted
    ("step.probe", 3, 1 / 3),         # ran in one call of three
    ("step.probe", 1, 0.0),           # never ran in the last call: 0
    ("step.prep", 2, 2.5),            # the window is the LAST two calls
    ("step.prep", 50, 2.0),           # a ring that holds fewer: all three
    (None, 3, 10 - (3 + 7 + 6) / 3),  # self time: direct children only
    ("inner", 3, 1.0),                # any depth inside the parent
])
def test_mean_per_call(span, calls, want):
    assert span_ms.mean_ms(RING, "step", span, calls) \
        == pytest.approx(want, abs=1e-9)


def test_parent_absent_is_none():
    assert span_ms.mean_ms(RING, "serve.step", "step.prep", 3) is None
    assert span_ms.mean_ms([], "step", None, 3) is None
    assert span_ms.mean_ms(RING, "step", "step.prep", 0) is None


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    from paddle_tpu.profiler import statistic
    monkeypatch.delattr(statistic, "closed_spans")
    assert span_ms.read({"window": {"steps": 3}}, "train.step") is None


def test_manifest_lists_the_step_metrics_last():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert names[-4:] == STEP_METRICS


@pytest.mark.parametrize("cell", TRAIN)
def test_train_cell_reads_its_phases(cpu_runner, cell):
    line = run.run_cell(cell, SEED + 4, 1.5, 1)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(STEP_METRICS) <= set(m)
    assert all(v["unit"] == "ms" for k, v in line["metrics"].items()
               if k in STEP_METRICS)
    for k in ("step_prep_ms.train", "step_dispatch_ms.train",
              "step_telemetry_ms.train"):
        assert m[k] > 0
    assert m["step_probe_ms.train"] >= 0
    # the four phases are the call: they add up to what the benchmark's
    # own clock around the call reads, less the parent's self time
    covered = sum(m[k] for k in STEP_METRICS)
    assert 0.85 * m["host_step_ms.train"] < covered \
        <= m["host_step_ms.train"]


@pytest.mark.parametrize("cell", SERVE)
def test_serve_cell_reads_its_phases(cpu_runner, cell):
    keep = {}
    run.run_cell(cell, SEED + 5, 1.5, 0, keep=keep)
    ctx = {"window": keep["window"]}
    assert ctx["window"]["steps"] > 0
    parts = {c: span_ms.read(ctx, "serve.step", f"serve.step.{c}")
             for c in SERVE_CHILDREN}
    assert all(v > 0 for v in parts.values()), parts
    self_ms = span_ms.read(ctx, "serve.step")
    assert 0 <= self_ms < 0.1 * sum(parts.values())
