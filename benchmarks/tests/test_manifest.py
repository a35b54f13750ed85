"""BENCHMARK.json against the files it names: every configuration, cell
and per-layer metric is data under benchmarks/, found by its name."""
import importlib
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    M = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_keys_and_names():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in M[k]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics


def test_configs_are_files_with_their_reference():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        cfg = load(*c["file"].split("/")[1:])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        ref = importlib.import_module(
            f"benchmarks.references.{cfg['reference']}")
        assert callable(ref.forward) and callable(ref.param_spec)


def test_cells_report_what_the_manifest_says():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for w in M["workloads"]:
        cell = load("workloads", f"{w['name']}.json")
        assert cell["name"] == w["name"] and cell["kind"] in ("train",
                                                              "serve")
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        reported = [n for n, m in e2e.items()
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in M["per_layer"])
        assert set(cell["correct"]["limits"])


def test_per_layer_metrics_have_readers_and_move_what_is_reported():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    cells = [w["name"] for w in M["workloads"]]
    for m in M["per_layer"]:
        spec = load("metrics", f"{m['name']}.json")
        for k in ("name", "unit", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert spec.get("workloads") == m.get("workloads")
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        assert callable(reader.read)
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in moved.get("workloads", cells), (m["name"], c)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_rehearsal_serve_cell_lists_its_closed_signature_set():
    from benchmarks.lib.signatures import recurrent_closure
    from benchmarks.tests import tiny
    e = tiny.serve_cell()["cell"]
    eng = e["engine"]
    assert eng["n_pages"] == eng["max_batch"] + 1     # slots = max_batch
    assert [tuple(s) for s in e["signatures"]] == recurrent_closure(
        eng["max_batch"], eng["prefill_chunk"])
