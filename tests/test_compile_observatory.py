"""The compilation observatory (ISSUE 6): per-executable compile/HLO
ledger, retrace forensics, and the ratcheting fusion + compile-budget
gates.

Proof points:
- every AOT-compiled executable emits exactly ONE `kind:"compile"`
  record per distinct signature (per-step, run_steps, accumulate,
  serving buckets; inspection paths add none), with HLO stats
  populated, and the records pass tools/check_metrics_schema.py;
- a forced retrace emits a structured `kind:"event"` naming the
  offending argument and the nature of the change, for each of
  shape / dtype / static-value;
- a persistent-cache-hit run (subprocess pair sharing a cache dir)
  records cache_hit=True, near-zero compile_s, and zero new on-disk
  entries;
- tools/check_compile_budget.py and tools/check_fusion.py run green
  against the checked-in BASELINE_HLO.json and fail (nonzero, naming
  the executable) on an injected regression;
- flight-recorder debug bundles include compile_ledger.json; the
  Chrome trace gains a named "compilation" track; load_profiler_result
  exposes `.compiles` / `.compile_ledger()`.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu import profiler
from paddle_tpu.jit import TrainStep
from paddle_tpu.profiler import (statistic, monitor, flight_recorder,
                                 trace_export, compile_observatory)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    path = os.path.join(REPO, "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    statistic.reset_statistics()
    monitor.reset_metrics()
    flight_recorder.reset()
    compile_observatory.reset()
    yield


def _mse(out, y):
    return ((out - y) ** 2).mean()


def _make_step(width=16, seed=0, n=8):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(8, width), nn.ReLU(), nn.Linear(width, 4))
    o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
    step = TrainStep(m, _mse, o)
    rng = np.random.RandomState(seed)
    x = paddle.to_tensor(rng.randn(n, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(n, 4).astype(np.float32))
    return step, x, y


def _compile_recs(path, tag=None):
    recs = [json.loads(l) for l in open(path) if l.strip()]
    out = [r for r in recs if r.get("kind") == "compile"]
    return [r for r in out if r["tag"] == tag] if tag else out


def _retrace_events():
    return [e for e in flight_recorder.snapshot()["events"]
            if e["event"] == "retrace"]


# ------------------------------------------------- the compile ledger
def test_one_record_per_executable_signature(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step()
    float(step(x, y).item())
    float(step(x, y).item())        # warm: same signature, no record
    step.run_steps(2, x, y)
    xs = paddle.to_tensor(np.stack([x.numpy(), x.numpy()]))
    ys = paddle.to_tensor(np.stack([y.numpy(), y.numpy()]))
    float(step.accumulate(2, xs, ys).item())

    recs = _compile_recs(mfile)
    by_tag = {}
    for r in recs:
        by_tag.setdefault(r["tag"], []).append(r)
    assert set(by_tag) == {"train.step", "train.run_steps",
                           "train.accumulate"}
    assert all(len(v) == 1 for v in by_tag.values()), by_tag
    for r in recs:
        # HLO stats populated from the compiled executable itself
        assert r["instructions"] > 0
        assert r["fusion_count"] >= 0
        assert r["bytes_accessed"] > 0     # XLA cost analysis on CPU
        assert r["flops"] > 0
        assert r["peak_memory_bytes"] > 0
        assert r["lower_s"] > 0 and r["compile_s"] > 0
        assert r["cache_hit"] is False     # persistent cache off in-suite
        assert r["signature"] and isinstance(r["signature"], str)
        assert "fusion" in json.dumps(r["op_counts"]) or \
            r["fusion_count"] == 0
    # the static segment length is part of run_steps' recorded signature
    rs = by_tag["train.run_steps"][0]
    assert "n=2" in rs["args"]
    # the documented schema tool is the contract's enforcement point
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_file(str(mfile)) == []
    # in-process ledger mirrors the JSONL and aggregates per tag
    agg = compile_observatory.aggregate()
    assert agg["train.step"]["signatures"] == 1
    assert agg["train.step"]["fusion_count"] == \
        by_tag["train.step"][0]["fusion_count"]


def test_inspection_paths_add_no_records(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step()
    float(step(x, y).item())
    step.compiled_text(x, y)
    step.cost_analysis(x, y)
    step.flops(x, y)
    assert len(_compile_recs(mfile, "train.step")) == 1


def test_serving_buckets_one_record_each(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    from paddle_tpu.inference import InferenceEngine
    paddle.seed(0)
    eng = InferenceEngine(nn.Linear(8, 4), batch_sizes=(1, 2),
                          name="obs")
    try:
        assert eng.warm(np.zeros((1, 8), np.float32)) == 2
        eng.warm(np.zeros((1, 8), np.float32))  # warm again: no records
    finally:
        eng.shutdown()
    recs = _compile_recs(mfile)
    assert sorted(r["tag"] for r in recs) == \
        ["serve.obs.batch1", "serve.obs.batch2"]
    # distinct tags per bucket: bucket laddering is NOT a retrace
    assert _retrace_events() == []


# --------------------------------------------------- retrace forensics
def test_retrace_events_name_the_changed_argument(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step(n=8)
    float(step(x, y).item())
    assert _retrace_events() == []      # first compile is not a retrace

    # shape change: both batch args shrink 8 -> 4
    rng = np.random.RandomState(1)
    x4 = paddle.to_tensor(rng.randn(4, 8).astype(np.float32))
    y4 = paddle.to_tensor(rng.randn(4, 4).astype(np.float32))
    float(step(x4, y4).item())
    evs = _retrace_events()
    assert len(evs) == 1 and evs[0]["tag"] == "train.step"
    kinds = {(c["arg"], c["change"]) for c in evs[0]["changes"]}
    assert ("batch0", "shape") in kinds and ("batch1", "shape") in kinds
    shape_change = next(c for c in evs[0]["changes"]
                        if c["arg"] == "batch0")
    assert shape_change["from"] == "[8, 8]" and \
        shape_change["to"] == "[4, 8]"

    # dtype change: y flips to f16 — the diff picks the CLOSEST cached
    # signature, so the event names exactly the one changed argument
    y16 = paddle.to_tensor(rng.randn(8, 4).astype(np.float16))
    float(step(x, y16).item())
    ev = _retrace_events()[-1]
    assert ev["changes"] == [{"arg": "batch1", "change": "dtype",
                              "from": "float32", "to": "float16"}]
    assert "batch1: dtype float32 -> float16" in ev["summary"]

    # static-value change: run_steps' scanned segment length
    step.run_steps(2, x, y)
    assert len(_retrace_events()) == 2  # new tag, not a retrace
    step.run_steps(3, x, y)
    ev = _retrace_events()[-1]
    assert ev["tag"] == "train.run_steps"
    assert {"arg": "n", "change": "static",
            "from": "2", "to": "3"} in ev["changes"]

    # the events rode into the metrics JSONL as kind:"event" and the
    # whole file (compile + event records) validates
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_file(str(mfile)) == []
    jl = [json.loads(l) for l in open(mfile) if l.strip()]
    assert sum(1 for r in jl if r.get("kind") == "event"
               and r.get("event") == "retrace") == 3
    assert monitor.counter("jit.retrace_events").value == 3


def test_diff_signatures_units():
    sig = compile_observatory.abstract_signature
    a = sig((np.zeros((4, 8), np.float32),), static={"n": 2})
    b = sig((np.zeros((2, 8), np.float32),), static={"n": 2})
    c = sig((np.zeros((4, 8), np.int32),), static={"n": 3})
    d = compile_observatory.diff_signatures(a, b, arg_names=("x",))
    assert d == [{"arg": "x", "change": "shape",
                  "from": "[4, 8]", "to": "[2, 8]"}]
    d = compile_observatory.diff_signatures(a, c, arg_names=("x",))
    assert {c_["change"] for c_ in d} == {"static", "dtype"}
    # identical signatures: empty diff, stable key
    assert compile_observatory.diff_signatures(a, a) == []
    assert compile_observatory.signature_key(a) == \
        compile_observatory.signature_key(sig(
            (np.zeros((4, 8), np.float32),), static={"n": 2}))
    # python scalars mirror jax weak-type semantics: a new VALUE is the
    # same signature (jit would not retrace either)
    assert compile_observatory.signature_key(sig((3,))) == \
        compile_observatory.signature_key(sig((4,)))


# ------------------------------------------- persistent-cache hit runs
_CACHE_CHILD = """
import json
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.jit import TrainStep
from paddle_tpu.framework import compile_cache

paddle.seed(0)
m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
step = TrainStep(
    m, lambda out, y: nn.functional.cross_entropy(out, y), o)
x = paddle.to_tensor(
    np.random.RandomState(0).randn(4, 16).astype(np.float32))
y = paddle.to_tensor(np.arange(4, dtype=np.int64) % 8)
float(step(x, y).item())
print(json.dumps({"entries": sorted(compile_cache.cache_entry_names())}))
"""


@pytest.mark.heavy
def test_cache_hit_records_near_zero_compile_no_new_entries(tmp_path):
    """Two processes sharing one persistent cache dir: the second's
    compile record must say cache_hit=True with near-zero compile_s and
    add NO new on-disk entries."""
    cache = tmp_path / "xla_cache"

    def run(idx):
        mfile = tmp_path / f"metrics{idx}.jsonl"
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", 
                    "PADDLE_TPU_COMPILE_CACHE": str(cache),
                    "PADDLE_TPU_METRICS_FILE": str(mfile),
                    "PYTHONUNBUFFERED": "1"})
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("{")][-1]
        return json.loads(line)["entries"], _compile_recs(
            mfile, "train.step")

    entries1, recs1 = run(1)
    assert len(recs1) == 1 and recs1[0]["cache_hit"] is False
    assert recs1[0]["cache_entries_added"] >= 1
    assert entries1, "first process wrote no cache entries"
    entries2, recs2 = run(2)
    assert len(recs2) == 1
    assert recs2[0]["cache_hit"] is True
    assert recs2[0]["cache_entries_added"] == 0
    assert entries2 == entries1          # no new on-disk entries
    # near-zero: a hit deserializes instead of compiling (the schema
    # tool enforces the same bound on every cache-hit record)
    assert recs2[0]["compile_s"] < recs1[0]["compile_s"]
    cms = _load_tool("check_metrics_schema")
    assert recs2[0]["compile_s"] <= cms.CACHE_HIT_COMPILE_S_MAX


# ------------------------------------------------------ ratchet gates
@pytest.mark.heavy
def test_gates_green_on_baseline_red_on_regression(tmp_path):
    """The canonical workload's ledger passes both gates against the
    checked-in BASELINE_HLO.json; an injected compile-time / fusion /
    bytes regression fails each gate nonzero, naming the executable."""
    gc = _load_tool("_gate_common")
    ledger = tmp_path / "ledger.jsonl"
    gc.run_workload(str(ledger))

    def gate(tool, *args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", tool)]
            + list(args), capture_output=True, text=True, timeout=120)

    for tool in ("check_compile_budget.py", "check_fusion.py"):
        out = gate(tool, "--ledger", str(ledger), "--require-all")
        assert out.returncode == 0, f"{tool}:\n{out.stdout}{out.stderr}"
        assert "OK:" in out.stdout

    # the ledger itself is schema-clean
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_file(str(ledger)) == []

    # inject a regression into train.step only
    bad = tmp_path / "regressed.jsonl"
    with open(ledger) as f, open(bad, "w") as g:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "compile" and \
                    rec.get("tag") == "train.step":
                rec["compile_s"] *= 100
                rec["fusion_count"] += 50
                rec["bytes_accessed"] *= 10
            g.write(json.dumps(rec) + "\n")
    out = gate("check_compile_budget.py", "--ledger", str(bad),
               "--require-all")
    assert out.returncode == 1
    assert "train.step" in out.stdout and "exceeds budget" in out.stdout
    out = gate("check_fusion.py", "--ledger", str(bad), "--require-all")
    assert out.returncode == 1
    assert "train.step: fusion_count" in out.stdout
    assert "bytes_accessed" in out.stdout
    # the regression names ONLY the regressed executable
    assert "train.accumulate: fusion_count" not in out.stdout


def test_gate_missing_executable_fails_require_all(tmp_path):
    """A baseline tag absent from a canonical ledger (renamed
    executable) must fail loudly under --require-all."""
    cb = _load_tool("check_compile_budget")
    gc = _load_tool("_gate_common")
    baseline = gc.load_baseline(os.path.join(REPO, "BASELINE_HLO.json"))
    violations, _, _ = cb.compare(baseline, {}, 2.5, 2.0,
                                  require_all=True)
    assert violations and "not in the ledger" in violations[0]
    # without --require-all a partial ledger only notes it
    violations, notes, _ = cb.compare(baseline, {}, 2.5, 2.0,
                                      require_all=False)
    assert not violations and notes


# ------------------------------------------- downstream observability
def test_debug_bundle_includes_compile_ledger(tmp_path):
    step, x, y = _make_step()
    float(step(x, y).item())
    d = flight_recorder.dump("manual", base_dir=str(tmp_path))
    assert d is not None
    payload = json.load(open(os.path.join(d, "compile_ledger.json")))
    tags = [r["tag"] for r in payload["records"]]
    assert "train.step" in tags
    assert payload["by_tag"]["train.step"]["signatures"] == 1
    manifest = json.load(open(os.path.join(d, "MANIFEST.json")))
    assert manifest["compile_records"] == len(payload["records"])


def test_trace_export_compilation_track(tmp_path):
    step, x, y = _make_step()
    float(step(x, y).item())
    events = trace_export.chrome_trace_events()
    comp = [e for e in events if e.get("cat") == "compile"]
    names = {e["name"] for e in comp}
    assert "lower train.step" in names and "compile train.step" in names
    assert all(e["tid"] == trace_export.COMPILE_TID for e in comp)
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in comp)
    sl = next(e for e in comp if e["name"] == "compile train.step")
    assert sl["args"]["tag"] == "train.step"
    assert sl["args"]["cache_hit"] is False
    # the named track rides the metadata
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               and e["tid"] == trace_export.COMPILE_TID
               and e["args"]["name"] == "compilation" for e in events)
    # and the whole trace still passes the lint
    path = trace_export.write_chrome_trace(str(tmp_path / "t.json"))
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_file(path) == []


def test_load_profiler_result_exposes_compiles(tmp_path, monkeypatch):
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step()
    float(step(x, y).item())
    float(step(x, y).item())
    result = profiler.load_profiler_result(str(mfile))
    assert len(result.steps) == 2
    assert len(result.compiles) == 1
    led = result.compile_ledger()
    assert led["train.step"]["signatures"] == 1
    assert led["train.step"]["fusion_count"] >= 0
    assert "1 compile records" in result.summary()
    # host_stats.json roundtrip carries the ledger too
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    path = prof.export_host_stats(str(tmp_path / "host_stats.json"))
    back = profiler.load_profiler_result(path)
    assert back.compile_ledger()["train.step"]["signatures"] == 1


def test_compile_schema_rejects_bad_records():
    cms = _load_tool("check_metrics_schema")
    good = {"ts": 1.0, "rank": 0, "kind": "compile", "tag": "t",
            "signature": "abc", "lower_s": 0.1, "compile_s": 0.2,
            "cache_hit": False, "instructions": 10, "fusion_count": 2,
            "bytes_accessed": 100.0, "flops": 5.0,
            "peak_memory_bytes": 64.0}
    assert cms.validate_line(json.dumps(good)) == []
    bad = dict(good, compile_s=-1.0)
    assert any("compile_s" in e for e in
               cms.validate_line(json.dumps(bad)))
    bad = dict(good, cache_hit=True,
               compile_s=cms.CACHE_HIT_COMPILE_S_MAX + 1)
    assert any("cache_hit" in e for e in
               cms.validate_line(json.dumps(bad)))
    bad = dict(good)
    del bad["fusion_count"]
    assert any("fusion_count" in e for e in
               cms.validate_line(json.dumps(bad)))
    bad = dict(good, op_counts={"fusion": -1})
    assert any("op_counts" in e for e in
               cms.validate_line(json.dumps(bad)))
    bad = dict(good, tag="")
    assert any("tag" in e for e in cms.validate_line(json.dumps(bad)))


def test_compile_record_names_the_pallas_kernels_under_their_scope():
    """hlo_stats counts a compiled text's Pallas kernels by "<innermost
    named scope>/<kernel>" (flash attention's entry names the layout it
    gave its kernels there); the record carries them, the schema tool
    takes them, and obs_report prints them under the tag."""
    class Compiled:
        def as_text(self):
            call = ('  %flash_attention_{k}.4 = bf16[8,1024,1024]{{2,1,0}} '
                    'custom-call(%a, %b), custom_call_target='
                    '"tpu_custom_call", metadata={{op_name="jit(step)/'
                    'while/body/flash.direct/flash_attention_{k}/'
                    'pallas_call" stack_frame_id=8}}\n')
            return ("HloModule m\n  %p = f32[2]{0} parameter(0)\n"
                    + call.format(k="fwd") + call.format(k="dq")
                    + call.format(k="dq"))

    stats = compile_observatory.hlo_stats(Compiled())
    assert stats["kernels"] == {"flash.direct/flash_attention_fwd": 1,
                                "flash.direct/flash_attention_dq": 2}
    rec = {"ts": 1.0, "rank": 0, "kind": "compile", "tag": "train.step",
           "signature": "abc", "lower_s": 0.1, "compile_s": 0.2,
           "cache_hit": False, "instructions": stats["instructions"],
           "fusion_count": 0, "bytes_accessed": 1.0, "flops": 1.0,
           "peak_memory_bytes": 64.0, "kernels": stats["kernels"]}
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_line(json.dumps(rec)) == []
    bad = dict(rec, kernels={"flash.direct/flash_attention_fwd": -1})
    assert any("kernels" in e for e in cms.validate_line(json.dumps(bad)))
    text = _load_tool("obs_report").render([rec])
    assert "kernels: flash.direct/flash_attention_dq x2" in text
