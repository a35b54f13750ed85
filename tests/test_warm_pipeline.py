"""The compile pipeline (ISSUE 7): background AOT compilation with
single-flight dedup, compile-cache pack/seed, executable-sharing
warmup, and the warm-set wall-clock gate.

Proof points:
- two threads requesting the same (tag, signature) produce ONE compile
  and ONE ledger record (single-flight dedup), and a dispatch racing a
  warm() joins the in-flight compile instead of recompiling;
- a warm set's executables compile OVERLAPPED: the `kind:"warm"`
  record's wall_s lands well under the sum of per-executable seconds
  (calibrated best-of-3 on the 2-CPU container);
- warming uses exactly the steady-state abstract signatures: steady
  traffic after a warm adds ZERO (tag, signature) pairs to the
  compilation observatory's ledger — TrainStep flavors and serving
  buckets alike;
- `compile_cache.pack` -> fresh subprocess -> `seed_from` roundtrip:
  the seeded process compiles the same workload as all-cache-hit
  ledger records (near-zero compile_s, cache_entries_added == 0) and
  exports a valid `kind:"seed"` record;
- concurrent compiles keep exact hit/miss attribution (the racy
  entry-set diff around overlapping compiles is fixed via jax's
  per-thread cache events + a claimed-entries ledger);
- tools/check_metrics_schema.py validates (and rejects malformed)
  warm/seed records; tools/check_compile_budget.py gates the warm-set
  wall-clock against BASELINE_HLO.json and only ever ratchets tighter.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import compile_cache
from paddle_tpu.jit import TrainStep, warm
from paddle_tpu.profiler import (statistic, monitor, flight_recorder,
                                 compile_observatory)

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


def _mse(a, b):
    return ((a - b) ** 2).mean()


def _make_step(width=16, seed=0, n=8):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(8, width), nn.ReLU(), nn.Linear(width, 4))
    o = opt.SGD(learning_rate=0.05, parameters=m.parameters())
    step = TrainStep(m, _mse, o)
    rng = np.random.RandomState(seed)
    x = paddle.to_tensor(rng.randn(n, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(n, 4).astype(np.float32))
    return step, x, y


def _recs(path, kind="compile", tag=None):
    recs = [json.loads(l) for l in open(path) if l.strip()]
    out = [r for r in recs if r.get("kind") == kind]
    return [r for r in out if r["tag"] == tag] if tag else out


# --------------------------------------------------- single-flight dedup
def test_single_flight_dedup_one_ledger_record(tmp_path, monkeypatch):
    """N threads warming one (tag, signature) concurrently -> one
    compile, one ledger record, one executable; the extra requests JOIN
    the flight (warm.joined counts them) and all resolve to the same
    entry."""
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step()
    handles = []
    lock = threading.Lock()

    def w():
        h = step.warm(x, y)
        with lock:
            handles.append(h)

    threads = [threading.Thread(target=w) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    entries = {id(h.result(timeout=120)) for h in handles}
    assert len(entries) == 1          # every handle resolved to ONE entry
    assert len(_recs(mfile, tag="train.step")) == 1
    assert len(step._exec) == 1
    # at least one request joined an existing flight (the first
    # submitted; with 4 racers some must have deduped)
    assert monitor.counter("warm.joined").value >= 1
    assert monitor.counter("warm.submitted").value == 1
    # the warmed executable is the one dispatch uses: training works and
    # records no further compile
    float(step(x, y).item())
    assert len(_recs(mfile, tag="train.step")) == 1


def test_dispatch_joins_inflight_warm(tmp_path, monkeypatch):
    """__call__ issued while warm() is still compiling must block only
    on that one executable — and produce no duplicate ledger record."""
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step(width=32, seed=1)
    h = step.warm(x, y)               # background compile starts
    loss = float(step(x, y).item())   # dispatch joins the flight
    assert np.isfinite(loss)
    assert h.done()
    assert len(_recs(mfile, tag="train.step")) == 1
    assert step.retraces == 1


def test_dispatch_miss_never_queues_behind_unrelated_warms():
    """A dispatch-path miss compiles INLINE on the calling thread when
    it wins the single-flight race — it must not sit in the executor
    queue behind unrelated background warms. With every worker pinned
    by slow thunks, a fresh dispatch still completes in a fraction of
    their runtime."""
    n = warm.workers() + 2

    def sleeper():
        time.sleep(6)
        return ("x", {"lower_s": 0.0, "compile_s": 6.0,
                      "cache_hit": False})

    blocked = [warm.submit((f"slow{i}", i), f"slow{i}", sleeper)[0]
               for i in range(n)]
    try:
        step, x, y = _make_step(width=24, seed=7)
        t0 = time.perf_counter()
        loss = float(step(x, y).item())   # miss -> inline compile
        dt = time.perf_counter() - t0
        assert np.isfinite(loss)
        # generous bound: the tiny-step compile is well under a second;
        # queueing behind even one 6s sleeper would blow past this
        assert dt < 5.0, f"dispatch waited {dt:.1f}s behind warm queue"
    finally:
        warm.join(blocked, record=False)


def test_warm_handle_error_propagates_and_retries():
    """A failing compile thunk rejects every joiner with the real error
    and leaves the flight closed, so a retry compiles fresh."""
    calls = []

    def bad():
        calls.append(1)
        raise RuntimeError("boom in compile")

    h, submitted = warm.submit(("t", "sig"), "t", bad)
    assert submitted
    with pytest.raises(RuntimeError, match="boom in compile"):
        h.result(timeout=60)
    # the failed flight closed: a new submit runs the thunk again
    h2, submitted2 = warm.submit(("t", "sig"), "t", lambda: ("ok", {}))
    assert submitted2
    assert h2.result(timeout=60)[0] == "ok"
    assert calls == [1]


# ------------------------------------------- executable-sharing warmup
@pytest.mark.heavy
def test_warmup_adds_zero_executables_beyond_steady_state(tmp_path,
                                                          monkeypatch):
    """Warm the full executable set (per-step, run_steps, accumulate,
    serving buckets), then run steady-state traffic: the observatory
    ledger must gain ZERO (tag, signature) pairs — warmup shapes ARE
    the steady-state shapes."""
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    step, x, y = _make_step(seed=2)
    xs = paddle.to_tensor(np.stack([x.numpy()] * 2))
    ys = paddle.to_tensor(np.stack([y.numpy()] * 2))
    from paddle_tpu.inference import InferenceEngine
    paddle.seed(2)
    eng = InferenceEngine(nn.Linear(8, 4), batch_sizes=(1, 2),
                          name="wp")
    try:
        handles = [step.warm(x, y),
                   step.warm_run_steps(2, x, y),
                   step.warm_accumulate(2, xs, ys)]
        handles += eng.warm_async(np.zeros((1, 8), np.float32))
        summary = warm.join(handles)
        assert summary["n_executables"] == 5
        assert summary["compiled_now"] == 5
        warmed = compile_observatory.ledger_signatures()
        assert len(warmed) == 5

        # steady state: every path reuses a warmed executable
        float(step(x, y).item())
        step.run_steps(2, x, y)
        float(step.accumulate(2, xs, ys).item())
        eng(np.zeros((1, 8), np.float32))
        assert compile_observatory.ledger_signatures() == warmed
    finally:
        eng.shutdown()
    # the already-warm set joins as instantly-done handles with zero
    # marginal cost
    again = warm.join([step.warm(x, y),
                       step.warm_run_steps(2, x, y)], record=False)
    assert again["compiled_now"] == 0
    assert again["sum_s"] == 0.0


@pytest.mark.heavy
def test_warm_set_compiles_overlapped(tmp_path, monkeypatch):
    """The warm set's wall-clock must land meaningfully under the sum
    of its per-executable compile seconds — the overlap the background
    executor exists for. Calibrated best-of-3 on the 2-CPU container
    (host 'weather' can serialize any single round): one clean round
    passes; the failure message carries every round's numbers."""
    if warm.workers() < 2:
        pytest.skip("compile overlap needs >= 2 warm workers; this "
                    f"container gives {warm.workers()} (1 CPU) — wall "
                    "== sum is physics here, not a regression")
    mfile = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(mfile))
    rounds = []
    for rnd in range(3):
        compile_observatory.reset()
        paddle.seed(10 + rnd)  # fresh params -> fresh executables
        m = nn.Sequential(nn.Linear(64, 128), nn.Tanh(),
                          nn.Linear(128, 64), nn.Tanh(),
                          nn.Linear(64, 8))
        o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
        step = TrainStep(m, _mse, o)
        rng = np.random.RandomState(rnd)
        x = paddle.to_tensor(rng.randn(16, 64).astype(np.float32))
        y = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
        xs = paddle.to_tensor(np.stack([x.numpy()] * 2))
        ys = paddle.to_tensor(np.stack([y.numpy()] * 2))
        s = warm.join([step.warm(x, y),
                       step.warm_run_steps(2, x, y),
                       step.warm_accumulate(2, xs, ys)])
        rounds.append(s)
        # meaningful compiles (not measuring thread overhead) that
        # finished wall-clock under 90% of their serial cost
        if s["sum_s"] > 0.5 and s["wall_s"] < 0.9 * s["sum_s"]:
            break
    else:
        pytest.fail(
            "no round overlapped: " + "; ".join(
                f"wall {r['wall_s']:.2f}s vs sum {r['sum_s']:.2f}s"
                for r in rounds))
    # the evidence rode into the metrics JSONL as kind:"warm" records
    # and the whole file validates
    wrecs = _recs(mfile, kind="warm")
    assert len(wrecs) == len(rounds)
    assert wrecs[-1]["n_executables"] == 3
    cms = _load_tool("check_metrics_schema")
    assert cms.validate_file(str(mfile)) == []


# --------------------------------------------------- pack/seed roundtrip
_SEED_CHILD = """
import json, os, sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.jit import TrainStep
from paddle_tpu.framework import compile_cache

mode = sys.argv[1]
if mode == "seed":
    info = compile_cache.seed_from(sys.argv[2])
    print("seed-info: " + json.dumps(info), file=sys.stderr)

paddle.seed(0)
m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
step = TrainStep(
    m, lambda out, y: nn.functional.cross_entropy(out, y), o)
x = paddle.to_tensor(
    np.random.RandomState(0).randn(4, 16).astype(np.float32))
y = paddle.to_tensor(np.arange(4, dtype=np.int64) % 8)
float(step(x, y).item())
step.run_steps(2, x, y)

if mode == "pack":
    out = compile_cache.pack(sys.argv[2])
    print(json.dumps({"packed": out["entries"]}))
else:
    print(json.dumps({"entries": len(compile_cache.cache_entry_names())}))
"""


@pytest.mark.heavy
def test_pack_seed_roundtrip_fresh_subprocess(tmp_path):
    """Process 1 compiles cold under cache A and packs it; process 2 —
    fresh, with a DIFFERENT cache dir — seeds from the pack and must
    compile the same workload as all-cache-hit records adding zero
    entries. This is the donated-artifact warm start (and proves cache
    keys don't hash the cache path)."""

    def run(mode, cache, extra, idx):
        mfile = tmp_path / f"metrics{idx}.jsonl"
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PADDLE_TPU_COMPILE_CACHE": str(cache),
                    "PADDLE_TPU_METRICS_FILE": str(mfile),
                    "PYTHONUNBUFFERED": "1"})
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, "-c", _SEED_CHILD, mode, str(extra)],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("{")][-1]
        return json.loads(line), mfile, proc.stderr

    pack_dir = tmp_path / "artifact"
    out1, m1, _ = run("pack", tmp_path / "cacheA", pack_dir, 1)
    assert out1["packed"] >= 2          # step + run_steps at least
    assert (pack_dir / "MANIFEST.json").exists()
    manifest = json.loads((pack_dir / "MANIFEST.json").read_text())
    assert manifest["schema"] == compile_cache.PACK_SCHEMA
    assert len(manifest["entries"]) == out1["packed"]
    recs1 = _recs(m1)
    assert recs1 and all(r["cache_hit"] is False for r in recs1)

    out2, m2, err2 = run("seed", tmp_path / "cacheB", pack_dir, 2)
    recs2 = _recs(m2)
    assert {r["tag"] for r in recs2} == {"train.step",
                                         "train.run_steps"}
    cms = _load_tool("check_metrics_schema")
    for r in recs2:
        # all-cache-hit, zero new entries, near-zero compile seconds
        assert r["cache_hit"] is True, r
        assert r["cache_entries_added"] == 0, r
        assert r["compile_s"] <= cms.CACHE_HIT_COMPILE_S_MAX
    # the seed itself exported a valid kind:"seed" record
    seeds = _recs(m2, kind="seed")
    assert len(seeds) == 1
    assert seeds[0]["entries_seeded"] == out1["packed"]
    assert seeds[0]["entries_skipped"] == 0
    assert cms.validate_file(str(m2)) == []
    # and the seeded cache gained nothing beyond the artifact
    assert out2["entries"] == out1["packed"]


_ATTR_CHILD = """
import json, threading
import jax, jax.numpy as jnp
from paddle_tpu.framework import compile_cache
from paddle_tpu.jit.api import aot_compile
from paddle_tpu.profiler import compile_observatory as cobs

x = jnp.ones((96, 96))
def go(tag, f):
    aot_compile(jax.jit(f), (x,), tag=tag)

# phase 1: two DIFFERENT programs compile concurrently (miss + miss)
t1 = threading.Thread(target=go, args=("m1", lambda a: a @ a + 1.0))
t2 = threading.Thread(target=go, args=("m2", lambda a: (a * 2) @ a.T))
t1.start(); t2.start(); t1.join(); t2.join()
# phase 2: a HIT for m1's program overlapping a fresh MISS — the racy
# window the entry-set diff used to misattribute
t3 = threading.Thread(target=go, args=("hit", lambda a: a @ a + 1.0))
t4 = threading.Thread(target=go, args=("m3", lambda a: jnp.tanh(a) @ a))
t3.start(); t4.start(); t3.join(); t4.join()
recs = {r["tag"]: {"hit": r["cache_hit"],
                   "added": r["cache_entries_added"]}
        for r in cobs.ledger()}
print(json.dumps({"recs": recs,
                  "disk": len(compile_cache.cache_entry_names())}))
"""


@pytest.mark.heavy
def test_concurrent_cache_hit_attribution(tmp_path):
    """Overlapping compiles with the persistent cache ON: every record's
    hit/miss flag is exact (per-thread jax cache events), a hit claims
    zero entries even when a concurrent miss lands entries inside its
    window, and no entry is double-counted."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PADDLE_TPU_COMPILE_CACHE": str(tmp_path / "cache"),
                "PYTHONUNBUFFERED": "1"})
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _ATTR_CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("{")][-1])
    recs = out["recs"]
    # hit/miss flags are EXACT for every record; entry counts may shift
    # between overlapping misses (documented: one miss's window can
    # swallow the other's entries) but never double-count — per phase,
    # the misses' claims sum to at least one entry each on average and
    # a hit always claims zero
    assert recs["m1"]["hit"] is False and recs["m2"]["hit"] is False
    assert recs["m1"]["added"] + recs["m2"]["added"] >= 2
    assert recs["m3"]["hit"] is False and recs["m3"]["added"] >= 1
    # the racy case: the hit stays a hit and claims nothing, even with
    # the concurrent miss m3 landing entries inside its window
    assert recs["hit"]["hit"] is True
    assert recs["hit"]["added"] == 0


# ------------------------------------------------- schema + budget gate
def test_warm_and_seed_schema_validation():
    cms = _load_tool("check_metrics_schema")
    good_warm = {"ts": 1.0, "rank": 0, "kind": "warm",
                 "n_executables": 3, "compiled_now": 2, "cache_hits": 1,
                 "wall_s": 1.5, "sum_s": 4.0,
                 "tags": ["train.step", "train.run_steps"]}
    assert cms.validate_line(json.dumps(good_warm)) == []
    bad = dict(good_warm, compiled_now=5)
    assert any("compiled_now" in e
               for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_warm, cache_hits=3)
    assert any("cache_hits" in e
               for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_warm, wall_s=-0.1)
    assert any("wall_s" in e for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_warm)
    del bad["sum_s"]
    assert any("sum_s" in e for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_warm, tags=["ok", ""])
    assert any("tags" in e for e in cms.validate_line(json.dumps(bad)))

    good_seed = {"ts": 1.0, "rank": 0, "kind": "seed", "source": "/a",
                 "cache_dir": "/b", "entries_seeded": 4,
                 "entries_skipped": 0}
    assert cms.validate_line(json.dumps(good_seed)) == []
    bad = dict(good_seed, entries_seeded=-1)
    assert any("entries_seeded" in e
               for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_seed, source="")
    assert any("source" in e for e in cms.validate_line(json.dumps(bad)))
    bad = dict(good_seed)
    del bad["entries_skipped"]
    assert any("entries_skipped" in e
               for e in cms.validate_line(json.dumps(bad)))


def test_budget_gate_warm_set_comparand(tmp_path):
    """check_compile_budget's warm-set wall-clock comparand: green
    within budget, red (named) when the overlap breaks, ratcheted only
    tighter by --update."""
    cb = _load_tool("check_compile_budget")
    baseline = {"executables": {},
                "warm_set": {"wall_s": 2.0, "sum_s": 6.0,
                             "n_executables": 5}}
    ok = {"kind": "warm", "wall_s": 2.2, "sum_s": 6.0,
          "n_executables": 5}
    v, n, r = cb.compare_warm(baseline, ok, 2.5, 2.0, False)
    assert v == [] and r is None
    # regression: wall blew past base*factor+slack (overlap broke)
    slow = dict(ok, wall_s=2.0 * 2.5 + 2.0 + 1.0)
    v, n, r = cb.compare_warm(baseline, slow, 2.5, 2.0, False)
    assert len(v) == 1 and "warm_set" in v[0] and "overlap" in v[0]
    # faster run ratchets
    fast = dict(ok, wall_s=1.2)
    v, n, r = cb.compare_warm(baseline, fast, 2.5, 2.0, False)
    assert v == [] and r == {"wall_s": 1.2, "sum_s": 6.0,
                             "n_executables": 5}
    # a baseline with warm_set but a ledger without a warm record is a
    # violation only under --require-all
    v, n, r = cb.compare_warm(baseline, None, 2.5, 2.0, False)
    assert v == [] and n
    v, n, r = cb.compare_warm(baseline, None, 2.5, 2.0, True)
    assert len(v) == 1
    # the checked-in baseline carries the warm_set entry
    gc = _load_tool("_gate_common")
    payload = gc.load_baseline(os.path.join(REPO, "BASELINE_HLO.json"))
    assert payload["warm_set"]["wall_s"] > 0
    assert payload["warm_set"]["wall_s"] < payload["warm_set"]["sum_s"]


def test_gate_common_load_warm_record(tmp_path):
    gc = _load_tool("_gate_common")
    p = tmp_path / "l.jsonl"
    p.write_text(
        json.dumps({"kind": "compile", "tag": "t"}) + "\n"
        + json.dumps({"kind": "warm", "wall_s": 1.0, "sum_s": 2.0}) + "\n"
        + json.dumps({"kind": "warm", "wall_s": 3.0, "sum_s": 4.0}) + "\n")
    rec = gc.load_warm_record(str(p))
    assert rec["wall_s"] == 3.0          # the LAST warm record wins
    p2 = tmp_path / "none.jsonl"
    p2.write_text(json.dumps({"kind": "compile", "tag": "t"}) + "\n")
    assert gc.load_warm_record(str(p2)) is None


def test_copy_seed_entries_skips_what_is_there_and_what_is_no_entry(
        tmp_path):
    """Seeding is idempotent file copies: entries land in the cache dir,
    a second seed skips them all, and a pack's manifest, hidden files
    and directories are no entries."""
    src = tmp_path / "artifact"
    src.mkdir()
    (src / "abc-cache").write_bytes(b"x" * 64)
    (src / "def-cache").write_bytes(b"y" * 64)
    (src / "MANIFEST.json").write_text("{}")
    (src / ".hidden").write_text("no")
    (src / "subdir").mkdir()
    dst = tmp_path / "cache"
    assert compile_cache.copy_seed_entries(str(src), str(dst)) == (2, 0)
    assert sorted(os.listdir(dst)) == ["abc-cache", "def-cache"]
    assert compile_cache.copy_seed_entries(str(src), str(dst)) == (0, 2)
    with pytest.raises(ValueError, match="not a directory"):
        compile_cache.seed_from(str(tmp_path / "missing"))
