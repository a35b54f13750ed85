"""The distributed observatory (ISSUE 13): per-collective timing,
rank-skew/straggler detection and clock-aligned multi-rank traces.

Proof points:
- every collective call folds into the rollup and the sampled subset
  emits schema-valid `kind:"collective"` records (eager calls with real
  bandwidth, traced insertions flagged);
- a step's `step_time_s` is the interval between dispatch returns, a
  host stall included, and every step enters the `train.step_s`
  reservoir the rankstat percentiles come from, in both train steps;
- `kind:"rankstat"` records validate, snapshot atomically into the
  gather dir, and rank 0's gather feeds the straggler detector
  (edge-triggered, naming rank + lag);
- merged traces are CLOCK-ALIGNED: a fabricated 5 s clock skew
  disappears when otherData.clock_offset_s is applied (and survives
  --no-align);
- `load_profiler_result` exposes `.collectives` / `.rankstats` from
  both JSONL and host_stats.json;
- tools/obs_report.py renders the run summary;
- END TO END: a 4-process `launch.py` run with a 300 ms
  `delay@train.step` fault on exactly one rank produces a schema-valid
  rankstat stream and a straggler event naming that rank, plus
  clock-aligned mergeable traces.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.distributed as dist
from paddle_tpu import optimizer as opt
from paddle_tpu import profiler
from paddle_tpu.jit import TrainStep
from paddle_tpu.profiler import (dist_observatory as dobs, monitor,
                                 statistic, flight_recorder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_dist_obs_worker.py")


def _load_tool(name):
    path = os.path.join(REPO, "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    statistic.reset_statistics()
    monitor.reset_metrics()
    flight_recorder.reset()
    dobs.reset()
    monkeypatch.delenv("PADDLE_TPU_RANKSTAT_DIR", raising=False)
    yield
    dobs.reset()


def _make_step():
    paddle.seed(0)
    m = nn.Linear(8, 8)
    o = opt.SGD(learning_rate=0.01, parameters=m.parameters())
    step = TrainStep(m, lambda out, y: ((out - y) ** 2).mean(), o)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(4, 8).astype(np.float32))
    return step, x


# ------------------------------------------------ collective telemetry
def test_eager_collective_emits_record_and_rollup(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE",
                       str(tmp_path / "m.jsonl"))
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_SAMPLE", "1")
    t = paddle.to_tensor(np.ones(1024, np.float32))
    dist.all_reduce(t)
    dist.wait(t)
    roll = dobs.collective_rollup()
    assert roll["all_reduce"]["calls"] == 1
    assert roll["all_reduce"]["bytes"] == 4096
    assert roll["all_reduce"]["wall_s"] > 0
    assert roll["all_reduce"]["traced_calls"] == 0
    recs = [r for r in dobs.collectives_tail()]
    ops = {r["op"] for r in recs}
    assert {"all_reduce", "wait"} <= ops
    ar = next(r for r in recs if r["op"] == "all_reduce")
    assert ar["group"] == "dp" and ar["bytes"] == 4096
    assert ar["traced"] is False and ar["bw_gbps"] > 0
    # the JSONL lines validate against the schema tool
    tool = _load_tool("check_metrics_schema")
    assert tool.validate_file(str(tmp_path / "m.jsonl")) == []


def test_collective_sampling_cadence(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_SAMPLE", "4")
    for _ in range(9):
        dobs.record_collective("psum", "dp", 128, 1e-5)
    # sampled at call 1 (first), 4, 8 — rollup counts all 9
    assert len(dobs.collectives_tail()) == 3
    assert dobs.collective_rollup()["psum"]["calls"] == 9
    assert dobs.collective_rollup()["psum"]["bytes"] == 9 * 128


def test_traced_collective_flagged_not_timed(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_SAMPLE", "1")
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    f = jax.jit(shard_map(lambda v: dist.psum(v, "dp"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P()))
    out = f(np.ones(4, np.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    roll = dobs.collective_rollup()["psum"]
    assert roll["traced_calls"] >= 1 and roll["wall_s"] == 0.0
    rec = next(r for r in dobs.collectives_tail() if r["op"] == "psum")
    assert rec["traced"] is True and rec["bw_gbps"] == 0.0
    # eager wait accounting must exclude traced insertion time
    assert dobs.eager_wait_s() == 0.0


# ------------------------------------------------ step-time accounting
def _make_hybrid_step():
    from paddle_tpu.distributed.env import build_mesh
    from paddle_tpu.distributed.fleet.hybrid_train import HybridTrainStep
    paddle.seed(0)
    m = nn.Linear(8, 8)
    o = opt.SGD(learning_rate=0.01, parameters=m.parameters())
    step = HybridTrainStep(m, lambda out, y: ((out - y) ** 2).mean(), o,
                           build_mesh(dp=8))
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(8, 8).astype(np.float32))
    return step, x


@pytest.mark.parametrize("make", [_make_step, _make_hybrid_step],
                         ids=["TrainStep", "HybridTrainStep"])
def test_step_time_keeps_host_stalls_and_every_step_is_in_the_reservoir(
        make, tmp_path, monkeypatch):
    """A step that did not compile and has a predecessor is steady: its
    step_time_s is the interval between dispatch returns, so a host
    stall between two calls (here 250 ms of sleep before calls 17 and
    18, across what was the probe's cadence) shows in that step and in
    no other, and each of the 20 steps is one observation of the
    train.step_s reservoir the rankstat percentiles come from."""
    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    step, x = make()
    loss = None
    for i in range(1, 21):
        if i in (17, 18):
            time.sleep(0.25)
        loss = step(x, x)
    float(loss.item())
    steps = {r["step"]: r for r in
             (json.loads(l) for l in path.read_text().splitlines()
              if l.strip()) if r["kind"] == "step"}
    assert sorted(steps) == list(range(1, 21))
    assert steps[1]["compile_s"] > 0
    for i in range(2, 21):
        assert steps[i]["compile_s"] == 0.0, steps[i]
        if i in (17, 18):
            assert steps[i]["step_time_s"] > 0.24, steps[i]
        else:
            assert 0.0 < steps[i]["step_time_s"] < 0.2, steps[i]
    hist = monitor.get_metric("train.step_s")
    assert hist.count == 20
    tool = _load_tool("check_metrics_schema")
    assert tool.validate_file(str(path)) == []


def test_emit_rankstat_respects_disable_unless_forced(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_EVERY", "0")
    monitor.histogram("train.step_s").observe(0.01)
    assert dobs.emit_rankstat(step=1) is None       # epoch-boundary path
    assert dobs.emit_rankstat(step=1, force=True) is not None  # gate/dryrun


# ------------------------------------------------ rankstat + straggler
def test_rankstat_record_schema_and_snapshot(tmp_path, monkeypatch):
    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_DIR",
                       str(tmp_path / "gather"))
    for v in (0.01, 0.02, 0.03):
        monitor.histogram("train.step_s").observe(v)
    rec = dobs.emit_rankstat(step=3)
    assert rec is not None
    assert rec["step_time_p50_s"] > 0
    assert rec["step_time_p99_s"] >= rec["step_time_p50_s"]
    assert 0.0 <= rec["collective_wait_share"] <= 1.0
    # atomic snapshot for the rank-0 gather
    snap = tmp_path / "gather" / "rankstat.0.json"
    assert snap.exists()
    peer = json.loads(snap.read_text())
    assert peer["rank"] == 0 and peer["step_time_p50_s"] > 0
    assert dobs.read_peer_rankstats(str(tmp_path / "gather"))[0]
    tool = _load_tool("check_metrics_schema")
    assert tool.validate_file(str(path)) == []


def test_rank0_gather_emits_straggler_naming_rank(tmp_path, monkeypatch):
    """Single-process simulation of the rank-0 gather: fake peer
    snapshots with one slow rank -> event:'straggler' names it."""
    path = tmp_path / "m.jsonl"
    gather = tmp_path / "gather"
    gather.mkdir()
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_DIR", str(gather))
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    for r, p50 in ((1, 0.011), (2, 0.31), (3, 0.009)):
        (gather / f"rankstat.{r}.json").write_text(json.dumps(
            {"rank": r, "step": 8, "steps_observed": 8,
             "step_time_p50_s": p50}))
    for _ in range(4):
        monitor.histogram("train.step_s").observe(0.01)
    dobs.emit_rankstat(step=8)
    evs = [e for e in flight_recorder.snapshot()["events"]
           if e.get("event") == "straggler"]
    assert len(evs) == 1, evs
    assert evs[0]["straggler_rank"] == 2
    assert evs[0]["lag_s"] > 0.25
    assert monitor.get_metric("dist.stragglers").value == 1
    # edge-triggered: the same skew again emits nothing new
    dobs.emit_rankstat(step=10)
    evs = [e for e in flight_recorder.snapshot()["events"]
           if e.get("event") == "straggler"]
    assert len(evs) == 1


def test_two_rank_world_straggler_detectable():
    """True median: in a 2-rank world the straggler's own time must not
    become the baseline (the upper-middle pick made it undetectable)."""
    from paddle_tpu.profiler.health import AnomalyDetector
    d = AnomalyDetector()
    evs = d.observe_ranks(5, {0: 0.1, 1: 0.4})
    assert len(evs) == 1 and evs[0]["straggler_rank"] == 1, evs


def test_gather_skips_stale_and_out_of_world_snapshots(
        tmp_path, monkeypatch):
    """An elastic restart reusing the log_dir (frozen snapshots from a
    dead rank / a shrunk world) must not feed phantom stragglers."""
    gather = tmp_path / "gather"
    gather.mkdir()
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE",
                       str(tmp_path / "m.jsonl"))
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_DIR", str(gather))
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    now = time.time()
    # rank 1: fresh, healthy. rank 5: outside the 2-rank world. rank 1
    # variant stale: a frozen slow snapshot from an hour ago
    (gather / "rankstat.1.json").write_text(json.dumps(
        {"rank": 1, "steps_observed": 8, "step_time_p50_s": 0.01,
         "ts": now}))
    (gather / "rankstat.5.json").write_text(json.dumps(
        {"rank": 5, "steps_observed": 8, "step_time_p50_s": 9.0,
         "ts": now}))
    (gather / "rankstat.3.json").write_text(json.dumps(
        {"rank": 3, "steps_observed": 8, "step_time_p50_s": 9.0,
         "ts": now - 3600}))
    for _ in range(4):
        monitor.histogram("train.step_s").observe(0.01)
    dobs.emit_rankstat(step=8)
    evs = [e for e in flight_recorder.snapshot()["events"]
           if e.get("event") == "straggler"]
    assert evs == [], evs  # the phantom slow ranks were filtered out


def test_maybe_rankstat_cadence(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_EVERY", "4")
    monitor.histogram("train.step_s").observe(0.01)
    assert dobs.maybe_rankstat(1) is not None   # first step always
    assert dobs.maybe_rankstat(2) is None
    assert dobs.maybe_rankstat(3) is None
    assert dobs.maybe_rankstat(4) is not None   # cadence boundary
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_EVERY", "0")
    assert dobs.maybe_rankstat(8) is None       # disabled


# ------------------------------------------------ schema rejections
def test_schema_rejects_bad_collective_and_rankstat(tmp_path):
    tool = _load_tool("check_metrics_schema")
    base = {"ts": 1.0, "rank": 0}
    bad = tmp_path / "bad.jsonl"
    bad.write_text("\n".join(json.dumps(r) for r in [
        # infinite bandwidth must be named
        dict(base, kind="collective", op="psum", group="dp", bytes=8,
             wall_s=0.0, bw_gbps=float("inf")),
        # negative bytes
        dict(base, kind="collective", op="psum", group="dp", bytes=-1,
             wall_s=0.0, bw_gbps=0.0),
        # rank outside the world
        dict(base, rank=5, kind="rankstat", step=1, world_size=4,
             step_time_p50_s=0.01, step_time_p99_s=0.02,
             host_blocked_s=0.0, collective_wait_s=0.0,
             collective_wait_share=0.0, peak_bytes=0),
        # inverted percentiles
        dict(base, kind="rankstat", step=1, world_size=1,
             step_time_p50_s=0.05, step_time_p99_s=0.01,
             host_blocked_s=0.0, collective_wait_s=0.0,
             collective_wait_share=0.0, peak_bytes=0),
        # share out of range
        dict(base, kind="rankstat", step=1, world_size=1,
             step_time_p50_s=0.01, step_time_p99_s=0.02,
             host_blocked_s=0.0, collective_wait_s=0.0,
             collective_wait_share=1.5, peak_bytes=0),
        # a step record: epilogue share out of range
        dict(base, kind="step", step=1, step_time_s=0.1, compile_s=0.0,
             cache_hit=True, peak_bytes=1, flops=1.0, mfu=0.1,
             epilogue_bytes=8, epilogue_share=1.5),
    ]) + "\n")
    errors = tool.validate_file(str(bad))
    for needle in ("bw_gbps", "bytes must be >= 0", "world_size",
                   "percentiles cannot invert",
                   "collective_wait_share", "epilogue_share"):
        assert any(needle in e for e in errors), (needle, errors)


# ------------------------------------------------ clock alignment
def _fake_trace(path, rank, offset_s, event_wall_s):
    """A minimal trace whose one slice happened at `event_wall_s` on
    rank 0's clock but was STAMPED with a clock running `offset_s`
    ahead (exactly what a skewed rank exports)."""
    events = [
        {"ph": "M", "name": "process_name", "pid": rank, "tid": 0,
         "ts": 0, "args": {"name": f"paddle_tpu rank {rank}"}},
        {"ph": "M", "name": "thread_name", "pid": rank, "tid": 21,
         "ts": 0, "args": {"name": "collectives"}},
        {"ph": "X", "name": "collective.psum", "cat": "collective",
         "ts": (event_wall_s + offset_s) * 1e6, "dur": 1000.0,
         "pid": rank, "tid": 21, "args": {}},
    ]
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "otherData": {"rank": rank,
                                 "clock_offset_s": offset_s}}, f)


def test_merge_traces_clock_aligns(tmp_path):
    mt = _load_tool("merge_traces")
    a, b = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
    _fake_trace(a, 0, 0.0, event_wall_s=100.0)
    _fake_trace(b, 1, 5.0, event_wall_s=100.0)  # clock 5 s ahead
    out = str(tmp_path / "merged.json")
    assert mt.main(["-o", out, a, b]) == 0
    merged = json.load(open(out))
    assert merged["otherData"]["clock_aligned"] is True
    slices = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(slices) == 2
    ts = sorted(e["ts"] for e in slices)
    # the SAME physical instant: aligned to within a millisecond
    assert abs(ts[1] - ts[0]) < 1e3, ts
    # metadata is NEVER shifted (a thread_name at ts 0 must not land
    # 5 s before the timeline)
    metas = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
    assert all(e["ts"] == 0 for e in metas), metas
    # the aligned merge still validates as a Chrome trace
    tool = _load_tool("check_metrics_schema")
    assert tool.validate_file(out) == []
    # and --no-align keeps the raw 5 s skew
    out2 = str(tmp_path / "raw.json")
    assert mt.main(["-o", out2, "--no-align", a, b]) == 0
    raw = [e for e in json.load(open(out2))["traceEvents"]
           if e.get("ph") == "X"]
    ts = sorted(e["ts"] for e in raw)
    assert abs(ts[1] - ts[0]) > 4.9e6


def test_trace_export_stamps_clock_offset(tmp_path):
    from paddle_tpu.profiler import trace_export
    monitor.histogram("train.step_s").observe(0.01)
    path = trace_export.write_chrome_trace(str(tmp_path / "t.json"))
    payload = json.load(open(path))
    assert payload["otherData"]["clock_offset_s"] == 0.0


# ------------------------------------------------ load_profiler_result
def test_load_profiler_result_exposes_new_kinds(tmp_path, monkeypatch):
    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_SAMPLE", "1")
    monkeypatch.setenv("PADDLE_TPU_RANKSTAT_EVERY", "2")
    step, x = _make_step()
    loss = None
    for _ in range(4):
        loss = step(x, x)
    float(loss.item())
    t = paddle.to_tensor(np.ones(64, np.float32))
    dist.all_reduce(t)
    # JSONL roundtrip
    res = profiler.load_profiler_result(str(path))
    assert len(res.steps) == 4
    assert any(r["op"] == "all_reduce" for r in res.collectives)
    assert len(res.rankstats) >= 1
    assert res.rankstats[0]["world_size"] >= 1
    assert "collective records" in res.summary()
    # host_stats.json roundtrip (mirrors how .compiles was added)
    monkeypatch.setenv("PADDLE_PROFILER_DIR", str(tmp_path / "prof"))
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    res2 = profiler.load_profiler_result(str(tmp_path / "prof"))
    assert any(r["op"] == "all_reduce" for r in res2.collectives)
    assert len(res2.rankstats) >= 1


# ------------------------------------------------ obs_report
def test_obs_report_renders_run_summary(tmp_path, monkeypatch):
    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_SAMPLE", "1")
    step, x = _make_step()
    loss = None
    for _ in range(4):
        loss = step(x, x)
    float(loss.item())
    t = paddle.to_tensor(np.ones(64, np.float32))
    dist.all_reduce(t)
    flight_recorder.record_event("straggler", step=4,
                                 straggler_rank=2, step_time_s=0.3,
                                 median_s=0.01, lag_s=0.29, world=4)
    rep = _load_tool("obs_report")
    recs = rep.load_records(str(path))
    text = rep.render(recs)
    assert "== training ==" in text
    assert "4 steps  wall" in text
    assert "== collectives ==" in text
    assert "all_reduce" in text
    assert "STRAGGLER rank 2" in text
    assert "== compiles ==" in text
    # the CLI contract
    assert rep.main([str(path)]) == 0
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert rep.main([str(empty)]) == 2


# ------------------------------------------------ end to end, 4 ranks
@pytest.mark.heavy
def test_four_process_straggler_and_clock_alignment(tmp_path):
    """The acceptance-criteria run: 4 launch.py ranks, a 300 ms
    delay@train.step fault on exactly rank 2 -> rank 0's gather emits
    a straggler event naming rank 2; every rank's JSONL (rankstat
    stream included) is schema-valid; every rank's trace carries a
    measured clock offset within same-host tolerance and the merged
    trace is valid and clock-aligned."""
    logdir = tmp_path / "logs"
    straggler = 2
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "JAX_"))}
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "4", "--log_dir", str(logdir),
         WORKER, str(tmp_path), str(straggler)],
        env=env, cwd=REPO, timeout=420,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    out = proc.stdout.decode(errors="replace")
    if proc.returncode != 0:
        for r in range(4):
            log = logdir / f"workerlog.{r}"
            if log.exists():
                out += f"\n--- workerlog.{r} ---\n" + log.read_text()[-2000:]
    assert proc.returncode == 0, out[-6000:]

    results = {}
    for r in range(4):
        with open(tmp_path / f"rank{r}.json") as f:
            results[r] = json.load(f)
        assert results[r]["world"] == 4
        # same-host clocks: the handshake's measured offsets are small
        assert abs(results[r]["clock_offset_s"]) < 0.5, results[r]
        # every rank produced a schema-shaped rankstat
        assert results[r]["rankstat"]["world_size"] == 4
    assert results[0]["clock_offset_s"] == 0.0  # rank 0 IS the reference
    # the injected delay is visible in the straggler's own telemetry
    assert results[straggler]["rankstat"]["step_time_p50_s"] > 0.25
    others = [results[r]["rankstat"]["step_time_p50_s"]
              for r in range(4) if r != straggler]
    assert max(others) < 0.25, others

    # rank 0's gather named the right rank, and ONLY that rank
    rank0_recs = [json.loads(l) for l in
                  (tmp_path / "metrics.rank0.jsonl").read_text()
                  .splitlines() if l.strip()]
    stragglers = [r for r in rank0_recs
                  if r.get("kind") == "event" and
                  r.get("event") == "straggler"]
    assert stragglers, "no straggler event in rank 0's metrics"
    assert {r["straggler_rank"] for r in stragglers} == \
        {straggler}, stragglers
    assert stragglers[0]["lag_s"] > 0.2

    # schema-valid rankstat stream on every rank
    tool = _load_tool("check_metrics_schema")
    for r in range(4):
        mfile = tmp_path / f"metrics.rank{r}.jsonl"
        recs = [json.loads(l) for l in mfile.read_text().splitlines()
                if l.strip()]
        assert sum(1 for x in recs if x.get("kind") == "rankstat") >= 2
        assert sum(1 for x in recs if x.get("kind") == "collective") >= 1
        assert tool.validate_file(str(mfile)) == [], mfile
    # the launch-propagated gather dir holds all 4 snapshots
    gather = logdir / "rankstat"
    assert {f"rankstat.{r}.json" for r in range(4)} <= \
        set(os.listdir(gather))

    # merged multi-rank trace: valid, clock-aligned, with per-rank pids
    mt = _load_tool("merge_traces")
    merged = str(tmp_path / "merged.json")
    assert mt.main(["-o", merged] +
                   [str(tmp_path / f"trace.rank{r}.json")
                    for r in range(4)]) == 0
    payload = json.load(open(merged))
    assert payload["otherData"]["clock_aligned"] is True
    offs = payload["otherData"]["clock_offsets_s"]
    assert len(offs) == 4 and all(abs(o) < 0.5 for o in offs)
    assert tool.validate_file(merged) == []
    pids = {e.get("pid") for e in payload["traceEvents"]}
    assert len(pids) >= 4  # one process group per rank
