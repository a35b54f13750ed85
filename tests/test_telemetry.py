"""Framework-wide telemetry (ISSUE 2): host span statistics, the metrics
registry + JSONL export, XLA cost-analysis FLOPs/MFU, and the
launch-env satellites.

Proof points:
- RecordEvent spans nest and aggregate correctly (counts, parent paths,
  thread merging).
- The metrics JSONL is valid one-object-per-line, rank-tagged, and
  passes tools/check_metrics_schema.py (the bench/driver contract).
- Profiler.summary() contains the framework-emitted span rows (compile,
  step, dataloader, collective, memory) after a jit train step.
- cost_analysis FLOPs for a known matmul match the 2·M·N·K closed form.
- load_profiler_result returns a queryable object (no more
  NotImplementedError).
- launch: no forced coordinator env for a 1-process world; --devices
  partitions per local rank.
"""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu import profiler
from paddle_tpu.jit import TrainStep
from paddle_tpu.profiler import statistic, monitor, cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_schema_tool():
    path = os.path.join(REPO, "tools", "check_metrics_schema.py")
    spec = importlib.util.spec_from_file_location("check_metrics_schema",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    statistic.reset_statistics()
    monitor.reset_metrics()
    yield


def _make_step():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, nn.CrossEntropyLoss(), o)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(8, 8).astype(np.float32))
    y = paddle.to_tensor(np.arange(8, dtype=np.int64) % 4)
    return step, x, y


# --------------------------------------------------- span statistics
def test_spans_nest_and_aggregate():
    with profiler.RecordEvent("outer"):
        with profiler.RecordEvent("inner"):
            pass
        with profiler.RecordEvent("inner"):
            pass
    with profiler.RecordEvent("outer"):
        pass
    outer = statistic.get_events("outer")
    inner = statistic.get_events("inner")
    assert len(outer) == 1 and outer[0]["count"] == 2
    assert len(inner) == 1 and inner[0]["count"] == 2
    assert inner[0]["path"] == "outer/inner"
    # parent total covers children
    assert outer[0]["total_s"] >= inner[0]["total_s"]


def test_record_span_merges_threads():
    def worker():
        with statistic.span("shared"):
            with statistic.span("leaf"):
                time.sleep(0.001)

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with statistic.span("shared"):
        with statistic.span("leaf"):
            time.sleep(0.002)
    shared = statistic.get_events("shared")[0]
    leaf = statistic.get_events("leaf")[0]
    assert shared["count"] == 4 and leaf["count"] == 4
    assert leaf["path"] == "shared/leaf"
    # the snapshot tree keeps the set of thread idents that hit a node
    # (a finished thread's ident may be reused, so >= 2 not == 4)
    tree = {n["name"]: n for n in statistic.snapshot()}
    assert len(tree["shared"]["threads"]) >= 2


def test_summary_table_renders_sorted():
    with statistic.span("small"):
        time.sleep(0.005)
    with statistic.span("big"):
        time.sleep(0.05)
    table = statistic.summary_table(time_unit="ms")
    assert "Total(ms)" in table
    assert table.index("big") < table.index("small")  # sorted by total
    big = statistic.get_events("big")[0]["total_s"]
    assert big >= 0.05 and f"{big * 1e3:.3f}" in table


# --------------------------------------------------- metrics registry
def test_metrics_registry_kinds():
    monitor.counter("t.calls").inc()
    monitor.counter("t.calls").inc(4)
    monitor.gauge("t.gauge").set(2.5)
    for v in (0.1, 0.3):
        monitor.histogram("t.hist").observe(v)
    snap = monitor.metrics_snapshot()
    assert snap["t.calls"] == 5
    assert snap["t.gauge"] == 2.5
    assert snap["t.hist"]["count"] == 2
    assert abs(snap["t.hist"]["avg"] - 0.2) < 1e-9
    with pytest.raises(TypeError):
        monitor.gauge("t.calls")  # kind conflict must be loud


def test_rank_comes_from_launch_env(tmp_path, monkeypatch):
    path = tmp_path / "m.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    assert monitor.export_step({"k": 1}, kind="custom")
    rec = json.loads(path.read_text().strip())
    assert rec["rank"] == 3 and rec["kind"] == "custom" and rec["k"] == 1
    monkeypatch.delenv("PADDLE_TPU_METRICS_FILE")
    assert not monitor.export_step({"k": 1})  # off without the env var


# --------------------------------------------- per-step JSONL export
def test_train_step_emits_valid_schema_jsonl(tmp_path, monkeypatch):
    path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    step, x, y = _make_step()
    for _ in range(3):
        float(step(x, y).item())
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    all_recs = [json.loads(l) for l in lines]
    # one step record per optimizer step, plus exactly one
    # kind:"compile" ledger record for the single cold compile
    # (profiler/compile_observatory.py)
    recs = [r for r in all_recs if r["kind"] == "step"]
    assert len(recs) == 3
    compiles = [r for r in all_recs if r["kind"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["tag"] == "train.step"
    for i, rec in enumerate(recs):
        assert rec["kind"] == "step" and rec["rank"] == 0
        assert rec["step"] == i + 1
        assert rec["flops"] > 0          # XLA cost analysis on CPU works
        assert rec["peak_bytes"] > 0
    assert recs[0]["compile_s"] > 0 and not recs[0]["cache_hit"]
    assert recs[1]["compile_s"] == 0.0 and recs[1]["cache_hit"]
    # the contract's enforcement point: the documented schema tool
    tool = _load_schema_tool()
    assert tool.validate_file(str(path)) == []
    assert tool.main([str(path)]) == 0


def test_schema_tool_rejects_drift(tmp_path):
    tool = _load_schema_tool()
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"ts": 1, "rank": 0, "kind": "step", "step": 1}\n'
                   "not json\n")
    errors = tool.validate_file(str(bad))
    assert any("step_time_s" in e for e in errors)
    assert any("not valid JSON" in e for e in errors)
    assert tool.main([str(bad)]) == 1
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert tool.validate_file(str(empty))


# ------------------------------------------- summary after a jit step
def test_summary_contains_framework_spans():
    import paddle_tpu.distributed as dist
    from paddle_tpu.io import DataLoader, TensorDataset

    step, x, y = _make_step()
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    ds = TensorDataset([np.arange(16, dtype=np.float32).reshape(8, 2)])
    for _ in DataLoader(ds, batch_size=4):
        pass
    dist.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
    float(step(x, y).item())
    paddle.device.max_memory_allocated()
    prof.step()
    prof.stop()
    text = prof.summary()
    for span_name in ("train.step", "train.step.dispatch",
                      "jit.trace_lower", "jit.compile",
                      "dataloader.next", "collective.all_reduce",
                      "device.memory"):
        assert span_name in text, f"summary missing {span_name}:\n{text}"
    # registry section rides along
    assert "jit.retraces" in text and "train.flops_per_step" in text


# ------------------------------------------------------ cost analysis
def test_matmul_flops_match_closed_form():
    import jax
    import jax.numpy as jnp
    M, N, K = 16, 32, 64
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((M, K), jnp.float32),
        jnp.ones((K, N), jnp.float32)).compile()
    ca = cost.cost_analysis(compiled)
    assert ca["flops"] == 2 * M * N * K
    assert cost.executable_flops(compiled) == 2 * M * N * K
    assert cost.executable_bytes(compiled) > 0


def test_train_step_cost_analysis_free_after_run():
    step, x, y = _make_step()
    float(step(x, y).item())
    retraces = step.retraces
    ca = step.cost_analysis(x, y)     # cached executable: no new compile
    assert step.retraces == retraces
    assert ca["flops"] > 0 and step.flops(x, y) > 0


def test_mfu_helper():
    assert cost.mfu(0.0, 1.0, 1e12) == 0.0
    assert cost.mfu(5e11, 1.0, 1e12) == 0.5
    assert cost.mfu(5e11, 0.0, 1e12) == 0.0
    assert cost.mfu(5e11, 1.0, 0.0) == 0.0  # unknown peak (CPU)


# --------------------------------------------- load_profiler_result
def test_load_profiler_result_roundtrip(tmp_path):
    with profiler.RecordEvent("phase_a"):
        with profiler.RecordEvent("phase_b"):
            pass
    monitor.counter("c").inc(7)
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.step()
    prof.stop()
    path = prof.export_host_stats(str(tmp_path / "host_stats.json"))
    result = profiler.load_profiler_result(path)
    assert result.get("phase_b")[0]["count"] == 1
    assert result.get("phase_b")[0]["path"] == "phase_a/phase_b"
    assert result.total_s("phase_a") > 0
    assert result.metrics["c"] == 7
    assert "phase_a" in result.summary()


def test_load_profiler_result_reads_metrics_jsonl(tmp_path, monkeypatch):
    path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    step, x, y = _make_step()
    float(step(x, y).item())
    float(step(x, y).item())
    result = profiler.load_profiler_result(str(path))
    assert len(result.steps) == 2
    assert result.steps[1]["cache_hit"] is True


# --------------------------------------------------- launch satellites
def _launch_args(**kw):
    from paddle_tpu.distributed.launch import _parse
    argv = []
    for k, v in kw.items():
        argv += [f"--{k}", str(v)]
    return _parse(argv + ["train.py"])


def test_single_rank_gang_gets_no_coordinator_env():
    """nnodes*nproc == 1 must keep the single-controller init path: no
    forced PADDLE_TPU_COORDINATOR/NUM_PROCESSES (round-5 advisor)."""
    from paddle_tpu.distributed.launch import _rank_env
    env = _rank_env(_launch_args(), "127.0.0.1:5000", 0, 0)
    assert "PADDLE_TPU_COORDINATOR" not in env
    assert "PADDLE_TPU_NUM_PROCESSES" not in env
    assert "PADDLE_TPU_PROCESS_ID" not in env
    assert env["PADDLE_TRAINER_ID"] == "0"      # reference env still set
    assert env["PADDLE_TRAINERS_NUM"] == "1"


def test_multi_rank_gang_keeps_coordinator_env():
    from paddle_tpu.distributed.launch import _rank_env
    env = _rank_env(_launch_args(nproc_per_node=2), "127.0.0.1:5000", 1, 0)
    assert env["PADDLE_TPU_COORDINATOR"] == "127.0.0.1:5000"
    assert env["PADDLE_TPU_NUM_PROCESSES"] == "2"
    assert env["PADDLE_TPU_PROCESS_ID"] == "1"


def test_devices_partition_per_local_rank():
    from paddle_tpu.distributed.launch import _rank_env
    args = _launch_args(nproc_per_node=2, devices="0,1,2,3")
    env0 = _rank_env(args, "127.0.0.1:5000", 0, 0)
    env1 = _rank_env(args, "127.0.0.1:5000", 1, 0)
    assert env0["PADDLE_VISIBLE_DEVICES"] == "0,1"
    assert env1["PADDLE_VISIBLE_DEVICES"] == "2,3"


def test_devices_indivisible_is_loud():
    from paddle_tpu.distributed.launch import _rank_devices
    with pytest.raises(SystemExit):
        _rank_devices("0,1,2", 2, 0)


def test_visible_devices_consumed_before_backend_init(monkeypatch):
    from paddle_tpu.distributed.env import _apply_visible_devices
    monkeypatch.setenv("PADDLE_VISIBLE_DEVICES", "2,3")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    _apply_visible_devices()
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2,3"
    assert os.environ["CUDA_VISIBLE_DEVICES"] == "2,3"
    # an explicitly set backend var wins over the paddle one
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    _apply_visible_devices()
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0"
