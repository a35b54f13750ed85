"""The program finds its own stalls.

- `jit.api.export_step_metrics` declares a steady step interval over
  twice the median of the last 32 (once 8 are kept) a stall, and emits
  one `kind:"stall"` record saying what covered it; an ordinary step
  emits none;
- every garbage collection is counted and lands in the span ring as a
  `host.gc` span carrying its generation;
- the consumer's wait in the device prefetch ring is the
  `prefetch.wait` span;
- the span ring's `start_ns` maps onto a profiler trace's clock by
  pairing the trace's `bench.step` events with the ring's `train.step`
  spans (benchmarks/readers/host_spans.py, its `idle_unspanned`
  reading); the reader's `stall_ms` and `gc_ms` give the metrics
  `step_stall_ms.train` and `host_gc_ms.train`.
"""
import gc
import importlib.util
import json
import os
import statistics
import time
import types

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.io.device_prefetch import device_prefetch_iterator
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit import api as jit_api
from paddle_tpu.profiler import flight_recorder, monitor, statistic

from benchmarks.lib import xplane
from benchmarks.readers import host_spans, span_ms

from test_step_spans import host_lines

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
BASE_S = 0.02          # the loop's own work a step: a steady interval


@pytest.fixture(autouse=True)
def clean_recorder():
    statistic.reset_statistics()
    flight_recorder.reset()
    monitor.reset_metrics()
    yield


def make_step():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda a, b: nn.functional.mse_loss(a, b), o)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
    float(step(x, y))                      # compiles: not a steady step
    return step, x, y


def stall_records():
    return [r for r in flight_recorder.snapshot()["records"]
            if r.get("kind") == "stall"]


def loop(step, x, y, n, sleep_at=None, sleep_s=0.0):
    for i in range(n):
        with statistic.span("test.work"):
            time.sleep(BASE_S)
        if i == sleep_at:
            with statistic.span("test.injected"):
                time.sleep(sleep_s)
        step(x, y)


def test_one_injected_sleep_is_one_stall_covered_by_its_span():
    step, x, y = make_step()
    sleep_s = 10 * BASE_S
    loop(step, x, y, 30, sleep_at=20, sleep_s=sleep_s)
    recs = stall_records()
    assert len(recs) == 1, [r["interval_s"] for r in recs]
    r = recs[0]
    assert r["step"] == 2 + 20        # the call after the sleep
    excess = r["interval_s"] - r["median_s"]
    assert excess == pytest.approx(sleep_s, rel=0.2)
    assert r["interval_s"] > jit_api.STALL_FACTOR * r["median_s"]
    mine = r["covered"]["step_thread"]
    assert mine["test.injected"] == pytest.approx(sleep_s, rel=0.2)
    assert max(mine, key=mine.get) == "test.injected"
    assert r["end_ns"] - r["start_ns"] == pytest.approx(
        r["interval_s"] * 1e9, abs=1)
    # the loop's own work and the step's spans cover nearly everything
    assert 0 <= r["uncovered_s"] < 0.1 * r["interval_s"]
    assert r["cpu_s"] < 0.5 * r["interval_s"]      # it slept
    assert r["gc_s"] >= 0
    snap = monitor.metrics_snapshot()
    assert snap["train.stalls"] == 1
    assert snap["train.stall_s"] == pytest.approx(excess)


def _schema_tool():
    spec = importlib.util.spec_from_file_location(
        "check_metrics_schema", os.path.join(TOOLS, "check_metrics_schema.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_stall_records_pass_the_schema_tool_and_drift_does_not(
        tmp_path, monkeypatch):
    path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("PADDLE_TPU_METRICS_FILE", str(path))
    step, x, y = make_step()
    loop(step, x, y, 14, sleep_at=10, sleep_s=10 * BASE_S)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    stalls = [r for r in recs if r["kind"] == "stall"]
    assert len(stalls) == 1
    tool = _schema_tool()
    assert tool.validate_file(str(path)) == []
    good = stalls[0]
    bad = [dict(good, interval_s=good["median_s"] * 1.5),     # no stall
           dict(good, end_ns=good["start_ns"]),
           dict(good, cpu_s=-1.0),
           dict(good, uncovered_s=good["interval_s"] * 2),
           dict(good, covered={"step_thread": {"x": -1.0}}),
           {k: v for k, v in good.items() if k != "gc_s"}]
    for rec in bad:
        assert tool.validate_line(json.dumps(rec)), rec
    # obs_report prints the stall under the training rollup
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(TOOLS, "obs_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    text = rep.render(recs)
    train = text[text.index("== training =="):]
    assert f"stall at step {good['step']}:" in train
    assert "step thread: test.injected" in train


def test_a_steady_loop_has_no_stall():
    step, x, y = make_step()
    loop(step, x, y, 30)
    assert stall_records() == []
    assert "train.stalls" not in monitor.metrics_snapshot()
    # every steady interval was kept, the newest 32 at most
    assert 8 <= len(step._steady_intervals) <= jit_api.STALL_KEEP


def test_the_median_is_taken_every_eight_intervals_and_before_a_stall(
        monkeypatch):
    # steady calls whose intervals are set by hand: a clock that moves
    # only when told, and a median that counts its calls
    clock, medians = [0], []
    monkeypatch.setattr(jit_api, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: clock[0], process_time=time.process_time))
    monkeypatch.setattr(jit_api, "statistics", types.SimpleNamespace(
        median=lambda kept: medians.append(len(kept)) or
        statistics.median(kept)))
    step = types.SimpleNamespace(_step_i=0)
    info = {"lower_s": 0.0, "compile_s": 0.0}

    def call(interval_ms):
        clock[0] += int(interval_ms * MS)
        step._step_i += 1
        jit_api.export_step_metrics(step, 0.0, info, False)

    for _ in range(8):            # the first call has no interval
        call(10)
    assert medians == [] and step._stall_median is None
    call(10)                      # 8 kept: the first median
    assert medians == [8] and step._stall_median == pytest.approx(0.010)
    for _ in range(7):            # ordinary steps take no median
        call(19)
    assert medians == [8]
    call(19)                      # 16 kept: 8 of 10 ms, 8 of 19
    assert medians == [8, 16] and step._stall_median == pytest.approx(0.0145)
    for _ in range(6):
        call(19)
    # over twice the cached median (29 ms) but not twice the median
    # taken afresh (19 ms): no stall
    call(32)
    assert medians == [8, 16, 22] and stall_records() == []
    call(40)                      # over twice 19 ms: a stall
    recs = stall_records()
    assert len(recs) == 1
    assert recs[0]["median_s"] == pytest.approx(0.019)
    assert recs[0]["interval_s"] == pytest.approx(0.040)
    assert medians == [8, 16, 22, 23, 24]   # and the 8th since: refreshed


def test_stall_record_reads_the_ring_between_two_stamps():
    t0 = time.perf_counter_ns()
    with statistic.span("outer"):
        with statistic.span("inner"):
            time.sleep(0.01)
        time.sleep(0.01)
    time.sleep(0.01)                         # in no span
    with statistic.span("open"):
        now = (time.perf_counter_ns(), time.process_time(),
               statistic.gc_pause_s())
        rec = jit_api.stall_record(7, (t0, now[1], now[2]), now, 0.001)
    mine = rec["covered"]["step_thread"]
    assert set(mine) == {"outer", "inner", "open"}
    assert mine["outer"] == pytest.approx(0.02, abs=0.005)
    assert mine["inner"] == pytest.approx(0.01, abs=0.005)
    assert rec["uncovered_s"] == pytest.approx(0.01, abs=0.005)
    assert rec["covered"]["other_threads"] == {}
    assert rec["step"] == 7 and rec["cpu_s"] == 0 and rec["gc_s"] == 0


def test_a_collection_is_a_host_gc_span_and_counted():
    step, x, y = make_step()
    for i in range(4):
        if i == 2:
            before = monitor.metrics_snapshot()
            paused = statistic.gc_pause_s()
            with statistic.span("test.collect"):
                gc.collect()
            after = monitor.metrics_snapshot()
            pause = statistic.gc_pause_s() - paused
        step(x, y)
    assert after["gc.collections.gen2"] == before.get(
        "gc.collections.gen2", 0) + 1
    assert after["gc.pause_s.gen2"] - before.get("gc.pause_s.gen2", 0.0) \
        == pytest.approx(pause)
    assert pause > 0
    spans = statistic.closed_spans()
    outer = next(s for s in spans if s["name"] == "test.collect")
    g = [s for s in spans if s["name"] == "host.gc"
         and s["thread"] == outer["thread"]
         and outer["start_ns"] <= s["start_ns"]
         and s["start_s"] + s["dur_s"] <= outer["start_s"] + outer["dur_s"]]
    assert [s["args"] for s in g] == [{"gen": 2}]
    assert g[0]["depth"] == outer["depth"] + 1
    assert g[0]["dur_s"] == pytest.approx(pause)
    # a collection is in neither the aggregate tree nor a trace
    assert statistic.get_events("host.gc") == []


def test_a_slow_producer_shows_in_prefetch_wait():
    delay = 0.03

    def source():
        for i in range(8):
            time.sleep(delay)
            yield (np.full((4,), i, np.float32),)

    got = [b for b in device_prefetch_iterator(source(), depth=1)]
    assert len(got) == 8
    spans = statistic.closed_spans()
    waits = [s for s in spans if s["name"] == "prefetch.wait"]
    assert len(waits) == 9            # 8 batches and the end of the stream
    # the metric prefetch_wait_ms.train: the mean over the last n waits
    ms = span_ms.mean_ms(spans, "prefetch.wait", None, 8)
    assert 0.7 * delay * 1e3 <= ms <= 2.0 * delay * 1e3


def test_the_ring_maps_onto_a_profiler_trace(tmp_path):
    step, x, y = make_step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    n = 8
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # the session's first events come late: three steps before the
        # wrapped ones, as the benchmark's window follows its set-up
        for _ in range(3):
            step(x, y)
        for _ in range(n):
            with jax.profiler.TraceAnnotation("bench.step"):
                loss = step(x, y)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    events = [e for line in host_lines(tmp_path) for e in line]
    bench = [e[:3] for e in events if e[0] == "bench.step"]
    assert len(bench) == n
    spans = statistic.closed_spans()
    off, worst = host_spans.pairing(spans, bench)
    assert worst <= host_spans.MAX_RESIDUAL_NS
    names = ("train.step", "train.step.prep", "train.step.dispatch",
             "train.step.telemetry")
    traced = {m: sorted(e[1] for e in events if e[0] == m)[-n:]
              for m in names}
    ring = {m: [s["start_ns"] for s in spans if s["name"] == m][-n:]
            for m in names}
    for m in names:
        assert len(traced[m]) == n
        for t, r in zip(traced[m], ring[m]):
            assert abs(r + off - t) <= 50_000, (m, r + off - t)


# ---- the reader on synthetic rings and traces ----------------------------
MS = 1_000_000


def ring(starts_ms, dur_ms=1.0, thread=1, extra=()):
    spans = [{"name": "train.step", "start_ns": int(s * MS),
              "start_s": s * 1e-3, "dur_s": dur_ms * 1e-3,
              "thread": thread, "depth": 0} for s in starts_ms]
    for name, s, d, tid in extra:
        spans.append({"name": name, "start_ns": int(s * MS),
                      "start_s": s * 1e-3, "dur_s": d * 1e-3,
                      "thread": tid, "depth": 0})
    return spans


def stall(start_ms, end_ms, median_ms):
    return {"kind": "stall", "start_ns": int(start_ms * MS),
            "end_ns": int(end_ms * MS),
            "interval_s": (end_ms - start_ms) * 1e-3,
            "median_s": median_ms * 1e-3}


def test_stall_ms_sums_the_program_stalls_inside_the_window():
    # steps every 10 ms from 0 to 190 ms, 1 ms each; the window is the
    # last 10 (100 to 191 ms)
    spans = ring([10 * i for i in range(20)])
    inside = [stall(120.5, 160.5, 10), stall(170.5, 190.5, 10)]
    # an older stall, and the one whose interval runs from before the
    # window's first step into it (the set-up's), are not read
    before = [stall(20.5, 60.5, 10), stall(90.5, 100.5, 4)]
    assert host_spans.stall_ms(spans, inside + before, 10) \
        == pytest.approx(30.0 + 10.0)
    assert host_spans.stall_ms(spans, [], 10) == 0.0
    assert host_spans.stall_ms(spans, before, 10) == 0.0
    # an older program's ring has no start_ns
    old = [{k: v for k, v in s.items() if k != "start_ns"} for s in spans]
    assert host_spans.stall_ms(old, inside, 10) is None


def test_stall_ms_reads_the_program_own_stall():
    step, x, y = make_step()
    sleep_s = 10 * BASE_S
    loop(step, x, y, 30, sleep_at=20, sleep_s=sleep_s)
    ctx = {"window": {"steps": 20}}
    got = host_spans.read(ctx, "stall_ms")
    excess = monitor.metrics_snapshot()["train.stall_s"]
    assert got == pytest.approx(1e3 * excess)
    assert got == pytest.approx(1e3 * sleep_s, rel=0.2)
    # a window of the steps after the stall holds none
    assert host_spans.read({"window": {"steps": 5}}, "stall_ms") == 0.0


def test_gc_ms_sums_the_collections_inside_the_steps():
    extra = [("host.gc", 5, 2.0, 1), ("host.gc", 15, 1.0, 2),   # inside
             ("host.gc", -5, 3.0, 1), ("host.gc", 50, 4.0, 1)]  # outside
    spans = ring([0, 10, 20, 30], extra=extra)
    assert host_spans.gc_ms(spans, 4) == pytest.approx(3.0 / 4)
    assert host_spans.gc_ms(ring([0, 10]), 2) == 0.0
    assert host_spans.gc_ms([], 4) is None


def synthetic_trace(offset_ms, jitter_ms=(0, 0, 0)):
    """Three steps at 0, 10, 20 ms of the ring's clock, seen in a trace
    whose clock is offset_ms ahead; the device busy 1-8, 11-18, 21-28;
    bench.step events 0.01 ms before each train.step, and a bench.wait
    to 30 ms."""
    ops = {"/device:TPU:0": [("fusion.1", (s + 1 + offset_ms) * MS,
                              (s + 8 + offset_ms) * MS)
                             for s in (0, 10, 20)]}
    host = [("bench.step", (s - 0.01 + offset_ms + j) * MS,
             (s + 0.5 + offset_ms) * MS)
            for s, j in zip((0, 10, 20), jitter_ms)]
    host.append(("bench.wait", (20.5 + offset_ms) * MS,
                 (30 + offset_ms) * MS))
    return xplane.Trace(ops, {}, host, 0.03)


def test_idle_unspanned_maps_the_ring_through_the_pairing():
    off = 123456.0
    tr = synthetic_trace(off)
    # idle: -0.01-1 (from the first bench event), 8-11, 18-21 and 28-30
    # ms: 9.01 ms; mapped 0.01 ms early by the pairing's lead, the step's
    # thread is in train.step over -0.01-0.99, 9.99-10.99, 19.99-20.99
    # and in "wait" over 7.99-9.49; another thread's span counts for
    # nothing
    spans = ring([0, 10, 20], extra=[("wait", 8, 1.5, 1),
                                     ("other", 0, 30, 2)])
    mapped = host_spans.pairing(spans, [h for h in tr.host
                                        if h[0] == "bench.step"])
    assert mapped[0] == pytest.approx(off * MS - 0.01 * MS)
    assert mapped[1] == pytest.approx(0, abs=1)
    # covered: 1 + (1.49 + 1) + 1 of the 9.01 ms
    got = host_spans.idle_unspanned(spans, tr)
    assert got == pytest.approx(100 * (9.01 - 4.49) / 9.01, rel=1e-6)


def test_a_bad_pairing_attributes_nothing():
    # one bench.step 0.2 ms off its step: the residual is over 100 us
    tr = synthetic_trace(5.0, jitter_ms=(0, 0.2, 0))
    spans = ring([0, 10, 20])
    assert host_spans.pairing(spans, [h for h in tr.host
                                      if h[0] == "bench.step"]) is None
    assert host_spans.idle_unspanned(spans, tr) is None
    # a ring with fewer steps than the trace's bench.step events
    assert host_spans.idle_unspanned(ring([0, 10]), synthetic_trace(5.0)) \
        is None
    assert host_spans.idle_unspanned(spans, None) is None
