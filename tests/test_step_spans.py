"""The program's own spans on the profiler's clock (ISSUE 25).

`statistic.begin_span` enters a jax.profiler.TraceAnnotation, so every
span of the two hot loops lands in the `/host:CPU` plane of any
jax.profiler trace, on the thread that did the work:

- `TrainStep.__call__` / `HybridTrainStep.__call__`: one parent
  (`train.step` / `fleet.hybrid_step`) and the children that cover it —
  `train.step.prep`, `.dispatch`, `.telemetry`, and no call waits for
  the device;
- `GenerationEngine`'s scheduler thread: `serve.step` and its children,
  the step's sizes readable from the `serve.step.dispatch` event;
- with no profiler session the aggregate tree and the ring hold the same
  spans, and a body that raises leaves the thread's span stack balanced.
"""
import glob

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.framework import fault_injection as fi
from paddle_tpu.jit import TrainStep
from paddle_tpu.profiler import mem_observatory as mobs
from paddle_tpu.profiler import statistic

TRAIN_CHILDREN = ["train.step.prep", "train.step.dispatch",
                  "train.step.telemetry"]
SERVE_CHILDREN = ["serve.step.admit", "serve.step.plan",
                  "serve.step.dispatch", "serve.step.fetch",
                  "serve.step.emit", "serve.step.telemetry"]


@pytest.fixture(autouse=True)
def clean_recorder():
    from paddle_tpu.profiler import flight_recorder
    statistic.reset_statistics()
    flight_recorder.reset()
    yield
    fi.configure("")


def make_step(hidden=64, batch=8):
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, hidden), nn.ReLU(),
                      nn.Linear(hidden, 4))
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda a, b: nn.functional.mse_loss(a, b), o)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 16).astype(np.float32))
    y = paddle.to_tensor(rng.randn(batch, 4).astype(np.float32))
    return step, x, y


def host_lines(trace_dir):
    """[[(name, start_ns, end_ns, stats)] per host thread] of a trace."""
    from jax.profiler import ProfileData
    pb, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    lines = []
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns,
                               e.start_ns + e.duration_ns, dict(e.stats))
                              for e in line.events])
    return lines


def traced(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return host_lines(trace_dir)


def families(lines, parent, children):
    """[(parent event, {child name: [events inside it]})] over the
    thread lines that hold `parent`; every event named in `children`
    on such a line must lie inside some parent."""
    out = []
    for line in lines:
        ps = [e for e in line if e[0] == parent]
        if not ps:
            continue
        kids = [e for e in line if e[0] in children]
        # a parent that was open when the trace started or stopped is
        # not in it, though the children it had inside the trace are
        kids = [k for k in kids if ps[0][1] <= k[1] and k[2] <= ps[-1][2]]
        for p in ps:
            inside = [k for k in kids if p[1] <= k[1] and k[2] <= p[2]]
            out.append((p, {c: [k for k in inside if k[0] == c]
                            for c in children}))
        assert sum(len(v) for _, f in out[-len(ps):] for v in f.values()) \
            == len(kids), "a child event lies outside every parent"
    return out


def ring_children(parent_name):
    """[(parent, [its direct children])] from the recorder's ring."""
    ev = statistic.closed_spans()
    out = []
    for p in (e for e in ev if e["name"] == parent_name):
        lo, hi = p["start_s"], p["start_s"] + p["dur_s"]
        out.append((p, [e for e in ev if e["thread"] == p["thread"]
                        and e["depth"] == p["depth"] + 1
                        and lo <= e["start_s"]
                        and e["start_s"] + e["dur_s"] <= hi]))
    return out


# (a) ------------------------------------------------------------------
def test_train_step_spans_are_host_events_of_a_profiler_trace(tmp_path):
    step, x, y = make_step()
    float(step(x, y))                      # step 1 compiles, untraced

    def three_calls():
        for _ in range(3):
            loss = step(x, y)
        float(loss)

    fams = families(traced(tmp_path, three_calls), "train.step",
                    TRAIN_CHILDREN)
    assert len(fams) == 3
    assert [p[3]["step_num"] for p, _ in fams] == [2, 3, 4]
    for p, kids in fams:
        assert [len(kids[c]) for c in TRAIN_CHILDREN] == [1, 1, 1]
        # in order on the parent's own thread, none inside another
        order = sorted((k for v in kids.values() for k in v),
                       key=lambda k: k[1])
        assert [k[0] for k in order] == TRAIN_CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))


# (b) ------------------------------------------------------------------
def test_serve_step_spans_are_host_events_on_the_scheduler_thread(
        tmp_path):
    from paddle_tpu.inference import GenerationEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, dropout=0.0))
    m.eval()
    eng = GenerationEngine(m, n_pages=64, page_size=4, max_batch=2,
                           max_new_tokens=4)
    try:
        eng.submit(np.array([5, 9, 4])).result(timeout=300)   # compiles

        def two_requests():
            hs = [eng.submit(np.array([8, 1, 2])),
                  eng.submit(np.array([3, 1, 2, 7]))]
            for h in hs:
                h.result(timeout=300)
            # joins the scheduler thread: the last step, still open when
            # its tokens arrive, closes inside the trace
            eng.shutdown()

        lines = traced(tmp_path, two_requests)
    finally:
        eng.shutdown()
    fams = families(lines, "serve.step", SERVE_CHILDREN)
    stepped = [(p, k) for p, k in fams if k["serve.step.dispatch"]]
    assert stepped
    # one scheduler thread: every serve.step is on one line of the trace
    assert sum(1 for line in lines
               if any(e[0] == "serve.step" for e in line)) == 1
    for p, kids in stepped:
        assert [len(kids[c]) for c in SERVE_CHILDREN] \
            == [1, 1, 1, 1, 1, 2]
        s = kids["serve.step.dispatch"][0][3]
        assert 1 <= s["rows"] <= s["bucket_rows"] <= 2
        assert s["rows"] <= s["tokens"] <= s["bucket_tokens"]
    # the prompts' 7 tokens and the decode tokens all went through
    assert sum(k["serve.step.dispatch"][0][3]["tokens"]
               for _, k in stepped) >= 7 + 2 * 3
    # the waits between requests are spans of the same thread, no
    # child of any step
    ring = statistic.closed_spans()
    thread, = {e["thread"] for e in ring if e["name"] == "serve.step"}
    idle = [e for e in ring if e["name"] == "serve.idle"]
    assert idle and all(e["thread"] == thread and e["depth"] == 0
                        for e in idle)


# (c) ------------------------------------------------------------------
def test_without_a_profiler_session_tree_and_ring_hold_the_same_spans():
    step, x, y = make_step()
    for _ in range(3):
        loss = step(x, y)
    float(loss)
    tree = {r["path"]: r["count"] for r in statistic.get_events()}
    assert tree["train.step"] == 3
    for c in ("prep", "dispatch", "telemetry"):
        assert tree[f"train.step/train.step.{c}"] == 3
    assert tree["train.step/train.step.dispatch/jit.compile"] == 1
    fams = ring_children("train.step")
    assert len(fams) == 3
    for _, kids in fams:
        assert [k["name"] for k in kids] == [
            "train.step.prep", "train.step.dispatch",
            "train.step.telemetry"]
    ring = statistic.closed_spans()
    for path, count in tree.items():
        name = path.rsplit("/", 1)[-1]
        assert sum(1 for e in ring if e["name"] == name
                   and e["depth"] == path.count("/")) == count, path


# (d) ------------------------------------------------------------------
def test_children_cover_the_train_step():
    """What `train.step`'s children leave uncovered is the bookkeeping
    between them, some tens of microseconds a call: a sixteenth of a
    call on a model of no size (the CPU's 0.4 ms), under a hundredth
    once the CPU's dispatch takes the milliseconds it takes at these
    shapes."""
    step, x, y = make_step(hidden=2048, batch=2048)
    float(step(x, y))
    statistic.reset_statistics()
    for _ in range(24):
        loss = step(x, y)
    float(loss)
    fams = ring_children("train.step")[-24:]
    assert all([k["name"] for k in kids] == TRAIN_CHILDREN
               for _, kids in fams)
    whole = sum(p["dur_s"] for p, _ in fams)
    covered = sum(k["dur_s"] for _, kids in fams for k in kids)
    assert covered <= whole
    assert covered >= 0.95 * whole, (covered, whole)
    # dispatch_s of the step's telemetry is the dispatch child
    tree = {r["path"]: r for r in statistic.get_events()}
    assert tree["train.step/train.step.dispatch"]["count"] == 24


# (e) ------------------------------------------------------------------
def make_hybrid_step():
    from paddle_tpu.distributed.env import build_mesh
    from paddle_tpu.distributed.fleet.hybrid_train import HybridTrainStep
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = HybridTrainStep(
        m, lambda a, b: nn.functional.mse_loss(a, b), o, build_mesh(dp=8))
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
    return step, x, y


@pytest.mark.parametrize("make", [make_step, make_hybrid_step],
                         ids=["TrainStep", "HybridTrainStep"])
def test_no_call_into_the_step_waits_for_the_device(make, monkeypatch):
    """Twenty calls, the first of which compiles: none asks JAX to wait
    for an array or to fetch one."""
    waits = []

    def counted(name, real):
        def call(*args, **kw):
            waits.append(name)
            return real(*args, **kw)
        return call

    for name in ("block_until_ready", "device_get"):
        monkeypatch.setattr(jax, name, counted(name, getattr(jax, name)))
    step, x, y = make()
    for _ in range(20):
        loss = step(x, y)
    assert waits == []
    float(loss)


# (f) ------------------------------------------------------------------
def stack_is_balanced():
    with statistic.span("after"):
        pass
    last = statistic.closed_spans()[-1]
    return (last["name"], last["depth"]) == ("after", 0)


@pytest.mark.parametrize("exit_", ["oom", "nan", "plain"])
def test_spans_balance_when_the_body_raises(exit_, tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_DEBUG_DUMP", str(tmp_path))
    if exit_ == "plain":
        with pytest.raises(KeyError):
            with statistic.span("outer", step_num=1):
                statistic.begin_span("inner", rows=2)
                try:
                    raise KeyError("boom")
                finally:
                    statistic.end_span()
        assert stack_is_balanced()
        return
    step, x, y = make_step()
    float(step(x, y))
    if exit_ == "oom":
        fi.configure("oom@train.step#1")
        with pytest.raises(mobs.DeviceOOMError):
            step(x, y)
    else:
        # what jax_debug_nans makes the compiled step do on a NaN
        def found_nan(*args):
            raise FloatingPointError("invalid value (nan) encountered")

        (sig, (_, info)), = step._exec.items()
        step._exec[sig] = (found_nan, info)
        with pytest.raises(FloatingPointError):
            step(x, y)
    assert stack_is_balanced()
    # the failed call still closed its parent and its dispatch child
    _, kids = ring_children("train.step")[-1]
    assert [k["name"] for k in kids] == ["train.step.prep",
                                         "train.step.dispatch"]


# the hybrid step: another parent, the same children ---------------------
def test_hybrid_step_has_the_same_children_under_its_own_parent():
    step, x, y = make_hybrid_step()
    for _ in range(2):
        loss = step(x, y)
    float(loss)
    fams = ring_children("fleet.hybrid_step")
    assert len(fams) == 2
    for _, kids in fams:
        assert [k["name"] for k in kids] == TRAIN_CHILDREN
    assert not ring_children("train.step")
