"""The runner's whole control flow on the CPU, on tiny copies of each
configuration with the kernels in interpret mode: the last line's keys,
a sound run judged correct, each fault a cell can have judged not
correct, and the refusal to measure without a TPU. The tests steer the
runner from here (tests/tiny.py); the runner has no option for it."""
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import program as P
from benchmarks.lib import xplane
from benchmarks.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
# BENCHMARK.json's cells, and the rehearsal's own serving cell
MANIFEST = tiny.manifest_with_serve_cell(MANIFEST, tiny.serve_cell())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
TRAIN = [c for c in CELLS if ".train." in c]
SERVE = [c for c in CELLS if ".serve." in c]
SEED = 3000000019            # the driver's seeds pass 2**31
# limits for the TINY copies (bf16 or default-precision float32 against
# the float32 reference at hidden 64): sound runs read well under them
TINY_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
               "grad_norm_gap": 0.01, "change_norm_gap": 0.2,
               "max_logit_gap": 1e-4}
RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "small.xplane.pb")


@pytest.fixture
def cpu_runner(monkeypatch):
    tiny.patch(monkeypatch, limits=TINY_LIMITS)
    # a CPU trace holds no device plane: the traced run reads the
    # recorded chip trace instead
    from benchmarks.lib.tracing import Tracer
    monkeypatch.setattr(
        Tracer, "reduce",
        lambda self: xplane.Trace.from_file(RECORDED, self.window_s))


def check_line(line, metrics):
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    assert set(line["metrics"]) <= set(metrics) and line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    json.dumps(line)


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(cpu_runner, cell):
    line = run.run_cell(cell, SEED, 1.5, 0)
    want = [m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])]
    check_line(line, want)
    assert set(line["metrics"]) == set(want)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line(cpu_runner, cell):
    line = run.run_cell(cell, SEED + 1, 1.5, 1)
    want = [m["name"] for m in MANIFEST["per_layer"]
            if cell in m.get("workloads", [cell])]
    check_line(line, want)
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10
    # every per-layer metric but the kernels' rooflines (the recorded
    # trace holds none of this cell's kernels, so those readers find
    # nothing and the harness leaves them out; never a 0)
    missing = set(want) - set(line["metrics"])
    assert all("roofline" in m for m in missing), missing


def test_no_tpu_is_refused(capsys):
    with pytest.raises(P.NoChip) as e:
        P.require_tpu(1)
    assert e.value.code == 3
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code == 3
    assert capsys.readouterr().out == ""


# ---- the faults a cell can have: each must come out not correct -------
class FrozenStep:
    """A step that computes its loss and returns its state unchanged."""

    def __init__(self, step):
        self._step = step

    def __getattr__(self, name):
        return getattr(self._step, name)

    def __call__(self, *batch):
        import jax
        import jax.numpy as jnp
        s = self._step
        params = jax.tree.map(jnp.copy, s.params)
        state = jax.tree.map(jnp.copy, s.opt_state)
        loss = s(*batch)
        float(loss.item())
        s.set_tree_state(params=params, opt_state=state)
        return loss


def half_batch_loss(logits, labels):
    from benchmarks.lib import train
    half = logits.shape[0] // 2
    return train._whole_batch_loss(logits[:half], labels[:half])


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(cpu_runner, monkeypatch, cell, fault):
    from benchmarks.lib import train
    if fault == "state_unchanged":
        build = train.build_step
        monkeypatch.setattr(train, "build_step",
                            lambda *a: FrozenStep(build(*a)))
    else:
        monkeypatch.setattr(train, "_whole_batch_loss", train.loss_fn,
                            raising=False)
        monkeypatch.setattr(train, "loss_fn", half_batch_loss)
    line = run.run_cell(cell, SEED + 2, 1.0, 0)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", SERVE)
def test_altered_token_is_not_correct(cpu_runner, monkeypatch, cell):
    from paddle_tpu.inference.serving import GenerationEngine
    emit = GenerationEngine._emit

    def altered(self, seq, tok):       # every 5th token, where produced
        if len(seq.generated) % 5 == 4:
            tok = (int(tok) + 1) % self.model.cfg.vocab_size
        return emit(self, seq, tok)

    monkeypatch.setattr(GenerationEngine, "_emit", altered)
    line = run.run_cell(cell, SEED + 3, 1.5, 0)
    assert line["correct"] is False, line["compared"]
