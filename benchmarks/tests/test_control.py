"""The control of each kind of cell, at a size a test run can hold: the
plain reference put in the program's place and computed in the nearest
precision below the one the configuration states must come out as NOT
correct, where the reference itself (and, in test_rehearsal.py, the
program) comes out correct. The readings at the cells' own sizes, on the
chip, are in PERF.md; tools/calibrate.py takes them."""
import pytest

from benchmarks.lib import correct as C
from benchmarks.lib import program as P
from benchmarks.references.common import CONTROL_OF
from benchmarks.tests import tiny
from benchmarks.tests.test_rehearsal import SERVE, TINY_LIMITS, TRAIN


@pytest.mark.parametrize("cell_name", TRAIN)
def test_train_control_is_not_correct(monkeypatch, cell_name):
    from benchmarks.lib.reftrain import reference_train
    from benchmarks.lib.train import CHECK_STEPS, make_batches
    tiny.patch(monkeypatch, limits=TINY_LIMITS)
    cell, config, _, _ = P.load_cell(cell_name)
    ref_mod = P.reference_of(config)
    batches = make_batches(5, config["vocab_size"], cell["batch"],
                           cell["seq"], CHECK_STEPS)
    run = lambda **kw: reference_train(ref_mod, config, 5, batches,
                                       cell["optimizer"], micro=1, **kw)
    ref = run()
    same, _ = C.train_numbers(run(), ref)
    assert C.judge(same, cell["correct"]["limits"])[0]
    control, _ = C.train_numbers(run(prec=CONTROL_OF[config["dtype"]]), ref)
    ok, rows = C.judge(control, cell["correct"]["limits"])
    assert not ok, rows
    halved, _ = C.train_numbers(run(fault="half_batch"), ref)
    assert not C.judge(halved, cell["correct"]["limits"])[0]


@pytest.mark.parametrize("cell_name", SERVE)
def test_serve_control_is_not_correct(monkeypatch, cell_name):
    import numpy as np
    from benchmarks.lib.serve import reference_gaps
    tiny.patch(monkeypatch, limits=TINY_LIMITS)
    cell, config, _, _ = P.load_cell(cell_name)
    # at hidden 64 the tied embedding makes every token predict itself
    # by a wide margin; a wide vocabulary brings back the near-ties that
    # the full-size model has, and that a lower precision flips
    config["vocab_size"] = 8192
    # positions to judge: random contexts, as the first served tokens
    # after a random prompt are. The reference itself in the program's
    # place puts its own best token first everywhere; the control flips
    # some near-ties.
    rng = np.random.RandomState(5)
    sample = [(rng.randint(0, config["vocab_size"], size=8),
               rng.randint(0, config["vocab_size"], size=56))
              for _ in range(8)]
    limits = cell["correct"]["limits"]
    gaps, exact = reference_gaps(config, 5, sample, 64, 56, control="f32")
    assert exact == len(gaps) == 8 * 56
    assert C.judge(C.serve_numbers(gaps), limits)[0]
    gaps, exact = reference_gaps(config, 5, sample, 64, 56,
                                 control=CONTROL_OF[config["dtype"]])
    ok, rows = C.judge(C.serve_numbers(gaps), limits)
    assert not ok, (rows, exact)
