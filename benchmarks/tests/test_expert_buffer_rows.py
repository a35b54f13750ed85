"""`expert_buffer_rows_over_held.train` (readers/expert_buffer_rows_over_
held.py): nothing on a program that lacks the counter, as the parent of
the PR that brought it does; the ratio of the two sums where it has it;
and the traced line of both expert-layer cells carries it, on
tests/tiny.py's tiny copies."""
import pytest

from benchmarks import run
from benchmarks.readers import expert_buffer_rows_over_held as R
from benchmarks.tests.test_rehearsal import (  # noqa: F401 (fixture)
    SEED, cpu_runner)

METRIC = "expert_buffer_rows_over_held.train"


def test_nothing_without_the_counter_and_the_ratio_with_it():
    from paddle_tpu.profiler import monitor
    monitor.reset_metrics()
    assert R.read({}) is None
    # the parent: it counts the assignments, not the rows
    monitor.counter("moe.local_assignments").inc(4096)
    assert R.read({}) is None
    monitor.counter("moe.buffer_rows").inc(5120)
    assert R.read({}) == 1.25
    monitor.counter("moe.buffer_rows").inc(32768)
    monitor.counter("moe.local_assignments").inc(6000)
    assert R.read({}) == (5120 + 32768) / (4096 + 6000)
    monitor.reset_metrics()
    # a layer that saw no assignment to a held expert divides by nothing
    monitor.counter("moe.buffer_rows").inc(5120)
    monitor.counter("moe.local_assignments").inc(0)
    assert R.read({}) is None
    monitor.reset_metrics()


@pytest.mark.parametrize("cell", ["glm-4.7-flash-ep8.train.b2-s4096",
                                  "smallthinker-21b-ep8.train.b1-s16384"])
def test_traced_line_of_both_expert_cells_reports_it(cpu_runner, cell):
    line = run.run_cell(cell, SEED + 34, 1.5, 1)
    assert line["correct"] is True, line["compared"]
    got = line["metrics"][METRIC]
    assert got["unit"] == "ratio"
    # at least a row an assignment; at most router experts over held
    assert 1.0 <= got["value"] <= 8.0
