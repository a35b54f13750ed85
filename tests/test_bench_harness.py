"""bench.py harness robustness (round-5): the headline JSON line must
survive the driver killing the process at any point after measurement
(BENCH_r04.json recorded rc=124 with zero output; the contract now is
tee-on-measure). Runs the real bench.py CPU smoke path in a subprocess.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.heavy  # slow-compiling: tier-1 yes, quick commit gate no

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _bench_env(tmp_path, hold=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONUNBUFFERED": "1",
        "BENCH_1P3B": "0",
        # a private cache dir: the test must not warm/poison the repo one
        "PADDLE_TPU_COMPILE_CACHE": str(tmp_path / "xla_cache"),
        "BENCH_TOTAL_BUDGET": "150",
    })
    env.pop("XLA_FLAGS", None)  # no 8-device split for the bench child
    if hold is not None:
        env["BENCH_HOLD_AFTER_PRINT"] = str(hold)
    return env


def test_headline_survives_midrun_kill(tmp_path):
    """Kill -9 the whole bench process group the instant the headline
    line appears on stdout; the line must already be complete and
    parseable — exactly what the driver's `tail` would keep."""
    proc = subprocess.Popen(
        [sys.executable, "-u", BENCH], env=_bench_env(tmp_path, hold=60),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        start_new_session=True)
    headline = None
    deadline = time.time() + 150
    try:
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if line.startswith("{"):
                headline = line.strip()
                break
        # the driver's kill: whole process group, no grace
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    finally:
        proc.wait()
    assert headline, "no headline line before the kill"
    parsed = json.loads(headline)
    assert parsed["metric"] == "gpt_medium_train_tokens_per_sec_per_chip"
    assert parsed["value"] > 0
    assert parsed["unit"] == "tokens/s/chip"


@pytest.mark.heavy
def test_bench_persistent_cache_records_state(tmp_path):
    """A completed run must leave the compile-state marker that drives
    warm-cache attempt ordering, and end with a merged final line."""
    env = _bench_env(tmp_path)
    out = subprocess.run(
        [sys.executable, "-u", BENCH], env=env, timeout=170,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    assert out.returncode == 0
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines, out.stdout
    final = json.loads(lines[-1])
    assert final["value"] > 0
    assert "gpt_1p3b_tokens_per_sec" in final  # merged shape
    state_path = tmp_path / "xla_cache" / "bench_state.json"
    assert state_path.exists()
    state = json.loads(state_path.read_text())
    assert any(k.startswith("headline") for k in state)
