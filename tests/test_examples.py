"""The examples/ scripts must actually run (tiny configs, CPU pin)."""
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.heavy]  # multi-minute: out of tier-1 and the quick gate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *extra],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.heavy
def test_train_gpt():
    out = _run("train_gpt.py", "--size", "tiny", "--steps", "4",
               "--batch", "2", "--seq", "32")
    assert "loss" in out and "tokens/s" in out


@pytest.mark.heavy
def test_train_gpt_hybrid():
    out = _run("train_gpt_hybrid.py", "--dp", "2", "--mp", "2",
               "--zero", "2", "--steps", "2", "--seq", "32")
    assert "mesh" in out and "loss" in out


@pytest.mark.heavy
def test_train_gpt_hybrid_sequence_parallel():
    out = _run("train_gpt_hybrid.py", "--dp", "2", "--sep", "4",
               "--mp", "1", "--zero", "1", "--steps", "2", "--seq", "64")
    assert "'sp'" in out or "sp" in out

@pytest.mark.heavy
def test_generate_gpt():
    out = _run("generate_gpt.py", "--tokens", "8")
    assert "warm" in out


@pytest.mark.heavy
def test_train_vision_hapi():
    out = _run("train_vision_hapi.py", "--model", "resnet18",
               "--epochs", "1", "--batch", "32")
    assert "loss" in out or "acc" in out
