"""Framework-level persistent compile cache (framework/compile_cache.py).

The acceptance proof for the warm-start contract: the same jitted train
step in two SEPARATE processes, sharing only the on-disk cache dir — the
second process must skip the cold compile (compile_s well under the 15 s
bound; on TPU the same mechanism turns a 60 s+ GPT compile into a
seconds-long cache load).
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.jit import TrainStep

paddle.seed(0)
m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
step = TrainStep(
    m, lambda out, y: nn.functional.cross_entropy(out, y), o)
x = paddle.to_tensor(
    np.random.RandomState(0).randn(4, 16).astype(np.float32))
y = paddle.to_tensor(np.arange(4, dtype=np.int64) % 8)
float(step(x, y).item())
print(json.dumps({
    "compile_s": step.compile_s,
    "retraces": step.retraces,
    "cache_dir": __import__(
        "paddle_tpu.framework.compile_cache",
        fromlist=["cache_dir"]).cache_dir(),
}))
"""


def _run_child(cache_dir):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_COMPILE_CACHE": str(cache_dir),
        "PYTHONUNBUFFERED": "1",
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def test_second_process_skips_cold_compile(tmp_path):
    cache = tmp_path / "xla_cache"
    first = _run_child(cache)
    assert first["cache_dir"] == str(cache)
    assert first["retraces"] == 1
    entries = [n for n in os.listdir(cache) if not n.startswith(".")]
    assert entries, "first process wrote no cache entries"
    second = _run_child(cache)
    # the acceptance bound: a warm process must never pay a cold compile
    assert second["compile_s"] < 15, second
    assert os.listdir(cache), "cache dir vanished"


def test_enable_disable_and_env_knobs(tmp_path):
    prev = compile_cache.cache_dir()
    try:
        d = compile_cache.enable_compile_cache(str(tmp_path / "cc"))
        assert d == str(tmp_path / "cc") and os.path.isdir(d)
        assert compile_cache.cache_dir() == d
        assert jax.config.jax_compilation_cache_dir == d
        # "0" and friends disable
        assert compile_cache.enable_compile_cache("0") is None
        assert compile_cache.cache_dir() is None
        # off is the SWITCH, not the directory: the one writer of
        # jax_compilation_cache_dir is enable_compile_cache
        assert jax.config.jax_enable_compilation_cache is False
        assert compile_cache.enable_compile_cache(d) == d
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        if prev:
            compile_cache.enable_compile_cache(prev)
        else:
            compile_cache.disable_compile_cache()


@pytest.mark.parametrize("jax_var,fw_var,arg,want", [
    ("/j", "/f", None, "/j"),        # the JAX variable wins
    ("/j", None, "/a", "/j"),        # ... over an explicit path too
    ("/j", "0", None, None),         # only "off" beats it
    (None, "/f", None, "/f"),
    (None, None, "/a", "/a"),
    (None, None, None, compile_cache.DEFAULT_CACHE_DIR),
])
def test_cache_dir_rule(monkeypatch, jax_var, fw_var, arg, want):
    """ONE rule, jax-free: JAX_COMPILATION_CACHE_DIR, else
    PADDLE_TPU_COMPILE_CACHE / the explicit path, else
    <checkout>/.xla_cache — never a path under the home directory."""
    for name, val in (("JAX_COMPILATION_CACHE_DIR", jax_var),
                      ("PADDLE_TPU_COMPILE_CACHE", fw_var)):
        if val is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, val)
    assert compile_cache.resolve_cache_dir(arg) == want
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
        repo, ".xla_cache")


def test_jax_variable_places_cache_and_disables_the_writer(
        tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the framework uses that
    directory and writes jax_compilation_cache_dir nowhere."""
    prev = compile_cache.cache_dir()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "j"))
    monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path / "j")
        assert os.path.isdir(tmp_path / "j")
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        monkeypatch.undo()
        if prev:
            compile_cache.enable_compile_cache(prev)
        else:
            compile_cache.disable_compile_cache()


def test_uncreatable_cache_dir_warns_naming_the_path(tmp_path):
    prev = compile_cache.cache_dir()
    blocker = tmp_path / "file"
    blocker.write_text("x")
    bad = str(blocker / "cache")
    try:
        with pytest.warns(RuntimeWarning, match="file/cache"):
            assert compile_cache.enable_compile_cache(bad) is None
        assert compile_cache.cache_dir() is None
    finally:
        if prev:
            compile_cache.enable_compile_cache(prev)
        else:
            compile_cache.disable_compile_cache()
