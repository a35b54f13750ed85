"""The driver's proof-points must keep working: __graft_entry__
exposes entry() + dryrun_multichip()."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    return env


def test_graft_entry_compiles():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; import jax; f, a = g.entry(); "
         "out = jax.jit(f)(*a); print('SHAPE', out.shape)"],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SHAPE" in proc.stdout


@pytest.mark.heavy
def test_dryrun_multichip_8():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); print('OK')"],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


# ---- chip_smoke.py: the proof must not be had without the chip ----------

@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-the-repo", "alone-in-a-directory"])
def test_chip_smoke_fails_without_the_chip(alone, tmp_path):
    """Off the chip the script ends non-zero and never prints
    `"ok": true` — in the checkout (the device phase finds no TPU) and
    in a directory that holds the script and nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        import shutil
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script, cwd = str(tmp_path / "chip_smoke.py"), str(tmp_path)
    env = _env()
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=280)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout[-500:]


def _smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case", ["exact", "near-tie", "wrong",
                                  "noisy-dtype"])
def test_chip_smoke_greedy_check(case):
    """check_greedy: the argmax passes; a token that loses by less than
    twice the MEASURED rounding noise (or 2e-2) is a near-tie; one that
    loses by more fails the phase."""
    import numpy as np
    cs = _smoke()
    rng = np.random.RandomState(0)
    ref = rng.randn(2, 3, 50).astype(np.float32)
    ref[..., 7] = 9.0                       # the reference's argmax
    ref[..., 8] = 9.0 - 0.015               # within 2e-2 of it
    ref[..., 9] = 9.0 - 0.05                # beyond 2e-2
    own = ref.copy()
    tok = {"exact": 7, "near-tie": 8, "wrong": 9, "noisy-dtype": 9}[case]
    if case == "noisy-dtype":               # the served dtype strays 0.03
        own[..., 7] -= 0.03
    outs = [np.full(3, tok, np.int32) for _ in range(2)]
    if case == "wrong":
        with pytest.raises(AssertionError, match="lose to the float32"):
            cs.check_greedy("t", outs, own, ref)
        return
    rec, bound = cs.check_greedy("t", outs, own, ref)
    assert rec["tokens_checked"] == 6
    assert rec["tokens_exact_argmax"] == (6 if case == "exact" else 0)
    assert rec["tokens_beyond_2e-2"] == (6 if case == "noisy-dtype" else 0)
    want = 0.06 if case == "noisy-dtype" else 2e-2
    assert abs(bound - want) < 1e-6
