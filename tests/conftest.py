"""Pin tests to the CPU backend with 8 virtual devices so distributed
(mesh/sharding) tests run without real multi-chip hardware (SURVEY.md §4):
JAX_PLATFORMS=cpu and the host-platform device count, both set before jax
is imported."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The framework-level persistent compile cache (framework/compile_cache.py)
# stays OFF for the in-process suite — see the NOTE below on CPU AOT
# reloads, and an inherited user cache dir must not be polluted by test
# processes. Unconditional: subprocess tests that exercise the cache set
# the env var explicitly in their child environments.
os.environ["PADDLE_TPU_COMPILE_CACHE"] = "0"
# JAX's own variable beats every other choice of cache directory (the
# one rule in framework/compile_cache.py): on a machine that exports it,
# the tests that hand a child a private tmp_path cache and count its
# hits and misses would read and write the shared directory instead
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# an inherited metrics export path must not collect the whole suite's
# step records; telemetry tests set it explicitly (tmp_path)
os.environ.pop("PADDLE_TPU_METRICS_FILE", None)
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", "tests must run on the CPU backend"
assert jax.device_count() == 8, "expected 8 virtual CPU devices"

# NOTE: a persistent XLA compilation cache was tried here and removed —
# on this suite the wall time is tracing/eager dispatch, not XLA
# compiles, and the CPU AOT entries reload with machine-feature
# mismatch warnings (potential SIGILL per cpu_aot_loader). The wall-
# clock answer is the two-tier gate in pytest.ini instead.

import pytest  # noqa: E402


@pytest.fixture
def flash_interpret(monkeypatch):
    """F.scaled_dot_product_attention takes the Pallas flash kernels, in
    interpret mode (on the chip they are the default attention)."""
    import paddle_tpu.ops as ops
    monkeypatch.setattr(ops, "_FLASH_ENV", "interpret")
    assert ops.flash_attention_available()


def _kernel_calls(jaxpr, name):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr
                if hasattr(sub, "eqns"):
                    n += _kernel_calls(sub, name)
    return n


@pytest.fixture
def flash_kernel_calls():
    """count(fn, *args) -> how many pallas_call equations of each flash
    kernel the jaxpr of fn(*args) holds, bodies of scans, checkpoints
    and calls included: (forward, dq, dkv)."""
    def count(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        return tuple(_kernel_calls(jaxpr, f"flash_attention_{k}")
                     for k in ("fwd", "dq", "dkv"))
    return count


@pytest.fixture
def remat_saved():
    """saved(fn, *args) -> [(aval, why)] of what the jax.checkpoint-ed
    fn keeps for its backward pass beside constants and arguments."""
    from jax._src.ad_checkpoint import saved_residuals

    def saved(fn, *args):
        return [(str(aval), why) for aval, why in saved_residuals(fn, *args)
                if "constant" not in why and "argument" not in why]
    return saved
