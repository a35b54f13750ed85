"""The benchmark's own tests run on the CPU: hold JAX to it before
anything imports jax, keep the compile cache off, and make the repo
importable. (tier-1 collects tests/ only; these are run by hand:
`python -m pytest benchmarks/tests -q -p no:cacheprovider`.)"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("PADDLE_TPU_COMPILE_CACHE", "0")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
