"""The seam between the benchmark and the system under test: finding a
chip, building the program's model from a configuration file, handing
it the benchmark's seeded weights, counting compiles, reading memory.
Everything the benchmark takes from the program goes through here or
through the two runners (train.py, serve.py)."""
import importlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name):
    """(cell file, configuration file, manifest entry) of one workload
    of BENCHMARK.json. A missing manifest, or a checkout that holds the
    benchmark but not the program, ends the run here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"benchmarks/run.py: no workload {name!r} in "
                         "BENCHMARK.json")
    cell = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{entry['config']}.json")
    return cell, config, entry, manifest


def require_tpu(chips):
    """The devices of this run, or NoChip: the measurement path has no
    CPU fallback, and no number of a CPU run is ever printed under a
    device metric's name."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmarks/run.py: need {chips} TPU chip(s); JAX reports "
              f"{len(devs)} x {devs[0].platform!r}. Nothing is measured "
              "off the chip.", file=sys.stderr, flush=True)
        raise NoChip(3)
    from .peaks import peak_for
    peak_for(devs[0].device_kind)          # unknown kind: an error
    return devs[:chips]


def resolve(path):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def reference_of(config):
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")


def build_model(config):
    """The program's model for a configuration file, in the dtype the
    file states. Every key of the file that the program's own config
    object also has must agree with it: the file is what is run."""
    prog = config["program"]
    pcfg = resolve(prog["config"])(**prog.get("kwargs", {}))
    for k, v in config.items():
        if hasattr(pcfg, k) and getattr(pcfg, k) != v:
            raise ValueError(
                f"configuration {config['name']}: file says {k}={v!r}, "
                f"the program builds {getattr(pcfg, k)!r}")
    model = resolve(prog["model"])(pcfg)
    if config["dtype"] == "bfloat16":
        model.bfloat16()
    elif config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {config['dtype']!r}")
    return model


_LAYER = re.compile(r"\.h\.(\d+)\.")


def leaf_of(weights, name):
    """The benchmark's seeded leaf for the program's parameter `name`:
    layers are held stacked under "<prefix>.h.*.<leaf>"."""
    m = _LAYER.search(name)
    if m is None:
        return weights[name]
    return weights[_LAYER.sub(".h.*.", name, count=1)][int(m.group(1))]


def install_weights(model, weights):
    """Every Parameter of the program's model takes the benchmark's
    leaf of its name (cast to the Parameter's dtype)."""
    for name, p in model.named_parameters():
        p.set_value(leaf_of(weights, name))
    if hasattr(model, "clear_decode_cache"):
        model.clear_decode_cache()


class CompileCounter:
    """Compiles between start() and stop(): the program's own (every
    jit/api.aot_compile lands in the compile observatory, as in
    chip_smoke.PhaseMeter) and, wider, every backend compile request JAX
    itself reports, cache hits included — so a program the benchmark
    jitted inside the window shows too."""

    def __init__(self):
        self.program = 0
        self.backend = 0
        self._on = False

    def _program(self, ev):
        if self._on and ev["phase"] == "done":
            self.program += 1

    def _backend(self, event, duration, **kw):
        if self._on and event.endswith("backend_compile_duration"):
            self.backend += 1

    def install(self):
        import jax.monitoring
        from paddle_tpu.profiler import compile_observatory as co
        co.add_listener(self._program)
        jax.monitoring.register_event_duration_secs_listener(self._backend)
        return self

    def start(self):
        self.program = self.backend = 0
        self._on = True

    def stop(self):
        self._on = False
        return max(self.program, self.backend)


def memory_stat(devs, key):
    """One of the allocator's memory_stats() on the fullest chip; 0 where
    the backend keeps none (the CPU)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devs)


def memory_peak_bytes(devs):
    """The allocator's high-water mark on the fullest chip
    (memory_stats()["peak_bytes_in_use"]) — the figure the contract asks
    for. PR 21 found it does not count a program's temporaries; the
    compiler's own figure is reported beside it by the runners."""
    return memory_stat(devs, "peak_bytes_in_use")
