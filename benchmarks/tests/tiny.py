"""Tiny copies of the configurations and cells for the CPU rehearsal,
and the patches that steer the runner from a test: the cell's files, the
look for a chip, and the peaks table are replaced here — the runner
itself has no option for any of it."""
import copy
import json
import os

from benchmarks.lib import peaks
from benchmarks.lib import program as P

TINY = {
    "gpt2-medium": {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
                    "num_heads": 4, "intermediate_size": 256,
                    "max_position_embeddings": 128},
    "mamba-1.4b": {"vocab_size": 512, "hidden_size": 64, "num_layers": 2,
                   "dt_rank": 4, "max_position_embeddings": 128},
}
TINY_CELL = {
    "train": {"batch": 2, "seq": 64, "distinct_batches": 4,
              "reference_micro_batch": 1},
    "serve": {"warmup_seconds": 0.5},
}


def tiny_config(config):
    cfg = copy.deepcopy(config)
    small = TINY[cfg["name"]]
    cfg.update(small)
    prog = cfg["program"]
    if prog["config"].endswith(":gpt_medium"):
        prog["config"] = "paddle_tpu.models.gpt:GPTConfig"
        prog["kwargs"] = {k: small[k] for k in small}
        prog["kwargs"]["scan_remat"] = prog.get("scan_remat", False)
    else:
        prog["kwargs"].update(small)
    return cfg


def tiny_cell(cell, cfg):
    c = copy.deepcopy(cell)
    c.update(TINY_CELL[c["kind"]])
    if c["kind"] == "serve":
        tr = c["traffic"]
        tr["prompt"] = {"lo": 4, "hi": 40, "median": 12, "sigma": 0.6}
        tr["output"] = {"lo": 4, "hi": 16, "median": 8, "sigma": 0.4}
        tr["max_total"] = 64
        if tr["loop"] == "open":
            tr["rate_rps"] = 6.0
        else:
            tr["clients"] = 4
        c["engine"].update(max_batch=4, n_pages=5 if cfg["reference"]
                           == "mamba" else 64, prefill_chunk=16)
        from benchmarks.lib.signatures import recurrent_closure
        c["signatures"] = recurrent_closure(4, 16) \
            if cfg["reference"] == "mamba" else []
        c["correct"]["pad_to"] = 64
        c["correct"]["requests"] = 3
    return c


def serve_cell():
    """The serving cell the rehearsal drives (tests/data/serve_cell.json;
    BENCHMARK.json holds no serving cell yet)."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "serve_cell.json")) as f:
        return json.load(f)


def manifest_with_serve_cell(manifest, extra):
    m = copy.deepcopy(manifest)
    m["workloads"].append(extra["workload"])
    m["configs"].append(extra["config_entry"])
    m["end_to_end"][:0] = extra["end_to_end"]
    m["per_layer"] += [p["manifest"] for p in extra["per_layer"]]
    return m


def patch(monkeypatch, limits=None):
    """Route P.load_cell to tiny copies (of BENCHMARK.json's cells, and
    of the rehearsal's serving cell) and P.require_tpu to the CPU's
    devices. `limits` overrides the compared numbers' limits."""
    import jax
    real, real_json = P.load_cell, P.load_json
    extra = serve_cell()
    specs = {f"{p['spec']['name']}.json": p["spec"]
             for p in extra["per_layer"]}

    def load_cell(name):
        if name == extra["workload"]["name"]:
            with open(os.path.join(P.ROOT, "BENCHMARK.json")) as f:
                manifest = manifest_with_serve_cell(json.load(f), extra)
            cell, entry = copy.deepcopy(extra["cell"]), extra["workload"]
            config = real_json("configs", f"{entry['config']}.json")
        else:
            cell, config, entry, manifest = real(name)
        cfg = tiny_config(config)
        cell = tiny_cell(cell, cfg)
        lim = cell["correct"]["limits"]
        lim.update({k: v for k, v in (limits or {}).items() if k in lim})
        return cell, cfg, entry, manifest

    def load_json(*parts):
        if parts[0] == "metrics" and parts[1] in specs:
            return specs[parts[1]]
        return real_json(*parts)

    monkeypatch.setattr(P, "load_cell", load_cell)
    monkeypatch.setattr(P, "load_json", load_json)
    monkeypatch.setattr(P, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(peaks.PEAKS, kind, peaks.PEAKS["TPU v5 lite"])
