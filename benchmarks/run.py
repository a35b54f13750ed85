#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
--trace 0, its per-layer metrics with --trace 1), `device`, with a
trace `breakdown`, and last the numbers compared beside their limits.
No TPU, or fewer chips than the cell asks for: exit 3 and no result.

A cell is data: benchmarks/workloads/<name>.json (traffic or batch,
engine parameters, limits), benchmarks/configs/<config>.json (sizes,
how the program builds it, its plain reference under
benchmarks/references/), and one benchmarks/metrics/<metric>.json per
per-layer metric naming its reader under benchmarks/readers/."""
import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
# set-up compiles a cell's signatures side by side (the program's warm
# pipeline defaults to 4 threads); the window itself starts none
os.environ.setdefault("PADDLE_TPU_COMPILE_WORKERS", "8")

from benchmarks.lib import correct as C      # noqa: E402
from benchmarks.lib import program as P      # noqa: E402
from benchmarks.lib.tracing import Tracer    # noqa: E402

RUNNERS = {"train": "benchmarks.lib.train", "serve": "benchmarks.lib.serve"}


def per_layer_metrics(manifest, workload, ctx):
    """Every per-layer metric of BENCHMARK.json that lists this cell (or
    lists none), read by its own reader. A reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = P.load_json("metrics", f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload, seed, seconds, trace, keep=None):
    """Everything a run does after its arguments are parsed; the tests
    call this with the cell's files and the chip check patched. `keep`,
    a dict, receives the runner's whole result (the calibration tool
    reads the served sample from it)."""
    cell, config, entry, manifest = P.load_cell(workload)
    devs = P.require_tpu(entry["chips"])
    from paddle_tpu.framework import compile_cache
    compile_cache.enable_compile_cache()
    tracer = Tracer(os.path.join(BENCH, ".out", "trace"),
                    cell.get("trace_seconds", 3))
    runner = importlib.import_module(RUNNERS[cell["kind"]])
    res = runner.run(cell, config, devs, seed, seconds, trace, T_PROCESS,
                     tracer)
    if keep is not None:
        keep.update(res)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"]}
    if trace:
        from benchmarks.lib.peaks import peak_for
        tr = tracer.reduce()
        ctx = {"trace": tr, "window": res["window"], "config": config,
               "cell": cell, "chips": len(devs),
               "peak": peak_for(devs[0].device_kind)}
        line["metrics"] = per_layer_metrics(manifest, workload, ctx)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        line["device"] = device
        line["breakdown"] = tr.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in res["end_to_end"].items()}
        line["device"] = device
    line["extra"] = res.get("extra", {})
    line["compared"] = C.report(res["rows"], res["correct"])
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, args.trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
