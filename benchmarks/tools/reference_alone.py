#!/usr/bin/env python3
"""The plain reference's three steps alone, at a cell's sizes: does
benchmarks/lib/reftrain.py as it stands fit the chip beside nothing else,
and how long does it take cold? Never run by a benchmark run. On the chip:

    chiprun -- python benchmarks/tools/reference_alone.py --workload <name>

Prints one JSON line: seconds (compile included), losses, the worst
gradient norms, `peak_bytes_in_use` and `bytes_limit`."""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.lib import program as P              # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--prec", default="f32")
    args = ap.parse_args(argv)
    cell, config, entry, _ = P.load_cell(args.workload)
    devs = P.require_tpu(entry["chips"])
    from paddle_tpu.framework import compile_cache
    compile_cache.enable_compile_cache()
    from benchmarks.lib.reftrain import reference_train
    from benchmarks.lib.train import CHECK_STEPS, make_batches
    batches = make_batches(args.seed, config["vocab_size"], cell["batch"],
                           cell["seq"], CHECK_STEPS)
    t0 = time.perf_counter()
    out = reference_train(P.reference_of(config), config, args.seed, batches,
                          cell["optimizer"], prec=args.prec,
                          micro=cell["reference_micro_batch"])
    stats = devs[0].memory_stats() or {}
    print(json.dumps({
        "workload": args.workload, "prec": args.prec,
        "seconds": time.perf_counter() - t0, "losses": out["losses"],
        "grad_norm_max": max(out["grad_norms"].values()),
        "change_norm_max": max(out["change_norms"].values()),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
