#!/usr/bin/env python3
"""Readings from which a cell's limits are set (PERF.md lists them):
the program's numbers over many seeds, the control's (the reference put
in the program's place, computed in the nearest precision below the one
the configuration states) and the planted faults' over three or more.
Never run by a benchmark run. On the chip:

    chiprun -- python benchmarks/tools/calibrate.py --workload <name> \\
        --seeds 11,12,... --control-seeds 11,12,13 --seconds 4

Prints one JSON line per reading and writes them all to
chiprun_out/calibrate/<workload>.jsonl."""
import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run                      # noqa: E402
from benchmarks.lib import correct as C         # noqa: E402
from benchmarks.lib import program as P         # noqa: E402
from benchmarks.references.common import CONTROL_OF  # noqa: E402


def train_control(cell, config, seed):
    """The reference in the control's precision, and with each planted
    fault, against the reference itself."""
    from benchmarks.lib.reftrain import reference_train
    from benchmarks.lib.train import CHECK_STEPS, make_batches
    ref_mod = P.reference_of(config)
    batches = make_batches(seed, config["vocab_size"], cell["batch"],
                           cell["seq"], CHECK_STEPS)
    kw = dict(micro=cell["reference_micro_batch"])
    hp = cell["optimizer"]
    ref = reference_train(ref_mod, config, seed, batches, hp, **kw)
    out = {}
    for what, args in (("control", {"prec": CONTROL_OF[config["dtype"]]}),
                       ("half_batch", {"fault": "half_batch"})):
        got = reference_train(ref_mod, config, seed, batches, hp, **kw,
                              **args)
        out[what], _ = C.train_numbers(got, ref)
        gc.collect()
    return out


def serve_control(cell, config, seed, sample):
    from benchmarks.lib.serve import reference_gaps
    gaps, exact = reference_gaps(
        config, seed, sample, cell["correct"]["pad_to"],
        cell["traffic"]["output"]["hi"],
        control=CONTROL_OF[config["dtype"]])
    return {"control": {**C.serve_numbers(gaps),
                        "tokens": len(gaps), "exact": exact}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    cell, config, _, _ = P.load_cell(args.workload)
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a") as f:
        def emit(rec):
            print(json.dumps(rec), flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()
        for seed in dict.fromkeys(seeds + cseeds):
            keep = {}
            if seed in seeds or cell["kind"] == "serve":
                line = run.run_cell(args.workload, seed, args.seconds, 0,
                                    keep=keep)
                emit({"what": "program", "seed": seed,
                      "correct": line["correct"],
                      "numbers": {k: v["value"]
                                  for k, v in line["compared"].items()},
                      "metrics": line["metrics"], "extra": line["extra"]})
            gc.collect()
            if seed in cseeds:
                got = train_control(cell, config, seed) \
                    if cell["kind"] == "train" \
                    else serve_control(cell, config, seed, keep["sample"])
                for what, numbers in got.items():
                    emit({"what": what, "seed": seed, "numbers": numbers})
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
