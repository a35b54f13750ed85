#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: one process, the engine
built and its closed signature set warmed once per engine setting, then
a short window of the cell's own traffic at each offered rate (open
loop) or client count (closed loop). Prints one JSON line per point:
offered and completed tokens/s, tails, backlog at the close.

    chiprun -- python benchmarks/tools/sweep_serve.py --workload <name> \\
        --rates 1,2,4,8 --seconds 20 [--engine '{"max_batch": 16}']
"""
import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
os.environ.setdefault("PADDLE_TPU_COMPILE_WORKERS", "8")

from benchmarks.lib import program as P           # noqa: E402
from benchmarks.lib import serve as S             # noqa: E402
from benchmarks.lib.signatures import recurrent_closure  # noqa: E402
from benchmarks.lib.tracing import Tracer         # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--engine", action="append", default=[],
                    help="JSON overrides of the cell's engine parameters; "
                         "repeat for several settings")
    args = ap.parse_args(argv)
    cell, config, entry, _ = P.load_cell(args.workload)
    devs = P.require_tpu(entry["chips"])
    from paddle_tpu.framework import compile_cache
    compile_cache.enable_compile_cache()
    from paddle_tpu.profiler import monitor
    S.stamp_tokens()
    out_dir = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a")

    def emit(rec):
        print(json.dumps(rec), flush=True)
        log.write(json.dumps(rec) + "\n")
        log.flush()

    tracer = Tracer(os.path.join(BENCH, ".out", "trace"), 0)
    for override in (args.engine or ["{}"]):
        c = json.loads(json.dumps(cell))
        c["engine"].update(json.loads(override))
        e = c["engine"]
        e["n_pages"] = e["max_batch"] + 1 if config["reference"] == "mamba" \
            else e["n_pages"]
        if config["reference"] == "mamba":
            c["signatures"] = recurrent_closure(e["max_batch"],
                                                e["prefill_chunk"])
        t0 = time.perf_counter()
        model, eng = S.build_engine(c, config, args.seed)
        emit({"engine": e, "signatures": len(c["signatures"]),
              "build_and_warm_s": time.perf_counter() - t0,
              "bytes_in_use": (devs[0].memory_stats() or {}).get("bytes_in_use")})
        steps = monitor.histogram("serve.batch_size")
        try:
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                tr = c["traffic"]
                if tr["loop"] == "open":
                    tr["rate_rps"] = rate
                else:
                    tr["clients"] = int(rate)
                load = S.Load(eng, tracer)
                s0, n0 = steps.count, steps.sum
                t0 = S.drive(load, c, config["vocab_size"], args.seed + i,
                             args.seconds)
                t_end = t0 + args.seconds
                n_steps, rows = steps.count - s0, steps.sum - n0
                in_flight = sum(1 for s in load.sent
                                if s.handle and not s.handle.future.done())
                for s in load.sent:      # drain before the next point
                    if s.handle is not None:
                        s.handle.result(timeout=600)
                drain_s = time.perf_counter() - t_end
                done = [s for s in load.sent
                        if (s.done_at() or t_end + 1) <= t_end]
                ttft = [s.stamps[0] - s.due for s in load.sent if s.stamps]
                gaps = [(b - a) * 1e3 for s in load.sent
                        for a, b in zip(s.stamps, s.stamps[1:])]
                emit({"rate": rate, "sent": len(load.sent),
                      "offered_tokens_per_s":
                          sum(s.max_new for s in load.sent) / args.seconds,
                      "serve_tokens_per_s":
                          sum(len(s.stamps) for s in done) / args.seconds,
                      "completed": len(done), "in_flight_at_close": in_flight,
                      "drain_s": drain_s,
                      "ttft_p50_ms": S.percentile(ttft, 50) * 1e3,
                      "ttft_p95_ms": S.percentile(ttft, 95) * 1e3,
                      "itl_p50_ms": S.percentile(gaps, 50),
                      "itl_p95_ms": S.percentile(gaps, 95),
                      "steps": n_steps, "rows_per_step": rows / max(n_steps, 1),
                      "step_ms": 1e3 * args.seconds / max(n_steps, 1),
                      "occupancy": sum(load.occupancy) / len(load.occupancy),
                      "late_p99_ms": S.percentile(load.late, 99) * 1e3})
        finally:
            eng.shutdown(wait=False)
        del model, eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
