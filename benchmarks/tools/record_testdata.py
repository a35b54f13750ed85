#!/usr/bin/env python3
"""Record the small device trace that benchmarks/tests/test_xplane.py
reduces (benchmarks/testdata/small.xplane.pb), and print what planes,
lines and events a trace of this runtime holds. Run on the chip:

    chiprun -- python benchmarks/tools/record_testdata.py

Three steps of a toy jitted program (two matmuls and a gap the host
makes by sleeping) under jax.profiler, with host annotations, so the
trace has device ops, an idle gap with a named host span over it, and
is a few hundred KB."""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, "chiprun_out", "testdata")


def describe(path, top=12):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        p = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            first_stats = {}
            if evs:
                try:
                    first_stats = {k: str(v)[:80] for k, v in evs[0].stats}
                except Exception as ex:  # stats are optional evidence
                    first_stats = {"error": repr(ex)}
            p["lines"].append({
                "line": line.name, "events": len(evs),
                "top": sorted(names.items(), key=lambda kv: -kv[1])[:top],
                "first_event_stats": first_stats})
        out.append(p)
    return out


def main():
    import jax
    import jax.numpy as jnp
    os.makedirs(OUT, exist_ok=True)
    tmp = os.path.join(OUT, "trace")
    shutil.rmtree(tmp, ignore_errors=True)

    @jax.jit
    def toy_step(a, b):
        with jax.named_scope("toy_matmul"):
            c = a @ b
        return jnp.tanh(c) @ b

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    toy_step(a, b).block_until_ready()
    jax.profiler.start_trace(tmp)
    t0 = time.perf_counter()
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            a = toy_step(a, b)
            a.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    pbs = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(OUT, "small.xplane.pb")
    shutil.copy(pbs[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    desc = describe(dst)
    with open(os.path.join(OUT, "small.describe.json"), "w") as f:
        json.dump({"window_s": window, "bytes": os.path.getsize(dst),
                   "device": jax.devices()[0].device_kind,
                   "planes": desc}, f, indent=1)
    print(json.dumps({"bytes": os.path.getsize(dst), "window_s": window,
                      "planes": [p["plane"] for p in desc]}))


if __name__ == "__main__":
    sys.exit(main())
