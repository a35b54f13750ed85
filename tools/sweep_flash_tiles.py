#!/usr/bin/env python
"""Device time of the three bare flash-attention kernels, per sub-tile.

Where attention_core.SUB_TILE_CAPS comes from: on the chip, run forward
and backward of the kernels through the public entry, on [B, T, H, D]
arrays at the shapes the models hand it (so what is timed is what they
run: the kernels, and under `all_device_ops` whatever copies the entry
puts around them), and read each kernel's device time from a profiler
trace (the benchmark's own reduction, benchmarks/lib/xplane.py), once
per candidate (tq, tk). The candidates are set by assigning
SUB_TILE_CAPS from here — the program has no option for it. A tree
without SUB_TILE_CAPS (an older commit on PYTHONPATH) is timed as it
stands, and `--control` times the compiler's own attention
(nn/functional/attention.py _sdpa_reference) the same way, and the
compiler's reduce for delta = rowsum(out * dout) over the [B, T, H*D]
layout, which the dq kernel makes in its prologue instead.

  chiprun -- python tools/sweep_flash_tiles.py --out chiprun_out/tiles.jsonl

One JSON line per (shape, candidate): ms per call of each kernel, the
visited-tile share, the largest error against the float32 einsum.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# an older commit named on PYTHONPATH wins over this checkout
sys.path.append(REPO)

SHAPES = {"gpt2-medium": (8, 1024, 16, 64), "gpt-1p3b": (2, 2048, 16, 128),
          "glm-mla": (2, 4096, 20, 256)}
KERNELS = ("flash_attention_fwd", "flash_attention_dq",
           "flash_attention_dkv")


def traced_ms(fn, args, calls):
    """{op name: ms per call} of the device ops of `calls` runs of fn."""
    import jax
    from benchmarks.lib import xplane
    out = tempfile.mkdtemp(prefix="flash_sweep_")
    try:
        jax.block_until_ready(fn(*args))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(out, profiler_options=opts)
        t0 = time.perf_counter()
        for _ in range(calls):
            res = fn(*args)
        jax.block_until_ready(res)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True)[0]
        tr = xplane.Trace.from_file(pb, wall)
        ms = {k: 1e3 * tr.kernel_seconds(k)[0] / calls for k in KERNELS}
        ms["all_device_ops"] = 1e3 * tr.busy_fullest_s / calls
        # what the entry and the compiler put around the kernels
        ms["others"] = {name: 1e3 * s / calls for name, s in tr.top_ops(9)
                        if not name.startswith("flash_attention_")}
        ms["wall"] = 1e3 * wall / calls
        return ms
    finally:
        shutil.rmtree(out, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tiles", default="128x128,256x256,512x512,1024x1024")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--label", default="change")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("sweep_flash_tiles: no TPU; a CPU time is no device time",
              file=sys.stderr)
        return 3
    from paddle_tpu.nn.functional.attention import _sdpa_reference
    from paddle_tpu.ops.pallas import attention_core as core
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_arrays

    causal = bool(args.causal)
    has_tiles = hasattr(core, "SUB_TILE_CAPS")
    # "policy": the tiles as attention_core has them, per kernel
    tiles = ([None if t == "policy" else tuple(int(x) for x in t.split("x"))
              for t in args.tiles.split(",")] if has_tiles else [None])
    lines = []
    for name in args.shapes.split(","):
        shape = B, T, H, D = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(26), 4)
        # held [B, T, H*D], as a model's projections leave them, and
        # viewed by head inside the compiled function, as a model views
        # them: a [B, T, H, D] array of its own is tiled over (H, D) on
        # the chip, and the view would cost a copy that no model pays
        q, k, v, w = (jax.random.normal(kk, (B, T, H * D), jnp.float32)
                      .astype(jnp.bfloat16) for kk in keys)

        def by_head(attn):
            return lambda *qkv: attn(*(x.reshape(shape) for x in qkv)) \
                .reshape(B, T, H * D)

        def grads_of(attn):
            # a weighted sum: every element of dout differs
            loss = lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        plain = by_head(lambda q, k, v: _sdpa_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), is_causal=causal))
        want = jax.jit(plain)(q, k, v), grads_of(plain)(q, k, v)
        if args.control:
            xla = by_head(lambda q, k, v: _sdpa_reference(
                q, k, v, is_causal=causal))
            ms = traced_ms(grads_of(xla), (q, k, v), args.calls)
            lines.append({"label": "xla_composition", "shape": name,
                          "causal": causal, "ms": ms})
            print(json.dumps(lines[-1]), flush=True)
            delta = jax.jit(lambda o, do: jnp.swapaxes(jnp.sum(
                (o.astype(jnp.float32) * do.astype(jnp.float32))
                .reshape(B, T, H, D), -1), 1, 2).reshape(B * H, 1, T))
            ms = traced_ms(delta, (q, w), args.calls)
            lines.append({"label": "xla_delta", "shape": name,
                          "ms": ms["all_device_ops"]})
            print(json.dumps(lines[-1]), flush=True)
        for t in tiles:
            if t is not None:
                core.SUB_TILE_CAPS = {kern: t for kern in core.SUB_TILE_CAPS}
            flash = by_head(lambda q, k, v: flash_attention_arrays(
                q, k, v, causal=causal, interpret=False))
            line = {"label": args.label, "shape": name, "causal": causal,
                    "caps": t}
            try:
                fwd, g = jax.jit(flash), grads_of(flash)
                t0 = time.perf_counter()
                got = fwd(q, k, v), g(q, k, v)
                jax.block_until_ready(got)
                line["compile_and_first_s"] = time.perf_counter() - t0
                line["ms"] = traced_ms(g, (q, k, v), args.calls)
                line["ms"]["fwd_alone"] = traced_ms(
                    fwd, (q, k, v), args.calls)["flash_attention_fwd"]
                err = lambda a, b: float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b))) / float(jnp.max(jnp.abs(b)))
                line["rel_err"] = {
                    "out": err(got[0], want[0]),
                    **{f"d{n}": err(a, b) for n, a, b
                       in zip("qkv", got[1], want[1])}}
                if hasattr(core, "heads_per_block"):
                    line["heads_per_block"] = core.heads_per_block(
                        *shape[2:])
                if has_tiles:
                    b = core.choose_flash_blocks(shape[1], shape[1],
                                                 shape[3])
                    line["blocks"] = [b.block_q, b.block_k]
                    line["tiles"] = {"fwd": b.fwd, "dq": b.dq, "dkv": b.dkv}
                    square = b.block_q == b.block_k
                    line["visited_share"] = core.visited_tile_share(
                        shape[1], shape[1],
                        b.fwd if square else (b.block_q, b.block_k), causal)
            except Exception as e:      # a refused tile is a finding
                line["error"] = str(e)[-600:]
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
