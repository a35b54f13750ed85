#!/usr/bin/env python
"""Dot-shape gate: attention score dots must be MXU-shaped.

Why (ISSUE 16; *Ragged Paged Attention*, arxiv 2604.15464): the TPU
MXU is a 128x128 systolic array fed by (8, 128) f32 tiles — a dot
whose M dimension is below 8 pads the sublane dimension with zeros and
runs at a fraction of peak no matter what the kernel around it does.
The seed-era serving kernel's per-(token, head) `[1, D] x [D, P]`
score dots were exactly this shape. This gate turns "MXU-shaped" from
a claim in a docstring into a ratchet: it lowers BOTH Pallas attention
kernels (serving ragged + training flash) at the canonical gate
geometries, parses every `stablehlo.dot_general` in the lowered
modules, and FAILS if any rank-2 dot result has M < MIN_DOT_ROWS — or
if a module contains no dots at all (a parse that finds nothing must
not pass vacuously).

It also checks the PLANNER side of the contract: the serving engine's
token-bucket rule (pad_t >= MIN_Q_TOKENS) composed with
attention_core.choose_q_block must yield q-block rows >= MIN_DOT_ROWS
for every bucket warm_async can emit — the kernel being capable of
MXU shapes is worthless if the scheduler feeds it 1-token buckets.

Kernels are lowered in Pallas interpret mode (their dots inline into
the StableHLO with their real shapes), so the gate runs on the same
CPU containers as tier-1 (tests/test_attention_blocking.py runs it).

Usage:
  python tools/check_dot_shapes.py [--min-rows 8] [-v]
Exit 0 clean, 1 on a narrow dot, 2 on gate failure.
"""
import argparse
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_DOT = re.compile(
    r"stablehlo\.dot_general.*->\s*tensor<([0-9x]+)x[a-z0-9]+>")


def dot_result_dims(stablehlo_text):
    """All dot_general result shapes (tuples of ints) in a lowered
    module's StableHLO text."""
    return [tuple(int(d) for d in m.group(1).split("x"))
            for m in _DOT.finditer(stablehlo_text)]


def check_module(name, text, min_rows):
    """(violations, n_dots) for one lowered module: every rank-2 dot's
    M (first result dim) must reach min_rows. Rank-3+ dots carry batch
    dims; their M is the second-to-last dim."""
    violations = []
    dims = dot_result_dims(text)
    if not dims:
        violations.append(
            f"{name}: no stablehlo.dot_general found in the lowered "
            "module — the parse found nothing to check (lowering or "
            "regex drift); the gate must not pass vacuously")
    for shape in dims:
        m = shape[-2] if len(shape) >= 2 else 1
        if m < min_rows:
            violations.append(
                f"{name}: dot_general result {'x'.join(map(str, shape))} "
                f"has M={m} < {min_rows} — a VPU-shaped score dot is "
                "back; check choose_q_block / head folding and the "
                "serving token-bucket floor")
    return violations, len(dims)


def lower_ragged_kernel():
    """Lower serve.ragged_step's attention kernel standalone at the
    canonical gate geometry (tools/_gate_common.py emit_workload: GPT
    hidden 32 / 2 heads -> D=16, page_size 16, the floored (8, 1, 1)
    signature)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention

    T, H, KVH, D = 8, 2, 2, 16
    n_pages, P, B, W = 8, 16, 1, 1
    sds = jax.ShapeDtypeStruct
    fn = jax.jit(lambda *a: ragged_paged_attention(*a, interpret=True))
    lowered = fn.lower(
        sds((T, H, D), jnp.float32),
        sds((n_pages, P, KVH, D), jnp.float32),
        sds((n_pages, P, KVH, D), jnp.float32),
        sds((B, W), jnp.int32), sds((T,), jnp.int32),
        sds((T,), jnp.int32))
    return lowered.as_text()


def lower_flash_kernel(B=2, T=16, H=2, D=16):
    """Lower the training flash kernel (fwd) standalone: at the
    canonical train-step geometry (batch 2, seq 16, 2 heads, D=16: the
    heads folded into the batch), or with TWO heads of 64 to a lane
    block of the model's own [B, T, H*D]."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import \
        flash_attention_arrays

    sds = jax.ShapeDtypeStruct
    fn = jax.jit(lambda q, k, v: flash_attention_arrays(
        q, k, v, causal=True, interpret=True))
    x = sds((B, T, H, D), jnp.float32)
    return fn.lower(x, x, x).as_text()


def check_planner(min_rows):
    """The serving bucket rule must deliver q-blocks >= min_rows for
    every T bucket the engine can pad to (pow2 floored at
    MIN_Q_TOKENS, up to a generous prefill-chunk ceiling)."""
    from paddle_tpu.ops.pallas.attention_core import (
        MIN_Q_TOKENS, MXU_ROWS, choose_q_block)
    violations = []
    if MIN_Q_TOKENS < min_rows:
        violations.append(
            f"planner: MIN_Q_TOKENS={MIN_Q_TOKENS} < {min_rows} — the "
            "serving pad floor no longer guarantees MXU-shaped blocks")
    t = MIN_Q_TOKENS
    while t <= 4096:  # every pow2 bucket a prefill chunk can land on
        bq = choose_q_block(t, cap=MXU_ROWS)
        if bq < min_rows:
            violations.append(
                f"planner: T bucket {t} yields q_block {bq} < "
                f"{min_rows}")
        t *= 2
    return violations


def check_verify_rows(min_rows):
    """Speculative verify-row geometry (inference/speculative.py): a
    k-draft verify row carries k+1 tokens, and SpeculativeConfig caps
    k at MIN_Q_TOKENS - 1 precisely so that every legal depth pads
    into the (MIN_Q_TOKENS, ...) token bucket — the same warm decode
    signature, still MXU-shaped. Walk every legal k and assert the
    padded bucket and its q-block both hold, so a future change to the
    k cap, the pad floor, or choose_q_block cannot silently ship
    sub-tile verify dots (or mint per-depth executables)."""
    from paddle_tpu.ops.pallas.attention_core import (
        MIN_Q_TOKENS, MXU_ROWS, choose_q_block)

    def pow2(n):
        p = 1
        while p < n:
            p *= 2
        return p

    violations = []
    for k in range(1, MIN_Q_TOKENS):  # every legal SpeculativeConfig.k
        t = max(pow2(k + 1), MIN_Q_TOKENS)  # the engine's pad rule
        if t != MIN_Q_TOKENS:
            violations.append(
                f"verify-row: k={k} ({k + 1} tokens) pads to bucket "
                f"{t} != MIN_Q_TOKENS {MIN_Q_TOKENS} — speculation "
                "would mint a new executable per depth")
        bq = choose_q_block(t, cap=MXU_ROWS)
        if bq < min_rows:
            violations.append(
                f"verify-row: k={k} bucket {t} yields q_block {bq} < "
                f"{min_rows} — sub-MXU verify dots")
    return violations


def main(argv=None):
    ap = argparse.ArgumentParser(
        "check_dot_shapes",
        description="attention score dots must have M >= the MXU "
                    "sublane tile")
    ap.add_argument("--min-rows", type=int, default=int(
        os.environ.get("PADDLE_TPU_MIN_DOT_ROWS", "8")))
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        modules = [("serve.ragged_step/paged_attention",
                    lower_ragged_kernel()),
                   ("train.step/flash_attention", lower_flash_kernel()),
                   ("train.step/flash_attention, 2 heads a block",
                    lower_flash_kernel(1, 128, 2, 64))]
    except Exception as e:  # lowering itself broke: gate failure
        print(f"check_dot_shapes: lowering failed: {e}", file=sys.stderr)
        return 2

    violations = []
    for name, text in modules:
        v, n = check_module(name, text, args.min_rows)
        violations += v
        print(f"{name}: {n} dot(s), "
              f"{'FAIL' if v else f'all M >= {args.min_rows}'}")
        if args.verbose:
            for shape in dot_result_dims(text):
                print(f"  dot -> {'x'.join(map(str, shape))}")
    violations += check_planner(args.min_rows)
    violations += check_verify_rows(args.min_rows)
    for v in violations:
        print(f"FAIL: {v}")
    if violations:
        print(f"FAIL: {len(violations)} narrow-dot violation(s)")
        return 1
    print(f"OK: every attention dot is MXU-shaped "
          f"(M >= {args.min_rows})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
