#!/usr/bin/env python
"""Pack / seed the persistent XLA compile cache — the donated-artifact
workflow that kills the cold-compile wall across machines and processes
(docs/PERFORMANCE.md "Killing the compile wall"; ROADMAP open item 3).

A compiled cache directory is a portable artifact: any machine that has
paid a workload's cold compiles can `pack` them, and a fresh machine
(or a CI runner, or a bench round under a hard compile budget) can
`seed` them — its first compiles then LOAD in seconds instead of
recompiling for minutes. The cache key includes the HLO fingerprint and
jax/backend versions, so a stale or mismatched artifact degrades to
ordinary cold compiles, never to wrong results.

Usage:
  python tools/seed_compile_cache.py pack DEST [--cache DIR]
      Copy the active cache's entries (PADDLE_TPU_COMPILE_CACHE or the
      default user cache; --cache overrides) into DEST with a
      MANIFEST.json naming them.

  python tools/seed_compile_cache.py seed SOURCE [--cache DIR]
      Copy SOURCE's entries (a pack artifact or any raw cache dir) into
      the active cache, skipping entries already present.

In-process, `paddle_tpu.framework.compile_cache.seed_from()` does
the same and emits a `kind:"seed"` metrics record.

Exit 0 on success, 2 on a bad source/cache.
"""
import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_compile_cache():
    """Load framework/compile_cache.py as a standalone module — it only
    needs stdlib + jax, so the CLI skips the full framework import (and
    its backend-init weight)."""
    path = os.path.join(REPO, "paddle_tpu", "framework",
                        "compile_cache.py")
    spec = importlib.util.spec_from_file_location("_compile_cache", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    ap = argparse.ArgumentParser(
        "seed_compile_cache",
        description="pack/seed the persistent XLA compile cache")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("pack", help="copy cache entries to a portable "
                                    "artifact dir")
    p.add_argument("dest")
    p.add_argument("--cache", default=None,
                   help="source cache dir (default: the active cache)")
    s = sub.add_parser("seed", help="pre-populate the cache from an "
                                    "artifact dir")
    s.add_argument("source")
    s.add_argument("--cache", default=None,
                   help="destination cache dir (default: the active "
                        "cache)")
    args = ap.parse_args(argv)

    cc = _load_compile_cache()
    try:
        if args.cmd == "pack":
            if args.cache is None:
                cc.enable_compile_cache()
            out = cc.pack(args.dest, source=args.cache)
            print(json.dumps({"packed": out}))
        else:
            out = cc.seed_from(args.source, dest=args.cache)
            print(json.dumps({"seeded": out}))
    except ValueError as e:
        print(f"seed_compile_cache: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
