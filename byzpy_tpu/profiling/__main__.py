"""CLI for the roofline profiler and kernel autotuner.

Profile every ``ops.robust`` aggregator at the BASELINE.md shapes::

    python -m byzpy_tpu.profiling --out benchmarks/results/roofline.jsonl

Sweep Pallas block shapes and persist winners in the tile cache::

    python -m byzpy_tpu.profiling --autotune \
        --cache benchmarks/results/autotune_cpu.json

Both honor ``JAX_PLATFORMS=cpu`` (the profiler calibrates the host's
achievable bandwidth/GFLOPs first so CPU fractions are honest).
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    """Entry point (``python -m byzpy_tpu.profiling``)."""
    from ..utils.platform import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser(
        prog="byzpy_tpu.profiling",
        description="roofline profiler + Pallas block-shape autotuner",
    )
    ap.add_argument("--out", default=None,
                    help="JSONL sink for profile records")
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink feature dims (CI smoke)")
    ap.add_argument("--names", nargs="*", default=None,
                    help="profile only these workloads")
    ap.add_argument("--autotune", action="store_true",
                    help="run the tile sweep instead of the profiler")
    ap.add_argument("--force", action="store_true",
                    help="re-sweep even on cache hits")
    ap.add_argument("--cache", default=None,
                    help="tile cache path (default: BYZPY_TPU_TUNE_CACHE; "
                         "one of the two is required to store a sweep)")
    args = ap.parse_args(argv)

    if args.autotune:
        from .autotune import DEFAULT_SHAPES, autotune_all

        shapes = DEFAULT_SHAPES
        if args.scale != 1.0:
            shapes = tuple(
                (n, max(256, int(d * args.scale))) for n, d in shapes
            )
        rows = autotune_all(
            shapes, repeat=max(2, args.repeat // 2), force=args.force,
            cache_path=args.cache,
        )
        for r in rows:
            print(json.dumps(r))
        return 0

    from .profiler import profile_suite

    records = profile_suite(
        args.out, scale=args.scale, repeat=args.repeat, names=args.names,
    )
    for rec in records:
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
