"""Decompose the Multi-Krum 64x1M headline: where do the milliseconds go?

Measures each stage of the pipeline independently, plus pure-bandwidth and
dispatch-overhead floors, to localise the gap between the measured aggregate
latency and the HBM roofline (~268 MB of input -> ~0.33 ms at v5e's
~819 GB/s).

Usage:  python benchmarks/profile_krum.py [--trace DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from byzpy_tpu.ops import robust
from byzpy_tpu.observability.compat import timed_call_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None, help="jax.profiler trace dir")
    ap.add_argument("--repeat", type=int, default=50)
    args = ap.parse_args()

    n, d = 64, 1_048_576
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    xb = x.astype(jnp.bfloat16)
    nbytes = x.nbytes

    t = partial(timed_call_s, warmup=3, repeat=args.repeat)

    results = {}

    # Floors.
    results["noop_scalar"] = t(jax.jit(lambda v: v[0, 0] * 1.0), x)
    results["read_sum"] = t(jax.jit(lambda v: jnp.sum(v)), x)  # one full HBM read
    results["copy"] = t(jax.jit(lambda v: v * 1.0000001), x)  # read + write

    # Stages.
    results["gram_f32"] = t(jax.jit(robust.gram_matrix), x)
    results["gram_bf16"] = t(jax.jit(robust.gram_matrix), xb)
    results["pairwise_f32"] = t(jax.jit(robust.pairwise_sq_dists), x)
    results["krum_scores"] = t(jax.jit(partial(robust.krum_scores, f=8)), x)
    results["multi_krum"] = t(jax.jit(partial(robust.multi_krum, f=8, q=12)), x)
    results["multi_krum_bf16"] = t(jax.jit(partial(robust.multi_krum, f=8, q=12)), xb)

    # Selection tail in isolation: mean of q gathered rows.
    sel = jnp.arange(12, dtype=jnp.int32)
    results["gather_mean"] = t(jax.jit(lambda v, s: jnp.mean(v[s], axis=0)), x, sel)

    # Coordinate-median headline cousin.
    results["coord_median"] = t(jax.jit(robust.coordinate_median), x)
    results["sort_axis0"] = t(jax.jit(lambda v: jnp.sort(v, axis=0)), x)

    bw = {k: nbytes / v / 1e9 for k, v in results.items() if k in ("read_sum", "gram_f32")}
    print(json.dumps({
        "device": str(jax.devices()[0]),
        "nbytes_MB": round(nbytes / 1e6, 1),
        "ms": {k: round(v * 1e3, 3) for k, v in results.items()},
        "effective_GBps": {k: round(v, 1) for k, v in bw.items()},
    }, indent=2))

    if args.trace:
        from byzpy_tpu.observability.compat import trace
        fn = jax.jit(partial(robust.multi_krum, f=8, q=12))
        jax.block_until_ready(fn(x))
        with trace(args.trace):
            for _ in range(10):
                out = fn(x)
            jax.block_until_ready(out)
        print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
