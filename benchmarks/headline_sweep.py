"""Headline optimization sweep: Multi-Krum 64x1M grads/sec variants.

The two-pass f32 floor is ~98k grads/sec (x read twice: Gram + selection
matvec = 536 MB at ~819 GB/s = ~0.65 ms per aggregate). This sweep
isolates what the round-2 streamed headline (40.7k) was losing to:

* scan vs vmap batching of the K rounds (scan slices 256 MB per step
  from the stacked input — if XLA materializes that slice it's a whole
  extra read+write per aggregate);
* f32 vs bf16 input (halves both passes' traffic);
* the d2-sort/rank tail (measured via krum_scores alone).

Usage: python benchmarks/headline_sweep.py [--K 8] [--repeat 15]
(the scan-of-kernel rows dominate the run time)
"""

import argparse
import json
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)
sys.path.insert(0, os.path.dirname(_here))

from functools import partial

import jax
import jax.numpy as jnp

from byzpy_tpu.ops import robust
from byzpy_tpu.observability.compat import timed_call_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--K", type=int, default=8)
    ap.add_argument("--repeat", type=int, default=15)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--d", type=int, default=1_048_576)
    args = ap.parse_args()
    K, n, d = args.K, args.n, args.d

    t = partial(timed_call_s, warmup=3, repeat=args.repeat)
    agg = partial(robust.multi_krum, f=8, q=12)
    xs = jax.random.normal(jax.random.PRNGKey(0), (K, n, d), jnp.float32)
    xb = xs.astype(jnp.bfloat16)

    rows = {}

    def rec(name, secs, per_agg_div=K):
        per = secs / per_agg_div
        rows[name] = {"ms_per_agg": round(per * 1e3, 3),
                      "grads_per_sec": round(n / per, 1)}
        print(json.dumps({"workload": name, **rows[name]}), flush=True)

    # per-call single dispatch (round-1 comparable)
    rec("single_dispatch_f32", t(jax.jit(agg), xs[0]), per_agg_div=1)

    # K rounds per dispatch: scan (round-2 headline shape)
    scan_fn = jax.jit(partial(robust.aggregate_stream, agg))
    rec("stream_scan_f32", t(scan_fn, xs))

    # K rounds per dispatch: vmap (batched matmuls, no per-step slice)
    vmap_fn = jax.jit(jax.vmap(agg))
    rec("stream_vmap_f32", t(vmap_fn, xs))

    # K rounds as ONE fused Pallas launch (round-3 headline shape):
    # 2 HBM sweeps per round, no per-round slice copies
    fused_fn = jax.jit(partial(robust.multi_krum_stream, f=8, q=12))
    rec("stream_fused_f32", t(fused_fn, xs))

    # bf16 variants
    rec("stream_scan_bf16", t(scan_fn, xb))
    rec("stream_vmap_bf16", t(vmap_fn, xb))
    rec("stream_fused_bf16", t(fused_fn, xb))

    # stage floors
    rec("krum_scores_only_f32",
        t(jax.jit(jax.vmap(partial(robust.krum_scores, f=8))), xs))
    rec("gram_only_f32", t(jax.jit(jax.vmap(robust.gram_matrix)), xs))
    rec("read_sum_floor", t(jax.jit(lambda v: jnp.sum(v, axis=(1, 2))), xs))


if __name__ == "__main__":
    main()
