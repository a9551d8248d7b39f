"""The full reference benchmark grid, measured on this machine's default
JAX backend (the real TPU chip under the driver).

One row per workload of ``byzpy/benchmarks/README.md:10-30`` — identical
shapes and hyperparameters — plus the 1M-dim north-star shapes. Each JSON
line carries the reference's published CPU latencies (ByzFL, ByzPy direct,
ByzPy best pool; from BASELINE.md, timeouts as None) so speedups are
computed from committed data, not prose.

Usage: python benchmarks/full_grid.py [--repeat N] > benchmarks/results/grid.jsonl
"""

import argparse
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)                      # for _timing
sys.path.insert(0, os.path.dirname(_here))     # repo root

import asyncio
from functools import partial

import jax
import jax.numpy as jnp

from _timing import report, timed_ms
from byzpy_tpu.aggregators import MinimumDiameterAveraging, MultiKrum, SMEA
from byzpy_tpu.engine.parameter_server import ParameterServer
from byzpy_tpu.ops import attack_ops, preagg, robust


def grads(n, d, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d), jnp.float32)


def row(name, ms, byzfl, direct, best_pool, **extra):
    """Emit one grid row with the reference floor and computed speedups.
    "Best" = the reference's best published number: its best pool, or its
    direct time where its own pooling made it slower (same rule as
    generate_plots.py / RESULTS.md)."""
    candidates = [v for v in (best_pool, direct) if v is not None]
    best = min(candidates) if candidates else None
    speedup = round(best / ms, 2) if best else None
    report(
        name, ms,
        ref_byzfl_ms=byzfl, ref_direct_ms=direct, ref_best_pool_ms=best_pool,
        speedup_vs_ref_best=speedup, **extra,
    )


def ps_multi_krum_round_ms(rounds=50):
    """Reference row 12: end-to-end PS with Multi-Krum, 10 honest + 3
    byzantine nodes, 50 rounds (ref benchmarks/README.md:23). Nodes hold
    SmallCNN-scale gradients (d=21,840 ~= the reference's MNIST SmallCNN).

    Node-local gradient computation happens on the HOST (numpy), exactly
    like the reference's CPU nodes — and so do the attack and the robust
    aggregate, via the framework's latency-aware placement policy
    (``utils.placement``): all inputs are host-resident and far below the
    size cap, so the whole round runs on the CPU backend with ZERO
    accelerator traffic.
    Device-resident nodes belong to the fused SPMD path (parallel/ps.py)."""
    import numpy as np
    import time

    from byzpy_tpu.attacks import EmpireAttack

    d = 21_840

    class Node:
        def __init__(self, i):
            self.rng = np.random.default_rng(i)
            self.grad = None

        def honest_gradient_for_next_batch(self):
            return [self.rng.standard_normal(d, dtype=np.float32)]

        def apply_server_gradient(self, g):
            self.grad = g

    class Byz(Node):
        attack = EmpireAttack(scale=-1.0)

        def byzantine_gradient_for_next_batch(self, honest):
            return [self.attack.apply_placed(honest_grads=[h[0] for h in honest])]

    ps = ParameterServer(
        honest_nodes=[Node(i) for i in range(10)],
        byzantine_nodes=[Byz(100 + i) for i in range(3)],
        aggregator=MultiKrum(f=3, q=5),
    )

    async def run():
        for _ in range(rounds):
            out = await ps.round()
        jax.block_until_ready(out)

    # warmup (compile)
    asyncio.run(_once(ps))
    t0 = time.perf_counter()
    asyncio.run(run())
    total = time.perf_counter() - t0
    return total / rounds * 1e3


async def _once(ps):
    out = await ps.round()
    jax.block_until_ready(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeat", type=int, default=20)
    args = ap.parse_args()
    r = args.repeat

    t = partial(timed_ms, repeat=r)
    print(f"# backend={jax.default_backend()} device={jax.devices()[0]}",
          file=sys.stderr)

    # -- the reference's 19-workload grid (BASELINE.md rows, same order) -----
    mda = MinimumDiameterAveraging(f=10)
    row("mda_30x2048_f10", t(lambda x: mda.aggregate(x), grads(30, 2048)),
        None, 353, 166)
    smea = SMEA(f=5)
    row("smea_16x4096_f5", t(lambda x: smea.aggregate(x), grads(16, 4096)),
        None, 82, 48.0)
    row("arc_256x65536_f8", t(jax.jit(partial(preagg.arc_clip, f=8)), grads(256, 65536)),
        191.27, 20.77, 50.87)
    row("cw_trimmed_mean_64x65536_f8",
        t(jax.jit(partial(robust.trimmed_mean, f=8)), grads(64, 65536)),
        68.08, 65.52, 15.15)
    row("cw_median_64x65536", t(jax.jit(robust.coordinate_median), grads(64, 65536)),
        None, 52, 37)
    row("multi_krum_80x65536_f20_q12",
        t(jax.jit(partial(robust.multi_krum, f=20, q=12)), grads(80, 65536)),
        78.17, 59.66, 26.30)
    row("geometric_median_64x65536",
        t(jax.jit(robust.geometric_median), grads(64, 65536)),
        None, 398.21, 142.97)
    row("caf_64x65536_f8", t(jax.jit(partial(robust.caf, f=8)), grads(64, 65536)),
        72.65, 54.51, 54.94)
    row("monna_64x65536_f8", t(jax.jit(partial(robust.monna, f=8)), grads(64, 65536)),
        51, 67, 11)
    row("centered_clipping_64x65536_M10",
        t(jax.jit(partial(robust.centered_clipping, c_tau=10.0, M=10)), grads(64, 65536)),
        146, 112, 50)
    row("cge_64x65536_f8", t(jax.jit(partial(robust.cge, f=8)), grads(64, 65536)),
        None, 100, 23)
    row("ps_multi_krum_10h_3b_per_round", ps_multi_krum_round_ms(),
        57, 71, 42, rounds=50)
    row("empire_64x65536",
        t(jax.jit(partial(attack_ops.empire, scale=-1.0)), grads(64, 65536)),
        50, 34, 14)
    row("little_96x65536_f12",
        t(jax.jit(partial(attack_ops.little, f=12, n_total=96)), grads(96, 65536)),
        70.39, 67.03, 32.86)
    row("gaussian_64x65536",
        t(jax.jit(lambda k: attack_ops.gaussian(k, (65536,))), jax.random.PRNGKey(1)),
        44.33, 12.6, 12.3)
    row("nnm_196x4096_f32", t(jax.jit(partial(preagg.nnm, f=32)), grads(196, 4096)),
        58, 12, 137)
    row("meamed_64x65536_f8",
        t(jax.jit(partial(robust.mean_of_medians, f=8)), grads(64, 65536)),
        152, 113, 59)
    perm = jax.random.permutation(jax.random.PRNGKey(2), 512)
    row("bucketing_512x16384_b32",
        t(jax.jit(partial(preagg.bucket_means, bucket_size=32)),
          grads(512, 16384), perm),
        23, 13.4, 21.7)
    row("clipping_256x65536_t2",
        t(jax.jit(partial(preagg.clip_rows, threshold=2.0)), grads(256, 65536)),
        382, 46, 61)

    # -- north-star 1M-dim shapes (no published reference numbers) ----------
    report("cw_median_64x1M", t(jax.jit(robust.coordinate_median), grads(64, 1 << 20)))
    report("multi_krum_64x1M_f8_q12",
           t(jax.jit(partial(robust.multi_krum, f=8, q=12)), grads(64, 1 << 20)))
    report("multi_krum_bf16_64x1M_f8_q12",
           t(jax.jit(partial(robust.multi_krum, f=8, q=12)),
             grads(64, 1 << 20).astype(jnp.bfloat16)))


if __name__ == "__main__":
    main()
