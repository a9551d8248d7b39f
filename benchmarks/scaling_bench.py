"""Fused SPMD parameter-server steps/sec vs mesh size.

North-star sweep (BASELINE.json): PS steps/sec scaling 8→128 chips with
≥90% efficiency. Runs over however many devices are visible — on a pod
slice that's real chips over ICI; locally use a virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/scaling_bench.py
"""

import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _here)                      # for _timing
sys.path.insert(0, os.path.dirname(_here))     # repo root

import time
from functools import partial

import jax
import jax.numpy as jnp

from _timing import report
from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.ops import robust
from byzpy_tpu.parallel.mesh import make_mesh, sharding
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

BATCH = 32


def steps_per_sec(n_devices, repeat=20):
    devices = jax.devices()[:n_devices]
    mesh = make_mesh([n_devices], ("nodes",), devices=devices)
    n_nodes = n_devices
    n_byz = n_nodes // 8
    cfg = PSStepConfig(n_nodes=n_nodes, n_byzantine=n_byz, learning_rate=0.05)
    bundle = mnist_mlp(seed=0, hidden=256)
    # trim as many as we can justify while keeping 2f < n
    f = min(max(n_byz, 1), (n_nodes - 1) // 2) if n_nodes > 2 else 0

    step, opt_state = build_ps_train_step(
        bundle, partial(robust.trimmed_mean, f=f), cfg, mesh=mesh
    )
    jit_step = jax.jit(step)
    xs = jax.device_put(
        jnp.zeros((n_nodes, BATCH, 28, 28, 1), jnp.float32), sharding(mesh, "nodes")
    )
    ys = jax.device_put(jnp.zeros((n_nodes, BATCH), jnp.int32), sharding(mesh, "nodes"))
    key = jax.random.PRNGKey(0)
    params = bundle.params

    params, opt_state, _ = jit_step(params, opt_state, xs, ys, key)  # compile
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(repeat):
        params, opt_state, _ = jit_step(params, opt_state, xs, ys, key)
    jax.block_until_ready(params)
    return repeat / (time.perf_counter() - t0)


def _ensure_virtual_devices(want: int = 8) -> None:
    """With fewer than ``want`` real devices, fall back to a virtual CPU
    mesh (rebuilt via jax.config + clear_backends). ROADMAP S5/D6: a
    scaling number from this fallback is one CPU split ``want`` ways, not
    a measurement."""
    if os.environ.get("SCALING_FORCE_CPU") != "1":
        if len(jax.devices()) >= want:
            return
    from _timing import force_cpu_platform

    force_cpu_platform(want)
    print(f"# fell back to {len(jax.devices())} virtual CPU devices", file=sys.stderr)


def main():
    _ensure_virtual_devices()
    n = len(jax.devices())
    sizes = [s for s in (1, 2, 4, 8, 16, 32, 64, 128) if s <= n]
    base = None
    for s in sizes:
        sps = steps_per_sec(s)
        if base is None:
            base = sps
        # ideal weak scaling: constant steps/sec as nodes (and total work)
        # grow with the mesh; efficiency = sps / single-device sps
        report(
            f"spmd_ps_steps_per_sec_{s}dev",
            1000.0 / sps,
            steps_per_sec=round(sps, 2),
            weak_scaling_efficiency=round(sps / base, 3),
        )


if __name__ == "__main__":
    main()
