"""Headline timings of the robust aggregate and the fused PS round, on a TPU.

Prints ONE JSON line. Every number in it was taken on the device the
line names (``platform``, ``device_kind``, ``device_count``); without a
TPU the script exits non-zero with the reason and prints nothing else.
Nothing here falls back: a kernel that fails to compile, an unknown
``device_kind`` or a failed step is the script's failure.

* ``multi_krum_64x1M_stream_grads_per_sec`` — Multi-Krum on a
  64 x 1,048,576 gradient matrix (BASELINE.json: "robust-agg grads/sec
  (Krum, CW-Median) at 1M-dim"), K rounds per dispatch.
* ``vs_baseline`` — geometric-mean speedup over the reference's best
  published ActorPool latencies on the two matched workloads it publishes
  (Multi-Krum 80x65,536 f=20 q=12 -> 26.30 ms; CW-Median 64x65,536 ->
  37 ms; BASELINE.md).
* ``second_metric`` — BASELINE config #3: PS steps/sec (MNIST MLP,
  trimmed mean, sign-flip) on the fused SPMD round.

ROADMAP S0 replaces this script with the benchmark proper (cells, a
trace reduction, regression bounds); until then it is the only timing
entry point and it is strict about where it ran.
"""

from __future__ import annotations

import json
import sys
from functools import partial

import jax
import jax.numpy as jnp


def _require_tpu() -> list:
    devices = jax.devices()
    wrong = sorted({d.platform for d in devices} - {"tpu"})
    if wrong:
        sys.exit(
            f"bench.py measures on a TPU; jax.devices() reports platform(s) "
            f"{wrong}. A CPU timing is not a device metric — nothing printed."
        )
    return devices


def timed(fn, *args, warmup: int = 2, repeat: int = 20) -> float:
    """Mean wall seconds per call around ``block_until_ready``."""
    from byzpy_tpu.observability.compat import timed_call_s

    return timed_call_s(fn, *args, warmup=warmup, repeat=repeat)


def main() -> None:
    devices = _require_tpu()

    from byzpy_tpu.ops import robust
    from byzpy_tpu.profiling import detect_hardware, roofline_s
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    spec = detect_hardware()  # raises on a device_kind with no published peaks
    key = jax.random.PRNGKey(0)

    # K rounds per dispatch as ONE fused Pallas launch
    # (selection_mean_stream_pallas via multi_krum_stream): 2K HBM sweeps,
    # no per-round slice copies.
    K = 32
    xs_1m = jax.random.normal(key, (K, 64, 1_048_576), jnp.float32)
    stream = jax.jit(partial(robust.multi_krum_stream, f=8, q=12))
    t_krum_1m = timed(stream, xs_1m, repeat=40) / K
    t_bf16 = timed(stream, xs_1m.astype(jnp.bfloat16), repeat=40) / K
    t_single = timed(jax.jit(partial(robust.multi_krum, f=8, q=12)), xs_1m[0])
    del xs_1m

    # Matched reference workloads for vs_baseline.
    x_krum = jax.random.normal(key, (80, 65_536), jnp.float32)
    t_krum = timed(jax.jit(partial(robust.multi_krum, f=20, q=12)), x_krum)
    x_med = jax.random.normal(key, (64, 65_536), jnp.float32)
    t_med = timed(jax.jit(robust.coordinate_median), x_med)
    ref_best = {"krum": 26.30e-3, "median": 37e-3}  # BASELINE.md best-pool
    speedup = ((ref_best["krum"] / t_krum) * (ref_best["median"] / t_med)) ** 0.5

    n, d = 64, 1 << 20
    floor_s = roofline_s(
        2.0 * n * n * d,  # the Gram contraction's FLOPs
        n * d * 4 + d * 4,  # read the round once, write the aggregate
        dtype="float32", spec=spec,
    )

    print(json.dumps({
        "metric": "multi_krum_64x1M_stream_grads_per_sec",
        "value": round(64 / t_krum_1m, 2),
        "unit": "grads/sec",
        "vs_baseline": round(speedup, 2),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # parallel.ps resolves mesh=None to the default mesh, which is
        # unset here: everything below ran on device 0 alone
        "mesh": "none (device 0 only)",
        "stream_K": K,
        "bf16_stream_grads_per_sec": round(64 / t_bf16, 2),
        "single_dispatch_grads_per_sec": round(64 / t_single, 2),
        "roofline": {
            "achieved_fraction": round(floor_s / t_krum_1m, 4),
            "roofline_ms_per_round": round(floor_s * 1e3, 4),
            "hardware": spec.name,
        },
        "second_metric": _ps_steps_metric(),
    }))


def _ps_steps_metric() -> dict:
    """BASELINE.json's second metric: PS steps/sec (MNIST MLP, trimmed
    mean, sign-flip — BASELINE config #3) on the fused SPMD round, one
    chip. (The HLO-derived 8→128-chip projection is
    ``benchmarks/ps_scaling_probe.py``'s own output, on the CPU mesh; it
    is a model and no longer rides a line of device numbers.)"""
    from byzpy_tpu.models import mnist_mlp, synthetic_classification
    from byzpy_tpu.ops import attack_ops, robust
    from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step

    n, n_byz, batch = 8, 2, 64
    bundle = mnist_mlp()
    x, y = synthetic_classification(n_samples=n * batch, seed=3)
    xs = x.reshape(n, batch, 28, 28, 1)
    ys = y.reshape(n, batch)
    step, opt0 = jit_ps_train_step(
        bundle,
        lambda m: robust.trimmed_mean(m, f=n_byz),
        PSStepConfig(n_nodes=n, n_byzantine=n_byz),
        attack=lambda honest, key: attack_ops.sign_flip(
            jnp.mean(honest, axis=0)
        ),
        donate=False,
    )
    t_round = timed(
        step, bundle.params, opt0, xs, ys, jax.random.PRNGKey(0), repeat=30
    )
    return {
        "metric": "ps_mnist_trimmed_mean_steps_per_sec",
        "value": round(1.0 / t_round, 2),
        "unit": "steps/sec",
        # ref: actor-mode PS MNIST round, best measured 42 ms/round
        # (BASELINE.md; reference benchmarks) -> 23.8 steps/sec
        "vs_baseline": round((1.0 / t_round) / (1.0 / 42e-3), 2),
        "round_ms": round(t_round * 1e3, 3),
        "config": "MNIST MLP 784-128-10, n=8 nodes (2 byzantine), "
                  "trimmed-mean f=2, sign-flip, batch 64/node, "
                  "fused SPMD round on one chip",
    }


if __name__ == "__main__":
    main()
