"""MeaMed dispatch-gate tuner (fold a tuned floor into the dispatch).

``MEAMED_MIN_DIM`` gates when ``ops.robust.mean_of_medians`` hands a
matrix to the fused single-sweep Pallas kernel instead of the XLA
sort/window/mask pipeline. This script derives/validates that floor:

* **CPU** (``JAX_PLATFORMS=cpu`` — always available): measures the XLA
  path's traffic multiple via XLA's own cost analysis (bytes accessed /
  the read-once-write-once floor; 24.7x at the grid row). The fused
  kernel moves ~1x the floor, so the crossover sits far below the
  generic ``MIN_PALLAS_DIM`` (256k dims, tuned for the ~2-pass sort
  kernels). The committed ``MEAMED_MIN_DIM = 64k`` is the conservative
  1/4-of-generic estimate (the kernel docstrings' ~4 TPU passes); the
  CPU pass-ratio evidence says lower would still win.
* **TPU** (through the chip tool; not run yet — ROADMAP S4/D5): times
  BOTH paths across a shape sweep and prints the measured crossover —
  the authoritative number. Commit it to
  ``byzpy_tpu/ops/pallas_kernels.py::MEAMED_MIN_DIM`` when it lands.

The floor is read per call in ``mean_of_medians``'s Python wrapper
(``BYZPY_TPU_MEAMED_MIN_DIM`` override wins), BEFORE anything traces —
flipping it between calls of the same shape redispatches immediately,
so this harness needs no cache clearing.

Run: ``python benchmarks/meamed_gate_tune.py`` (on either backend).
"""

from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp

from byzpy_tpu.ops import robust
from byzpy_tpu.ops.pallas_kernels import MEAMED_MIN_DIM, meamed_stream_pallas
from byzpy_tpu.observability.compat import timed_call_s

SHAPES = [
    (64, 16_384),
    (64, 65_536),
    (64, 262_144),
    (64, 1_048_576),
]


def _cpu_pass_ratio(n: int = 64, d: int = 65_536, f: int = 8) -> dict:
    """XLA path traffic multiple over the read-once floor, from XLA's
    own cost analysis — the CPU-derivable evidence behind the committed
    floor (the fused kernel reads the matrix exactly once)."""
    from byzpy_tpu.profiling.profiler import xla_cost

    x = jax.random.normal(jax.random.PRNGKey(7), (n, d), jnp.float32)
    os.environ["BYZPY_TPU_MEAMED_MIN_DIM"] = str(1 << 60)  # force XLA path
    try:
        cost = xla_cost(functools.partial(robust.mean_of_medians, f=f), x)
    finally:
        os.environ.pop("BYZPY_TPU_MEAMED_MIN_DIM", None)
    floor = (n * d + d) * 4
    ratio = (cost["bytes_accessed"] / floor) if cost["bytes_accessed"] else None
    return {
        "workload": f"meamed_xla_pass_ratio_{n}x{d}_f{f}",
        "xla_bytes_accessed": cost["bytes_accessed"],
        "floor_bytes": floor,
        "pass_ratio": round(ratio, 2) if ratio else None,
        "derived_floor": (
            int(262_144 / ratio) if ratio and ratio > 1 else None
        ),
        "committed_MEAMED_MIN_DIM": MEAMED_MIN_DIM,
    }


def main() -> None:
    on_tpu = jax.default_backend() == "tpu"
    print(json.dumps(_cpu_pass_ratio()))
    if not on_tpu:
        print(json.dumps({
            "note": "CPU run: interpret-mode kernel timings say nothing "
                    "about Mosaic, so no crossover is measured here. The "
                    "pass-ratio row above is the CPU-derived evidence for "
                    f"the committed floor ({MEAMED_MIN_DIM}); the sweep "
                    "below needs a TPU.",
        }))
        return

    crossover = None
    for n, d in SHAPES:
        x = jax.random.normal(jax.random.PRNGKey(7), (n, d), jnp.float32)
        # XLA path, forced via the floor override (read per call, so no
        # stale-trace hazard)
        os.environ["BYZPY_TPU_MEAMED_MIN_DIM"] = str(1 << 60)
        t_xla = timed_call_s(
            functools.partial(robust.mean_of_medians, f=8), x,
            warmup=2, repeat=20,
        ) * 1e3
        os.environ.pop("BYZPY_TPU_MEAMED_MIN_DIM", None)
        t_fused = timed_call_s(
            lambda a: meamed_stream_pallas(a[None], f=8)[0], x,
            warmup=2, repeat=20,
        ) * 1e3
        win = t_fused < t_xla
        if win and crossover is None:
            crossover = d
        print(json.dumps({
            "workload": f"meamed_{n}x{d}_f8",
            "xla_ms": round(t_xla, 2),
            "fused_ms": round(t_fused, 2),
            "fused_wins": bool(win),
        }))
    print(json.dumps({
        "recommended_MEAMED_MIN_DIM": crossover if crossover else "keep",
        "committed_MEAMED_MIN_DIM": MEAMED_MIN_DIM,
        "note": "set byzpy_tpu/ops/pallas_kernels.py MEAMED_MIN_DIM to the "
                "smallest d where the fused kernel wins, then refresh the "
                "grid row",
    }))


if __name__ == "__main__":
    main()
