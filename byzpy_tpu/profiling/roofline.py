"""Roofline model for the robust-aggregation hot path.

A kernel's wall-time floor on a chip is ``max(bytes / memory_bandwidth,
flops / peak_flops)`` (Williams et al. 2009). Every aggregator here is a
small-``n``-huge-``d`` streaming reduction, so the binding term is almost
always the bytes one — which is why the fused kernels in
``ops.pallas_kernels`` count HBM sweeps, not FLOPs, in their docstrings.
This module turns that accounting into numbers: a per-device
:class:`HardwareSpec` (known-chip table + env overrides + optional CPU
micro-calibration) and :func:`roofline_s`, the floor time for a measured
(flops, bytes, dtype) triple. ``profiler.profile_call`` divides the floor
by measured wall time to get the achieved-vs-roofline fraction the
ROADMAP's "as fast as the hardware allows" north star is tracked by.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict

_ENV_BW = "BYZPY_TPU_MEM_GBPS"
_ENV_F32 = "BYZPY_TPU_PEAK_GFLOPS_F32"
_ENV_BF16 = "BYZPY_TPU_PEAK_GFLOPS_BF16"


@dataclass(frozen=True)
class HardwareSpec:
    """One device's roofline parameters.

    ``mem_bw_gbps`` is main-memory (HBM/DRAM) bandwidth in GB/s;
    ``peak_gflops`` maps a dtype name (``"float32"``/``"bfloat16"``) to
    peak GFLOP/s. ``source`` records where the numbers came from
    (``"table"``, ``"env"``, ``"calibrated"``, ``"default"``) so JSONL
    rows are auditable."""

    name: str
    mem_bw_gbps: float
    peak_gflops: Dict[str, float] = field(default_factory=dict)
    source: str = "table"

    def peak_for(self, dtype: str) -> float:
        """Peak GFLOP/s for ``dtype`` (falls back to the float32 entry —
        conservative for narrower types)."""
        return self.peak_gflops.get(dtype, self.peak_gflops.get("float32", 1.0))


# Published (or widely-cited) chip parameters. The v5e bf16 number is the
# official 197 TFLOP/s; f32 MXU throughput is not published — 1/4 of bf16
# is the conventional estimate and is marked as such in `source`.
_KNOWN: Dict[str, HardwareSpec] = {
    "v5e": HardwareSpec(
        "tpu-v5e", 819.0, {"float32": 49_250.0, "bfloat16": 197_000.0}
    ),
    "v5 lite": HardwareSpec(
        "tpu-v5e", 819.0, {"float32": 49_250.0, "bfloat16": 197_000.0}
    ),
    "v4": HardwareSpec(
        "tpu-v4", 1228.0, {"float32": 68_750.0, "bfloat16": 275_000.0}
    ),
    "v3": HardwareSpec(
        "tpu-v3", 900.0, {"float32": 61_500.0, "bfloat16": 123_000.0}
    ),
}

# Process-wide calibration memo (CPU calibration costs ~1 s; do it once).
_CALIBRATED: Dict[str, HardwareSpec] = {}


def _env_overrides(spec: HardwareSpec) -> HardwareSpec:
    bw = os.environ.get(_ENV_BW)
    f32 = os.environ.get(_ENV_F32)
    bf16 = os.environ.get(_ENV_BF16)
    if not (bw or f32 or bf16):
        return spec
    peaks = dict(spec.peak_gflops)
    if f32:
        peaks["float32"] = float(f32)
    if bf16:
        peaks["bfloat16"] = float(bf16)
    return HardwareSpec(
        spec.name,
        float(bw) if bw else spec.mem_bw_gbps,
        peaks,
        source="env",
    )


def calibrate_cpu() -> HardwareSpec:
    """Measure this host's effective memory bandwidth (a 256 MB f32 copy)
    and matmul throughput (1024^3 f32 GEMM) through the jax CPU backend.
    ~1 s once per process; the result is memoized. These are *achievable*
    numbers (what XLA itself can reach), so CPU roofline fractions are
    honest rather than flattering."""
    if "cpu" in _CALIBRATED:
        return _CALIBRATED["cpu"]
    import jax
    import jax.numpy as jnp

    from ..observability.compat import timed_call_s

    m = 1 << 26  # 64M f32 = 256 MB
    x = jnp.zeros((m,), jnp.float32)
    copy = jax.jit(lambda a: a + 1.0)
    t_copy = timed_call_s(copy, x, warmup=1, repeat=3)
    bw = (2 * m * 4) / t_copy / 1e9  # read + write

    k = 1024
    a = jnp.zeros((k, k), jnp.float32)
    mm = jax.jit(lambda p: p @ p)
    t_mm = timed_call_s(mm, a, warmup=1, repeat=3)
    gflops = (2 * k**3) / t_mm / 1e9

    spec = HardwareSpec(
        "cpu", round(bw, 1),
        {"float32": round(gflops, 1), "bfloat16": round(gflops, 1)},
        source="calibrated",
    )
    _CALIBRATED["cpu"] = spec
    return spec


def detect_hardware(calibrate: bool = False) -> HardwareSpec:
    """Spec for jax's default device: the known-chip table keyed by
    ``device_kind``, env overrides (``BYZPY_TPU_MEM_GBPS`` /
    ``BYZPY_TPU_PEAK_GFLOPS_*``) applied on top. An accelerator whose
    ``device_kind`` is not in the table raises — a roofline share
    against another chip's peaks is not a number. On CPU,
    ``calibrate=True`` micro-benchmarks the host (preferred for real
    profiling runs); otherwise a labeled conservative default is used."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        if calibrate:
            return _env_overrides(calibrate_cpu())
        return _env_overrides(
            HardwareSpec("cpu-default", 30.0,
                         {"float32": 100.0, "bfloat16": 100.0},
                         source="default")
        )
    kind = dev.device_kind
    for marker, spec in _KNOWN.items():
        if marker in kind.lower():
            return _env_overrides(spec)
    raise ValueError(
        f"no published peaks for {dev.platform} device_kind {kind!r}; add "
        f"it to profiling.roofline._KNOWN with its source (known markers: "
        f"{sorted(_KNOWN)})"
    )


def roofline_s(
    flops: float, bytes_moved: float, *, dtype: str, spec: HardwareSpec
) -> float:
    """Roofline floor in seconds: ``max(bytes / BW, flops / peak)``."""
    t_mem = bytes_moved / (spec.mem_bw_gbps * 1e9) if bytes_moved else 0.0
    t_cmp = flops / (spec.peak_for(dtype) * 1e9) if flops else 0.0
    return max(t_mem, t_cmp)


def bound_kind(
    flops: float, bytes_moved: float, *, dtype: str, spec: HardwareSpec
) -> str:
    """Which roofline term binds: ``"memory"`` or ``"compute"``."""
    t_mem = bytes_moved / (spec.mem_bw_gbps * 1e9) if bytes_moved else 0.0
    t_cmp = flops / (spec.peak_for(dtype) * 1e9) if flops else 0.0
    return "memory" if t_mem >= t_cmp else "compute"


def traffic_floor_bytes(args, out) -> int:
    """The analytic bytes floor of any aggregate: every input read once,
    every output written once. XLA's ``bytes accessed`` measures what the
    *chosen program* touches (extra passes show up as a ratio above this
    floor — that ratio is exactly the "HBM sweeps" count the fused
    kernels advertise)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves((args, out)):
        size = getattr(leaf, "size", None)
        itemsize = getattr(leaf, "dtype", None)
        if size is not None and itemsize is not None:
            total += int(size) * leaf.dtype.itemsize
    return total


__all__ = [
    "HardwareSpec",
    "bound_kind",
    "calibrate_cpu",
    "detect_hardware",
    "roofline_s",
    "traffic_floor_bytes",
]
