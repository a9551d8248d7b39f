"""Achieved-vs-roofline profiler for the ``ops.robust`` hot path.

:func:`profile_call` wraps any jit-compatible entry point: it lowers and
compiles the function, pulls XLA's own cost analysis
(``lowered.compile().cost_analysis()`` — program FLOPs and bytes
accessed), measures wall time around ``block_until_ready``, and scores
the result against the hardware roofline (:mod:`.roofline`). One JSONL
row per (kernel, shape, dtype) with full provenance.

:func:`profile_suite` runs the whole ``ops.robust`` aggregator family at
the BASELINE.md grid shapes (plus the 1M-dim north-star shapes) — the
measurement the ISSUE's "achieved-vs-roofline fraction per (kernel,
shape, dtype)" acceptance row refers to. CLI:
``python -m byzpy_tpu.profiling --out benchmarks/results/roofline.jsonl``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .roofline import (
    HardwareSpec,
    bound_kind,
    detect_hardware,
    roofline_s,
    traffic_floor_bytes,
)


def _git_rev() -> Optional[str]:
    try:
        import subprocess

        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return None


def provenance() -> Dict[str, Any]:
    """Measurement provenance stamped onto every record: platform, device
    kind, jax version, git revision, UTC time."""
    import jax

    dev = jax.devices()[0]
    return {
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", None),
        "jax": jax.__version__,
        "git_rev": _git_rev(),
    }


def xla_cost(fn: Callable, *args: Any) -> Dict[str, Optional[float]]:
    """XLA cost analysis for ``jit(fn)(*args)``: program FLOPs and bytes
    accessed (``None`` where the backend exposes no analysis — e.g. some
    custom-call-only programs)."""
    import jax

    try:
        analysis = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        return {
            "flops": float(analysis["flops"]) if "flops" in analysis else None,
            "bytes_accessed": (
                float(analysis["bytes accessed"])
                if "bytes accessed" in analysis else None
            ),
        }
    except Exception:  # noqa: BLE001 — cost analysis is advisory
        return {"flops": None, "bytes_accessed": None}


def profile_call(
    fn: Callable,
    *args: Any,
    name: str,
    spec: Optional[HardwareSpec] = None,
    warmup: int = 2,
    repeat: int = 10,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Measure one entry point against the roofline.

    Returns a JSONL-ready record: measured wall ms, XLA cost analysis,
    the analytic traffic floor (inputs read once + output written once),
    the roofline floor time, and ``achieved_fraction`` = floor / measured
    (1.0 = running at the hardware limit). ``extra`` keys are merged into
    the record (hyper-parameters, workload tags)."""
    import jax

    from ..observability.compat import timed_call_s

    spec = spec or detect_hardware(calibrate=jax.default_backend() == "cpu")
    jfn = jax.jit(fn)
    cost = xla_cost(fn, *args)
    out = jfn(*args)
    floor_bytes = traffic_floor_bytes(args, out)
    measured_s = timed_call_s(jfn, *args, warmup=warmup, repeat=repeat)

    leaves = jax.tree_util.tree_leaves(args)
    dtype = str(leaves[0].dtype) if leaves else "float32"
    shape = tuple(getattr(leaves[0], "shape", ())) if leaves else ()
    flops = cost["flops"] or 0.0
    floor_s = roofline_s(flops, floor_bytes, dtype=dtype, spec=spec)
    record: Dict[str, Any] = {
        "name": name,
        "shape": list(shape),
        "dtype": dtype,
        "measured_ms": round(measured_s * 1e3, 4),
        "xla_flops": cost["flops"],
        "xla_bytes_accessed": cost["bytes_accessed"],
        "floor_bytes": floor_bytes,
        "hbm_sweeps": (
            round(cost["bytes_accessed"] / floor_bytes, 2)
            if cost["bytes_accessed"] and floor_bytes else None
        ),
        "roofline_ms": round(floor_s * 1e3, 4),
        "achieved_fraction": (
            round(floor_s / measured_s, 4) if measured_s > 0 else None
        ),
        "bound": bound_kind(flops, floor_bytes, dtype=dtype, spec=spec),
        "hardware": {
            "name": spec.name,
            "mem_bw_gbps": spec.mem_bw_gbps,
            "peak_gflops": spec.peak_gflops,
            "source": spec.source,
        },
        "provenance": provenance(),
    }
    if extra:
        record.update(extra)
    return record


def write_jsonl(records: Sequence[Dict[str, Any]], path: str) -> str:
    """Append records to a JSONL file (parent dirs created)."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "a") as fh:
        for rec in records:
            fh.write(json.dumps(rec) + "\n")
    return path


def baseline_workloads(
    *, scale: float = 1.0, include_stream: bool = True
) -> List[Tuple[str, Callable, Tuple[int, ...], Dict[str, Any]]]:
    """The BASELINE.md grid shapes for every ``ops.robust`` aggregator:
    ``(name, fn, shape, extra)`` tuples ready for :func:`profile_call`.

    ``scale`` shrinks the feature dimension (CI/tests run the machinery
    at toy sizes); ``include_stream`` adds the 1M-dim north-star stream
    shapes (the training-loop form)."""
    from ..ops import robust

    d64k = max(256, int(65_536 * scale))
    d1m = max(512, int((1 << 20) * scale))

    loads: List[Tuple[str, Callable, Tuple[int, ...], Dict[str, Any]]] = [
        ("cw_median", robust.coordinate_median, (64, d64k), {}),
        ("cw_trimmed_mean", partial(robust.trimmed_mean, f=8), (64, d64k),
         {"f": 8}),
        ("meamed", partial(robust.mean_of_medians, f=8), (64, d64k),
         {"f": 8}),
        ("multi_krum", partial(robust.multi_krum, f=20, q=12), (80, d64k),
         {"f": 20, "q": 12}),
        ("krum", partial(robust.krum, f=8), (64, d64k), {"f": 8}),
        ("geometric_median", robust.geometric_median, (64, d64k), {}),
        ("centered_clipping",
         partial(robust.centered_clipping, c_tau=10.0, M=10), (64, d64k),
         {"c_tau": 10.0, "M": 10}),
        ("cge", partial(robust.cge, f=8), (64, d64k), {"f": 8}),
        ("monna", partial(robust.monna, f=8), (64, d64k), {"f": 8}),
        ("caf", partial(robust.caf, f=8), (64, d64k), {"f": 8}),
    ]
    if include_stream:
        loads += [
            ("multi_krum_1M", partial(robust.multi_krum, f=8, q=12),
             (64, d1m), {"f": 8, "q": 12}),
            ("cw_median_1M", robust.coordinate_median, (64, d1m), {}),
        ]
    return loads


def profile_suite(
    out_path: Optional[str] = None,
    *,
    scale: float = 1.0,
    repeat: int = 10,
    names: Optional[Sequence[str]] = None,
    spec: Optional[HardwareSpec] = None,
    verbose: bool = True,
) -> List[Dict[str, Any]]:
    """Profile every ``ops.robust`` aggregator at the BASELINE.md shapes
    and (optionally) append the records to ``out_path`` as JSONL."""
    import jax
    import jax.numpy as jnp

    records = []
    for name, fn, shape, extra in baseline_workloads(scale=scale):
        if names and name not in names:
            continue
        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
        rec = profile_call(
            fn, x, name=name, spec=spec, repeat=repeat,
            extra={"workload": f"{name}_{shape[0]}x{shape[1]}", **extra},
        )
        records.append(rec)
        if verbose:
            print(
                f"{rec['workload']:36s} {rec['measured_ms']:10.3f} ms  "
                f"roofline {rec['roofline_ms']:8.3f} ms  "
                f"achieved {rec['achieved_fraction']:.3f}  "
                f"[{rec['bound']}-bound]",
                file=sys.stderr,
            )
    if out_path:
        write_jsonl(records, out_path)
    return records


__all__ = [
    "baseline_workloads",
    "profile_call",
    "profile_suite",
    "provenance",
    "write_jsonl",
    "xla_cost",
]
