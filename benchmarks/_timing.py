"""Shared timing harness for the benchmark scripts."""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Callable, Dict

import jax


def timed_ms(fn: Callable, *args: Any, warmup: int = 2, repeat: int = 20) -> float:
    """Mean wall milliseconds per call
    (:func:`byzpy_tpu.observability.compat.timed_call_s`)."""
    from byzpy_tpu.observability.compat import timed_call_s

    return timed_call_s(fn, *args, warmup=warmup, repeat=repeat) * 1e3


def report(name: str, ms: float, **extra: Any) -> Dict[str, Any]:
    row = {"workload": name, "ms": round(ms, 3), **extra}
    print(json.dumps(row))
    print(f"{name:48s} {ms:10.3f} ms  {extra or ''}", file=sys.stderr)
    return row


def force_cpu_platform(n_devices: int = 1) -> None:
    """Rebuild jax on the CPU platform in-process (optionally with virtual
    devices), through jax.config + clear_backends so it also works after
    a backend initialized. One copy for every benchmark script;
    ``__graft_entry__._ensure_devices`` stays self-contained by design
    (the driver runs it without this package on the path)."""
    from jax.extend import backend as jeb

    jax.config.update("jax_platforms", "cpu")
    jeb.clear_backends()
    if n_devices > 1:
        jax.config.update("jax_num_cpu_devices", n_devices)
        jeb.clear_backends()


__all__ = ["timed_ms", "report", "force_cpu_platform"]
