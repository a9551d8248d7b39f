"""Registry-backed ports of the seed-era ``utils.metrics`` API.

:class:`MetricsLogger` and :class:`StepTimer` predate the telemetry
subsystem (SURVEY §5 flagged them as the print-replacement stopgap).
They keep their exact public behavior — step-keyed history, JSONL sink,
summaries, block-on-outputs timing — but now also PUBLISH into the
process :func:`~byzpy_tpu.observability.metrics.registry`: every
numeric ``log()`` value becomes a ``byzpy_logged_<key>`` gauge and
every ``StepTimer.stop`` lands in the ``byzpy_step_seconds`` histogram,
so a Prometheus scrape of a training process sees them without any
caller change. ``byzpy_tpu.utils.metrics`` re-exports these under a
deprecation shim.
"""

from __future__ import annotations

import contextlib
import json
import re
import time
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional

import jax

from . import metrics as _metrics


def _scalar(value: Any) -> Any:
    """Coerce device values to JSON-able python, recursively: 0-d arrays
    become numbers, n-d arrays nested lists, containers are walked, and
    anything else non-serializable falls back to ``str``."""
    ndim = getattr(value, "ndim", None)
    if ndim == 0 and hasattr(value, "item"):
        try:
            return value.item()
        except Exception:  # noqa: BLE001
            return str(value)
    if ndim is not None and ndim > 0 and hasattr(value, "tolist"):
        try:
            return value.tolist()
        except Exception:  # noqa: BLE001
            return str(value)
    if isinstance(value, dict):
        return {str(k): _scalar(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_scalar(v) for v in value]
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    return str(value)


_METRIC_SAFE = re.compile(r"[^a-zA-Z0-9_]")


def _gauge_name(key: str) -> str:
    return "byzpy_logged_" + _METRIC_SAFE.sub("_", key)


class MetricsLogger:
    """Step-keyed metrics with history and an optional JSONL file sink;
    numeric values are mirrored into the process metrics registry as
    ``byzpy_logged_<key>`` gauges (labelless, last-write-wins)."""

    def __init__(self, sink_path: Optional[str] = None) -> None:
        self.history: List[Dict[str, Any]] = []
        self._sink_path = sink_path
        self._sink = open(sink_path, "a") if sink_path else None
        self._registry = _metrics.registry()
        self._gauges: Dict[str, _metrics.Gauge] = {}

    def log(self, step: int, **values: Any) -> Dict[str, Any]:
        """Record one step's values; returns the JSON-able record."""
        record = {"step": int(step), "time": time.time()}
        record.update({k: _scalar(v) for k, v in values.items()})
        self.history.append(record)
        for k, v in record.items():
            if k in ("step", "time") or isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                gauge = self._gauges.get(k)
                if gauge is None:
                    gauge = self._gauges[k] = self._registry.gauge(
                        _gauge_name(k), help=f"last value logged under {k!r}"
                    )
                gauge.set(float(v))
        if self._sink is not None:
            self._sink.write(json.dumps(record) + "\n")
            self._sink.flush()
        return record

    def series(self, key: str) -> List[Any]:
        """Every recorded value of ``key``, in log order."""
        return [r[key] for r in self.history if key in r]

    def latest(self, key: str) -> Any:
        """Most recent value of ``key`` (KeyError if never logged)."""
        for r in reversed(self.history):
            if key in r:
                return r[key]
        raise KeyError(key)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """min/max/mean/last per numeric key."""
        by_key: Dict[str, List[float]] = defaultdict(list)
        for r in self.history:
            for k, v in r.items():
                if k in ("step", "time"):
                    continue
                if isinstance(v, (int, float)):
                    by_key[k].append(float(v))
        return {
            k: {
                "min": min(vs),
                "max": max(vs),
                "mean": sum(vs) / len(vs),
                "last": vs[-1],
                "count": len(vs),
            }
            for k, vs in by_key.items()
        }

    def close(self) -> None:
        """Close the JSONL sink (history stays readable)."""
        if self._sink is not None:
            self._sink.close()
            self._sink = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace (view with TensorBoard / Perfetto).
    Host spans recorded by :mod:`byzpy_tpu.observability.tracing` inside
    this window correlate with the device trace via their
    ``TraceAnnotation`` names (:func:`~byzpy_tpu.observability.tracing.
    device_span`)."""
    jax.profiler.start_trace(log_dir, create_perfetto_link=False)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def timed_call_s(fn, *args: Any, warmup: int = 2, repeat: int = 20) -> float:
    """Mean wall seconds per call of ``fn(*args)`` over ``repeat``
    back-to-back dispatches, the clock stopped after
    ``jax.block_until_ready`` on the last output (dispatch is
    asynchronous and a device executes its queue in order, so the last
    output being ready means all of them ran)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat


class StepTimer:
    """Accurate step timing: blocks on the step's outputs before reading
    the clock, so XLA async dispatch can't make steps look instant.
    Every ``stop`` also lands in the registry's ``byzpy_step_seconds``
    histogram."""

    def __init__(self) -> None:
        self.times_s: List[float] = []
        self._t0: Optional[float] = None
        self._hist = _metrics.registry().histogram(
            "byzpy_step_seconds", help="StepTimer step wall seconds"
        )

    def start(self) -> None:
        """Mark the step's start."""
        self._t0 = time.perf_counter()

    def stop(self, *outputs: Any) -> float:
        """Block on ``outputs`` (if any), record and return the elapsed
        seconds."""
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() without start()")
        if outputs:
            jax.block_until_ready(outputs)
        dt = time.perf_counter() - self._t0
        self.times_s.append(dt)
        self._hist.observe(dt)
        self._t0 = None
        return dt

    @contextlib.contextmanager
    def measure(self, *outputs_holder: list) -> Iterator[None]:
        """``with t.measure(holder):`` — start on entry, stop on exit
        blocking on whatever the body placed in ``holder``."""
        self.start()
        try:
            yield
        finally:
            self.stop(*outputs_holder)

    @property
    def mean_s(self) -> float:
        """Mean recorded step seconds (0.0 when empty)."""
        return sum(self.times_s) / len(self.times_s) if self.times_s else 0.0

    @property
    def median_s(self) -> float:
        """Median recorded step seconds (0.0 when empty)."""
        if not self.times_s:
            return 0.0
        s = sorted(self.times_s)
        return s[len(s) // 2]


__all__ = ["MetricsLogger", "StepTimer", "timed_call_s", "trace"]
