"""Deprecated shim — the metrics/profiling helpers moved to
:mod:`byzpy_tpu.observability.compat`.

The seed-era :class:`MetricsLogger`/:class:`StepTimer` now live in the
telemetry subsystem and publish into its process-wide metrics registry
(``byzpy_logged_<key>`` gauges, the ``byzpy_step_seconds`` histogram)
while keeping their exact public behavior; :func:`trace` and
:func:`timed_call_s` moved with them. This
module re-exports everything so existing imports keep working, and
will be removed in a future major version — import from
``byzpy_tpu.observability`` instead.
"""

from __future__ import annotations

import warnings

from ..observability.compat import (  # noqa: F401 — re-exports
    MetricsLogger,
    StepTimer,
    timed_call_s,
    trace,
)

warnings.warn(
    "byzpy_tpu.utils.metrics is deprecated; import MetricsLogger/StepTimer/"
    "trace from byzpy_tpu.observability (registry-backed ports)",
    DeprecationWarning,
    stacklevel=2,
)

__all__ = ["MetricsLogger", "trace", "StepTimer", "timed_call_s"]
