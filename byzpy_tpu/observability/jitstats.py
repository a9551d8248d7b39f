"""Compile-cache observability: count XLA compiles per dispatch site.

An unexpected recompile is the #1 silent latency cliff the serving
tier's :class:`~byzpy_tpu.serving.buckets.BucketLadder` exists to
prevent — a cohort shape outside the ladder (or a dtype drift through
an aggregator's jit cache) costs hundreds of milliseconds on a CPU
mesh (seconds for a large program), with nothing detecting the
regression until p99 moves. The fix is observational, not structural:
jitted callables stay unwrapped (tests introspect ``_cache_size()`` /
``.lower()``, per the PR-8 contract), and the round loops that own them
call :func:`note_cache_size` with the cache size after each dispatch.
Growth since the last observation increments
``byzpy_jit_compiles_total{site}`` — a dashboard alerting on its rate
after warmup catches the cliff the moment it opens. The serving
frontend additionally compares the masked-aggregate cache against its
bucket ladder and warns (once per excess size, plus
``byzpy_serving_recompile_warnings_total{tenant}``) when compiles
exceed the ladder's shape count.

Published unconditionally (cold path: one ``_cache_size()`` read and a
dict lookup per round, far off any per-submission path).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from . import metrics as _metrics

_LOCK = threading.Lock()
_LAST: Dict[str, int] = {}


def note_cache_size(site: str, size: Optional[int]) -> int:
    """Record a dispatch site's current jit-cache size; any growth
    since the last observation is counted as fresh compiles on
    ``byzpy_jit_compiles_total{site}``. Returns the number of NEW
    compiles counted (0 when unchanged, shrunk, or ``size`` is None —
    a cleared cache must not produce negative counts, and the next
    growth past the high-water mark still registers)."""
    if size is None:
        return 0
    size = int(size)
    with _LOCK:
        prev = _LAST.get(site, 0)
        if size <= prev:
            return 0
        _LAST[site] = size
    delta = size - prev
    _metrics.registry().counter(
        "byzpy_jit_compiles_total",
        help="XLA compiles observed per dispatch site (jit-cache growth)",
        labels={"site": site},
    ).inc(delta)
    return delta


def compiles_seen(site: str) -> int:
    """The high-water jit-cache size observed at ``site`` (0 if never
    noted) — test/introspection helper."""
    with _LOCK:
        return _LAST.get(site, 0)


def reset() -> None:
    """Forget all per-site high-water marks (tests only; the registry
    counters themselves are reset via ``metrics.registry().reset()``)."""
    with _LOCK:
        _LAST.clear()


__all__ = ["compiles_seen", "note_cache_size", "reset"]
