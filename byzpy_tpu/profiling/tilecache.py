"""Shape-keyed on-disk cache of autotuned Pallas block shapes.

The autotuner (:mod:`byzpy_tpu.profiling.autotune`) sweeps feature-tile
candidates for the hot Pallas kernels and persists the winners here; the
dispatch heuristics in ``byzpy_tpu.ops.pallas_kernels`` (``_auto_tile`` /
``_auto_selection_tile`` / ``_auto_sort_tile``) consult this cache before
falling back to their analytic defaults. Resolution order everywhere is

1. ``BYZPY_TPU_TILE_<FAMILY>`` environment override (wins uncondition-
   ally — tuning harnesses flip it per run),
2. this cache, keyed ``(family, platform, n, d)``,
3. the in-code heuristic.

The cache file is plain JSON at the path ``BYZPY_TPU_TUNE_CACHE`` names.
There is no default location: with the variable unset no file is read,
so state outside the checkout never changes which kernel is compiled and
every machine resolves the same tiles. Robustness contract, pinned by
``tests/test_autotune_cache.py``: a missing, corrupt, or stale file —
and any individual entry that fails validation — degrades to
the heuristic; the cache can never crash a dispatch. This module is
stdlib-only so the kernels' lazy import of it costs nothing.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Any, Dict, Optional

_ENV_CACHE_PATH = "BYZPY_TPU_TUNE_CACHE"

# (path, mtime) -> parsed dict; guarded by _LOCK. Reload on mtime change
# so a sweep in the same process is visible to later dispatches.
_MEMO: Dict[str, Any] = {"path": None, "mtime": None, "data": {}}
_LOCK = threading.Lock()

#: Lane width every valid tile must be a multiple of (TPU vector lanes).
LANE = 128
#: Hard bounds on a cached tile: one lane up to 64k features.
MAX_TILE = 1 << 16


def cache_path() -> Optional[str]:
    """The cache file ``BYZPY_TPU_TUNE_CACHE`` names, or ``None`` (no
    cache: lookups miss, stores need an explicit ``path``)."""
    return os.environ.get(_ENV_CACHE_PATH) or None


def valid_tile(tile: Any) -> bool:
    """True iff ``tile`` is a usable Pallas feature-tile width: a positive
    lane-aligned int no larger than :data:`MAX_TILE`. Anything else (a
    stale or hand-mangled cache entry) is ignored by :func:`lookup`."""
    return (
        isinstance(tile, int)
        and not isinstance(tile, bool)
        and 0 < tile <= MAX_TILE
        and tile % LANE == 0
    )


def cache_key(family: str, *, platform: str, n: int, d: int) -> str:
    """Canonical cache key for one (kernel family, platform, shape).
    ``n`` is the SUBLANE-PADDED row count — the value the kernels'
    dispatch heuristics see (``autotune.sweep`` pads before storing)."""
    return f"{family}:{platform}:{int(n)}x{int(d)}"


def _load(path: str) -> Dict[str, Any]:
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    with _LOCK:
        if _MEMO["path"] == path and _MEMO["mtime"] == mtime:
            return _MEMO["data"]
    try:
        with open(path) as fh:
            data = json.load(fh)
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        # corrupt/unreadable cache: degrade to the heuristic, never crash
        data = {}
    with _LOCK:
        _MEMO.update(path=path, mtime=mtime, data=data)
    return data


def load_cache(path: Optional[str] = None) -> Dict[str, Any]:
    """Parsed cache contents (``{}`` for no, a missing or a corrupt file)."""
    path = path or cache_path()
    return dict(_load(path)) if path else {}


def lookup(
    family: str, *, platform: str, n: int, d: int, path: Optional[str] = None
) -> Optional[int]:
    """Tuned tile for ``(family, platform, n, d)``, or ``None`` when no
    valid entry exists (missing key, corrupt file, failed validation)."""
    path = path or cache_path()
    if path is None:
        return None
    entry = _load(path).get(cache_key(family, platform=platform, n=n, d=d))
    if isinstance(entry, dict):
        tile = entry.get("tile")
        return tile if valid_tile(tile) else None
    return None


def store(
    family: str,
    *,
    platform: str,
    n: int,
    d: int,
    tile: int,
    path: Optional[str] = None,
    **meta: Any,
) -> str:
    """Persist a tuned tile (read-modify-write with an atomic replace).
    Extra ``meta`` keys (measured ms, candidate list, timestamp) ride
    along for provenance. Returns the cache file path written."""
    if not valid_tile(tile):
        raise ValueError(f"refusing to cache invalid tile {tile!r}")
    path = path or cache_path()
    if path is None:
        raise ValueError(
            f"no tile cache file: pass path= or set {_ENV_CACHE_PATH}"
        )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with _LOCK:
        try:
            with open(path) as fh:
                data = json.load(fh)
            if not isinstance(data, dict):
                data = {}
        except (OSError, ValueError):
            data = {}
        data[cache_key(family, platform=platform, n=n, d=d)] = {
            "tile": int(tile), **meta
        }
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path) or ".", suffix=".tmp"
        )
        with os.fdopen(fd, "w") as fh:
            json.dump(data, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
        _MEMO.update(path=None, mtime=None, data={})  # force reload
    return path


__all__ = [
    "LANE",
    "MAX_TILE",
    "cache_key",
    "cache_path",
    "load_cache",
    "lookup",
    "store",
    "valid_tile",
]
