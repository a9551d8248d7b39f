"""Block-shape autotuner for the hot Pallas kernels.

Sweeps lane-aligned feature-tile candidates for the kernel families
the roofline profiler showed dominating the round loop —

* ``sort`` — ``pallas_kernels.sort_columns``
* ``gram`` — ``pallas_kernels.gram_pallas``
* ``selection`` — ``pallas_kernels.selection_mean_stream_pallas``
* ``sorted_reduce`` — ``pallas_kernels.sorted_reduce_stream_pallas``
* ``meamed`` — ``pallas_kernels.meamed_stream_pallas``
* ``quant`` — ``parallel.quantization.quantize_blockwise`` (the
  compressed-fabric encode; candidates stay multiples of the
  quantization block so scales never straddle a grid step)
* ``quant_fp8`` / ``quant_s4`` — the sub-int8 encodes
  (``parallel.quantization.encode_blockwise``; same block-multiple
  rule, separate cache keys because the f8 cast and nibble packing
  change the kernels' arithmetic intensity)

— and persists each winner in the shape-keyed on-disk cache
(:mod:`.tilecache`) that ``_auto_tile`` / ``_auto_selection_tile`` /
``_auto_sort_tile`` consult at dispatch time. Tiles are resolved in the
kernels' *Python wrappers*, before any ``jax.jit`` closure captures them,
so re-running a sweep (or flipping ``BYZPY_TPU_TILE_<FAMILY>``) changes
the very next dispatch — no stale-trace pitfall.

A sweep is skipped when the cache already holds a valid entry for the
(family, platform, shape) key (pass ``force=True`` to re-measure). Off
TPU the kernels run in interpret mode: the sweep machinery still works —
that is what the cache/override tests exercise — but interpret-mode
timings say nothing about Mosaic; a tune that changes dispatch is a
chip run (ROADMAP D4/D5).
"""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import tilecache

#: Candidate tile widths swept per family (lane-aligned, largest first;
#: per-candidate VMEM feasibility is checked by the kernel itself — a
#: candidate that fails to compile is skipped, not fatal).
CANDIDATES: Dict[str, Tuple[int, ...]] = {
    "sort": (1024, 2048, 4096, 8192),
    "gram": (512, 1024, 2048, 4096, 8192),
    "selection": (2048, 4096, 8192, 16384),
    "sorted_reduce": (1024, 2048, 4096, 8192, 16384),
    "meamed": (256, 512, 1024, 2048),
    "quant": (1024, 2048, 4096, 8192, 16384),
    "quant_fp8": (1024, 2048, 4096, 8192, 16384),
    "quant_s4": (1024, 2048, 4096, 8192, 16384),
    "ragged": (512, 1024, 2048, 4096, 8192),
}


def _kernel_runner(family: str) -> Callable:
    """A ``runner(x, tile)`` closure for one kernel family (imports are
    deferred so this module stays import-light)."""
    from ..ops import pallas_kernels as pk

    if family == "sort":
        return lambda x, tile: pk.sort_columns(x, tile=tile)
    if family == "gram":
        return lambda x, tile: pk.gram_pallas(x, tile=tile)
    if family == "selection":
        return lambda x, tile: pk.selection_mean_stream_pallas(
            x[None], f=max(0, x.shape[0] // 8), q=max(1, x.shape[0] // 4),
            mode="krum", tile=tile,
        )
    if family == "sorted_reduce":
        return lambda x, tile: pk.sorted_reduce_stream_pallas(
            x[None], mode="median", tile=tile
        )
    if family == "meamed":
        return lambda x, tile: pk.meamed_stream_pallas(
            x[None], f=max(1, x.shape[0] // 8), tile=tile
        )
    if family == "quant":
        from ..parallel.quantization import quantize_blockwise

        return lambda x, tile: quantize_blockwise(
            x, tile=tile, use_pallas=True
        ).values
    if family in ("quant_fp8", "quant_s4"):
        from ..parallel.quantization import encode_blockwise

        mode = "fp8" if family == "quant_fp8" else "s4"
        return lambda x, tile: encode_blockwise(
            x, mode, tile=tile, use_pallas=True
        ).values
    if family == "ragged":
        import jax.numpy as jnp

        # a representative serving batch: 4 cohorts splitting the rows,
        # one 0/1 weight row per cohort — the (C, R) weight-matrix form
        # of the segment-sum contraction every ragged aggregate ends in
        def _ragged(x, tile):
            n = x.shape[0]
            seg = (jnp.arange(n, dtype=jnp.int32) * 4) // max(n, 1)
            weights = (
                seg[None, :] == jnp.arange(4, dtype=jnp.int32)[:, None]
            ).astype(x.dtype)
            return pk.ragged_segment_sum_pallas(x, weights, tile=tile)

        return _ragged
    raise ValueError(f"unknown kernel family {family!r}")


def sweep(
    family: str,
    *,
    n: int,
    d: int,
    candidates: Optional[Sequence[int]] = None,
    repeat: int = 5,
    force: bool = False,
    cache_path: Optional[str] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    """Time every candidate tile for one (family, shape) and persist the
    winner. Returns a summary dict (``cached=True`` rows skipped the
    measurement because a valid cache entry already existed)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas_kernels import _SUBLANES, _round_up
    from ..observability.compat import timed_call_s

    platform = jax.default_backend()
    # cache keys carry the SUBLANE-PADDED row count — that is what the
    # kernels' dispatch-side _tuned_tile lookup uses (they only ever see
    # n_pad), so an unpadded key would be dead data
    n_key = max(_SUBLANES, _round_up(n, _SUBLANES))
    if not force:
        hit = tilecache.lookup(
            family, platform=platform, n=n_key, d=d, path=cache_path
        )
        if hit is not None:
            return {
                "family": family, "platform": platform, "n": n_key, "d": d,
                "tile": hit, "cached": True,
            }

    runner = _kernel_runner(family)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), jnp.float32)
    results: List[Tuple[int, float]] = []
    for tile in candidates or CANDIDATES[family]:
        if not tilecache.valid_tile(tile):
            continue
        try:
            t = timed_call_s(
                lambda a, _t=tile: runner(a, _t), x, warmup=1, repeat=repeat
            )
        except Exception as exc:  # noqa: BLE001 — infeasible tile: skip
            if verbose:
                print(f"  {family} tile={tile}: skipped "
                      f"({type(exc).__name__})", file=sys.stderr)
            continue
        results.append((tile, t))
        if verbose:
            print(f"  {family} {n}x{d} tile={tile}: {t * 1e3:.3f} ms",
                  file=sys.stderr)
    if not results:
        return {
            "family": family, "platform": platform, "n": n_key, "d": d,
            "tile": None, "cached": False, "error": "no candidate ran",
        }
    tile, best_s = min(results, key=lambda r: r[1])
    tilecache.store(
        family, platform=platform, n=n_key, d=d, tile=tile, path=cache_path,
        ms=round(best_s * 1e3, 4),
        candidates={str(t): round(s * 1e3, 4) for t, s in results},
        time_utc=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    )
    return {
        "family": family, "platform": platform, "n": n_key, "d": d,
        "tile": tile, "ms": round(best_s * 1e3, 4), "cached": False,
    }


#: Default shapes swept by :func:`autotune_all` — the BASELINE.md grid
#: row (64 x 65,536) and the 1M-dim north-star shape.
DEFAULT_SHAPES: Tuple[Tuple[int, int], ...] = ((64, 65_536), (64, 1 << 20))


def autotune_all(
    shapes: Sequence[Tuple[int, int]] = DEFAULT_SHAPES,
    *,
    families: Sequence[str] = tuple(CANDIDATES),
    repeat: int = 5,
    force: bool = False,
    cache_path: Optional[str] = None,
    verbose: bool = True,
) -> List[Dict[str, Any]]:
    """Sweep every (family, shape) pair; returns the summary rows."""
    out = []
    for n, d in shapes:
        for family in families:
            out.append(
                sweep(
                    family, n=n, d=d, repeat=repeat, force=force,
                    cache_path=cache_path, verbose=verbose,
                )
            )
    return out


__all__ = ["CANDIDATES", "DEFAULT_SHAPES", "autotune_all", "sweep"]
