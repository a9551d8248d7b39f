"""Minimum Diameter Averaging: exact search over ``(n - f)``-subsets
(behavioral parity: ``byzpy/aggregators/geometric_wise/minimum_diameter_average.py:80-444``).

The search is exact branch-and-bound on the host — the reference prunes a
DFS with a per-seed incumbent (``_search_seed``, minimum_diameter_average.py:359-380);
here the incumbent is **global** and pre-seeded with a greedy-peeling upper
bound, which prunes strictly harder. The ``(n, n)`` distance matrix comes
off the device once (``ops.robust.pairwise_sq_dists``); the subset search
itself is tiny host data, combinatorial by nature (SURVEY §7 hard part b).

A batched device scorer (``subset_diameters`` over combo index arrays) is
kept for the pool fan-out path and for validating the B&B result.
"""

from __future__ import annotations

import math
from itertools import combinations, islice
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from ...engine.graph.chunking import pool_size_from_context, select_adaptive_chunk_size
from ...engine.graph.operator import OpContext
from ...engine.graph.subtask import SubTask
from ...ops import robust
from ...utils.combinatorics import iter_combinations
from ...utils.trees import stack_gradients
from ..base import Aggregator

_DEVICE_BATCH = 4096

# below this many elements the host matmul beats a device round-trip (the
# search itself is host-side, so a device d2 must come back anyway)
_HOST_D2_ELEMENTS = 1 << 22


def _dists_for_search(x: jnp.ndarray) -> np.ndarray:
    if x.size <= _HOST_D2_ELEMENTS:
        arr = np.asarray(x, dtype=np.float64 if x.dtype == jnp.float64 else np.float32)
        norms = np.sum(arr * arr, axis=1, keepdims=True)
        d2 = norms + norms.T - 2.0 * (arr @ arr.T)
        return np.maximum(d2, 0.0)
    return np.asarray(robust.pairwise_sq_dists(x))


# ---------------------------------------------------------------------------
# Exact search: greedy bound + branch-and-bound DFS
# ---------------------------------------------------------------------------


def greedy_peel_bound(d2: np.ndarray, m: int) -> Tuple[float, List[int]]:
    """Upper bound: repeatedly drop the point with the largest max-distance
    to the survivors until ``m`` remain. O(n^2) and usually near-optimal —
    a strong incumbent for the B&B."""
    alive = list(range(d2.shape[0]))
    while len(alive) > m:
        sub = d2[np.ix_(alive, alive)]
        worst = int(np.argmax(sub.max(axis=1)))
        alive.pop(worst)
    diam = float(d2[np.ix_(alive, alive)].max()) if len(alive) > 1 else 0.0
    return diam, alive


def branch_and_bound_min_diameter(
    d2: np.ndarray,
    m: int,
    *,
    prefixes: Optional[Iterable[Sequence[int]]] = None,
    initial_bound: float = math.inf,
    initial_combo: Optional[Sequence[int]] = None,
) -> Tuple[float, List[int]]:
    """Exact minimum-diameter ``m``-subset by DFS over increasing indices.

    A branch extends the current set with index ``idx``; its diameter so
    far is the running max distance, and any branch whose max already
    reaches the incumbent is cut. ``initial_bound`` prunes from the very
    first branch even without ``initial_combo`` — a fully pruned search
    returns ``(initial_bound, [])``, meaning nothing beat the bound. With
    ``prefixes``, only subsets starting with one of the given index
    prefixes are explored (the pool-partitioned search; the incumbent
    still tightens across prefixes within one call).
    """
    n = d2.shape[0]
    best = [float(initial_bound), list(initial_combo or [])]

    def dfs(indices: List[int], current: float, start: int, remain: int) -> None:
        if remain == 0:
            if current < best[0]:
                best[0], best[1] = current, list(indices)
            return
        for idx in range(start, n - remain + 1):
            new_max = current
            if indices:
                row = d2[idx, indices]
                new_max = max(current, float(row.max()))
            if new_max >= best[0]:
                continue  # bound: cannot beat the incumbent
            indices.append(idx)
            dfs(indices, new_max, idx + 1, remain - 1)
            indices.pop()

    if prefixes is None:
        prefixes = [()]
    for prefix in prefixes:
        prefix = list(prefix)
        if len(prefix) > m:
            continue
        current = (
            float(d2[np.ix_(prefix, prefix)].max()) if len(prefix) > 1 else 0.0
        )
        if current >= best[0]:
            continue
        start = (prefix[-1] + 1) if prefix else 0
        dfs(prefix, current, start, m - len(prefix))
    return best[0], best[1]


def _exact_min_diameter(d2: np.ndarray, m: int) -> List[int]:
    bound, combo = greedy_peel_bound(d2, m)
    # strict-improvement DFS keeps the greedy combo unless something beats it
    _, best = branch_and_bound_min_diameter(
        d2, m, initial_bound=bound, initial_combo=combo
    )
    return best


# ---------------------------------------------------------------------------
# Device-batched scorer (pool path + validation)
# ---------------------------------------------------------------------------


def _combo_batches(
    n: int, m: int, batch: int, *, start: int = 0, count: int | None = None
) -> Iterable[np.ndarray]:
    """Fixed-size ``(batch, m)`` blocks; the tail is padded by repeating its
    first combo so every device call shares one compiled shape (padding
    can't win the min — it duplicates a real candidate)."""
    it = iter_combinations(n, m, start)
    if count is not None:
        it = islice(it, count)
    while True:
        block = list(islice(it, batch))
        if not block:
            return
        arr = np.asarray(block, dtype=np.int32)
        if arr.shape[0] < batch:
            pad = np.repeat(arr[:1], batch - arr.shape[0], axis=0)
            arr = np.concatenate([arr, pad], axis=0)
        yield arr


def _device_best(
    matrix: jnp.ndarray,
    batches: Iterable[np.ndarray],
    score_fn=robust.subset_diameters,
) -> tuple[float, np.ndarray]:
    """Scan batches keeping the per-batch best ON DEVICE; a single host
    sync at the end picks the global winner (each intermediate force would
    cost a device round-trip per batch). ``score_fn(matrix, combos) -> (c,) scores``; minimum wins."""
    best_scores = []
    best_combos = []
    for combos in batches:
        combos = jnp.asarray(combos)
        scores = score_fn(matrix, combos)
        i = jnp.argmin(scores)
        best_scores.append(scores[i])
        best_combos.append(combos[i])
    stacked = jnp.stack(best_scores)
    k = int(jnp.argmin(stacked))  # the one host sync
    return float(stacked[k]), np.asarray(best_combos[k])


def _score_combo_range(
    host_d2: np.ndarray, n: int, m: int, start: int, count: int
) -> tuple[float, np.ndarray]:
    """Best (min-diameter) combo among combinations [start, start+count)
    — brute-force device scoring for explicit-range pool subtasks."""
    d2 = jnp.asarray(host_d2)
    batch = min(_DEVICE_BATCH, count)
    return _device_best(
        d2, _combo_batches(n, m, batch, start=start, count=count)
    )


def _search_seed_group(
    host_d2: np.ndarray, seeds: Tuple[Tuple[int, ...], ...], m: int, bound: float
) -> tuple[float, np.ndarray]:
    """Pool subtask: B&B restricted to the given seed prefixes (ref:
    ``_mda_best_subset_seeded``, minimum_diameter_average.py:297-325)."""
    score, combo = branch_and_bound_min_diameter(
        np.asarray(host_d2), m, prefixes=seeds, initial_bound=bound
    )
    return score, np.asarray(combo if combo else [], dtype=np.int32)


class MinimumDiameterAveraging(Aggregator):
    """Average of the (n - f)-subset with the smallest pairwise diameter, found by branch-and-bound over the device-computed distance matrix."""
    name = "minimum-diameter-averaging"
    supports_subtasks = True

    def __init__(
        self,
        f: int,
        *,
        chunk_size: int = 20000,
        seed_prefix: int = 2,
        seeds_per_task: int = 4,
    ) -> None:
        if f < 0:
            raise ValueError("f must be >= 0")
        if chunk_size <= 0:
            raise ValueError("chunk_size must be > 0")
        self.f = int(f)
        self.chunk_size = int(chunk_size)
        self.seed_prefix = int(seed_prefix)
        self.seeds_per_task = int(seeds_per_task)

    def validate_n(self, n: int) -> None:
        if self.f >= n:
            raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={self.f})")

    def _aggregate_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        n = x.shape[0]
        m = n - self.f
        d2 = _dists_for_search(x)
        combo = _exact_min_diameter(d2, m)
        return robust.subset_mean(x, jnp.asarray(combo, dtype=jnp.int32))

    # -- pool path ----------------------------------------------------------

    def create_subtasks(self, inputs, *, context: OpContext):
        gradients = inputs.get(self.input_key)
        matrix, _ = stack_gradients(gradients)
        self.validate_n(matrix.shape[0])
        n = matrix.shape[0]
        m = n - self.f
        host_d2 = _dists_for_search(matrix)

        if 0 < self.seed_prefix < m:
            # partition the space by index prefixes; every task gets the
            # greedy incumbent so pruning starts tight everywhere. Tasks
            # where nothing beats it return an empty combo; if ALL do, the
            # greedy subset itself was optimal (reduce falls back to it).
            bound, _ = greedy_peel_bound(host_d2, m)
            depth = self.seed_prefix
            max_last = n - (m - depth) - 1

            def gen_seeded():
                group: List[Tuple[int, ...]] = []
                for seed in combinations(range(n), depth):
                    if seed[-1] > max_last:
                        continue
                    group.append(seed)
                    if len(group) >= self.seeds_per_task:
                        yield SubTask(
                            fn=_search_seed_group,
                            args=(host_d2, tuple(group), m, bound),
                            name=f"mda-seeds-{group[0]}",
                        )
                        group = []
                if group:
                    yield SubTask(
                        fn=_search_seed_group,
                        args=(host_d2, tuple(group), m, bound),
                        name=f"mda-seeds-{group[0]}",
                    )

            return gen_seeded()

        total = math.comb(n, m)
        chunk = select_adaptive_chunk_size(
            total, self.chunk_size, pool_size=pool_size_from_context(context)
        )

        def gen():
            for start in range(0, total, chunk):
                count = min(chunk, total - start)
                yield SubTask(
                    fn=_score_combo_range,
                    args=(host_d2, n, m, start, count),
                    name=f"mda-combos[{start}:{start + count}]",
                )

        return gen()

    def reduce_subtasks(self, partials, inputs, *, context: OpContext):
        matrix, unravel = stack_gradients(inputs.get(self.input_key))
        viable = [p for p in partials if len(np.atleast_1d(p[1]))]
        if not viable:
            # every seeded task was fully pruned by the shared bound: the
            # greedy incumbent is optimal (same d2 source as create_subtasks
            # so the recomputed combo matches the bound's derivation)
            d2 = _dists_for_search(matrix)
            _, combo = greedy_peel_bound(d2, matrix.shape[0] - self.f)
            return unravel(robust.subset_mean(matrix, jnp.asarray(combo, dtype=jnp.int32)))
        best_score, best_combo = min(viable, key=lambda p: p[0])
        return unravel(robust.subset_mean(matrix, jnp.asarray(best_combo)))


__all__ = [
    "MinimumDiameterAveraging",
    "branch_and_bound_min_diameter",
    "greedy_peel_bound",
]
