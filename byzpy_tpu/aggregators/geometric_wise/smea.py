"""SMEA: Smallest Maximum Eigenvalue Averaging
(behavioral parity: ``byzpy/aggregators/geometric_wise/smea.py:110-228``).

Two scoring paths, same score:

* **Device-pure** (default for combo spaces up to ``_DEVICE_COMBO_CAP``):
  Gram on the MXU, every subset's top eigenvalue via batched cyclic
  Jacobi (``ops.robust.subset_max_eigvals_jacobi``), argmin + winner mean
  on device. ONE dispatch, no host synchronization anywhere — a
  mid-call host sync serializes every round on a device round-trip.
* **Host LAPACK** (pool subtasks / huge combo spaces): stacked
  ``eigvalsh`` over chunked combo ranges, fanned out over the actor pool
  (``create_subtasks``), exactly like MDA.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from ...engine.graph.chunking import pool_size_from_context, select_adaptive_chunk_size
from ...engine.graph.operator import OpContext
from ...engine.graph.subtask import SubTask
from ...ops import robust
from ...utils.trees import stack_gradients
from ..base import Aggregator

_DEVICE_BATCH = 2048
# Device-pure scoring materializes the (n_combos, m, m) centered blocks in
# HBM: 32768 x 32 x 32 f32 = 134 MB, a comfortable cap.
_DEVICE_COMBO_CAP = 32768
# The fixed-8-sweep Jacobi scorer is precision-validated for m <= 32
# (tests pin m=11 against LAPACK; convergence degrades slowly with m) --
# larger subsets take the exact host-LAPACK path.
_DEVICE_JACOBI_MAX_M = 32


@functools.lru_cache(maxsize=32)
def _device_combos(n: int, m: int) -> jnp.ndarray:
    from .minimum_diameter_average import _combo_batches

    parts = [np.asarray(c) for c in _combo_batches(n, m, _DEVICE_COMBO_CAP)]
    # _combo_batches pads its tail block by repeating the first combo;
    # slice back to the exact count (a duplicate can never win argmin's
    # first-occurrence tie-break, but don't score it twice either).
    return jnp.asarray(np.concatenate(parts, axis=0)[: math.comb(n, m)].astype(np.int32))


@jax.jit
def _smea_select_mean(x: jnp.ndarray, combos: jnp.ndarray) -> jnp.ndarray:
    """Gram -> Jacobi subset scores -> argmin -> winner mean, all on
    device (ties: first combo in enumeration order, like the host loop)."""
    gram = robust.gram_matrix(x)
    scores = robust.subset_max_eigvals_jacobi(gram, combos)
    best = jnp.argmin(scores)
    return jnp.mean(x[combos[best]], axis=0)


def _score_combo_range_smea(
    host_gram: np.ndarray, n: int, m: int, start: int, count: int
) -> tuple[float, np.ndarray]:
    """Best (min top-eigenvalue) combo in [start, start+count).

    Scores on the HOST: the expensive O(n^2 d) Gram already ran on the MXU;
    what remains is thousands of m x m symmetric eigenproblems, and TPUs
    have no native eigensolver (XLA lowers eigh to a serialized QR
    iteration — measured 380 ms for C(16,11) subsets where stacked LAPACK
    eigvalsh needs ~15 ms). Same split as MDA: enumeration + small-matrix
    work on host, bulk linear algebra on device."""
    from .minimum_diameter_average import _combo_batches

    h = np.eye(m) - np.full((m, m), 1.0 / m)
    batch = min(_DEVICE_BATCH, count)
    # A node whose gradient contains NaN/inf poisons its Gram row; LAPACK
    # eigvalsh raises on non-finite input, so subsets containing such a
    # node are scored +inf without ever entering the eigensolver (an
    # adversary must not be able to crash — or win — the selection).
    bad_row = ~np.isfinite(host_gram).all(axis=1)
    best_score, best_combo = np.inf, None
    for combos in _combo_batches(n, m, batch, start=start, count=count):
        sub = host_gram[combos[:, :, None], combos[:, None, :]]  # (c, m, m)
        centered = h @ sub @ h
        combo_bad = bad_row[combos].any(axis=1)
        if combo_bad.any():
            centered[combo_bad] = np.eye(m)
        top = np.linalg.eigvalsh(centered)[:, -1]
        scores = np.where(combo_bad, np.inf, np.maximum(top, 0.0) / m)
        i = int(np.argmin(scores))
        if best_combo is None or scores[i] < best_score:
            best_score, best_combo = float(scores[i]), combos[i]
    return best_score, np.asarray(best_combo)


class SMEA(Aggregator):
    """Smallest-Maximum-Eigenvalue Averaging: average the (n - f)-subset whose centered Gram has the smallest top eigenvalue (batched-Jacobi scoring on device)."""
    name = "smea"
    supports_subtasks = True

    def __init__(self, f: int, *, chunk_size: int = 4096) -> None:
        if f < 0:
            raise ValueError("f must be >= 0")
        if chunk_size <= 0:
            raise ValueError("chunk_size must be > 0")
        self.f = int(f)
        self.chunk_size = int(chunk_size)

    def validate_n(self, n: int) -> None:
        if 2 * self.f >= n:
            raise ValueError(f"2f must be < n (got n={n}, f={self.f})")

    def _aggregate_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        n = x.shape[0]
        m = n - self.f
        if math.comb(n, m) <= _DEVICE_COMBO_CAP and m <= _DEVICE_JACOBI_MAX_M:
            return _smea_select_mean(x, _device_combos(n, m))
        gram = robust.gram_matrix(x)
        best_score, best_combo = _score_combo_range_smea(
            np.asarray(gram), n, m, 0, math.comb(n, m)
        )
        return robust.subset_mean(x, jnp.asarray(best_combo))

    def create_subtasks(self, inputs, *, context: OpContext):
        gradients = inputs.get(self.input_key)
        matrix, _ = stack_gradients(gradients)
        self.validate_n(matrix.shape[0])
        n = matrix.shape[0]
        m = n - self.f
        total = math.comb(n, m)
        host_gram = np.asarray(robust.gram_matrix(matrix))
        chunk = select_adaptive_chunk_size(
            total, self.chunk_size, pool_size=pool_size_from_context(context)
        )

        def gen():
            for start in range(0, total, chunk):
                count = min(chunk, total - start)
                yield SubTask(
                    fn=_score_combo_range_smea,
                    args=(host_gram, n, m, start, count),
                    name=f"smea-combos[{start}:{start + count}]",
                )

        return gen()

    def reduce_subtasks(self, partials, inputs, *, context: OpContext):
        best_score, best_combo = min(partials, key=lambda p: p[0])
        matrix, unravel = stack_gradients(inputs.get(self.input_key))
        return unravel(robust.subset_mean(matrix, jnp.asarray(best_combo)))


__all__ = ["SMEA"]
