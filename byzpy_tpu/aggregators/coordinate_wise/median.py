"""Coordinate-wise median aggregator
(behavioral parity: ``byzpy/aggregators/coordinate_wise/median.py:28-178``).

TPU execution: one ``jnp.median`` over the node axis — fully local per chip
when the matrix is feature-sharded, no communication. The pool-chunked path
fans out column blocks instead of the reference's shm chunks.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ...ops import robust
from ..base import Aggregator
from ..chunked import FeatureChunkedAggregator


def _median_chunk(chunk: np.ndarray) -> jnp.ndarray:
    return jnp.median(jnp.asarray(chunk), axis=0)


class CoordinateWiseMedian(FeatureChunkedAggregator, Aggregator):
    """Per-coordinate median over the node axis."""
    name = "coordinate-wise-median"
    _chunk_fn = staticmethod(_median_chunk)

    def __init__(self, *, chunk_size: int = 8192) -> None:
        if chunk_size <= 0:
            raise ValueError("chunk_size must be > 0")
        self.chunk_size = int(chunk_size)

    supports_masked_finalize = True

    def _aggregate_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        return robust.coordinate_median(x)

    def _aggregate_matrix_masked(
        self, x: jnp.ndarray, valid: jnp.ndarray
    ) -> jnp.ndarray:
        return robust.masked_coordinate_median(x, valid)

    def _aggregate_stream_matrix(self, xs: jnp.ndarray) -> jnp.ndarray:
        return robust.coordinate_median_stream(xs)

    def ragged_matrix_fn(self):
        """Ragged program, sort strategy resolved pre-trace (see
        ``CoordinateWiseTrimmedMean.ragged_matrix_fn``): segmented
        program on TPU (finite rows only — the serving ragged door
        routes non-finite cohorts to the exact fallback, and on finite
        data the masked program's NaN rewrite is a no-op, so parity
        stays bit-for-bit), per-cohort masked program on the XLA
        fallback."""
        from ...ops import ragged as ragged_ops
        from ...ops.pallas_kernels import targets_tpu

        if not targets_tpu():
            return super().ragged_matrix_fn()

        def fn(flat, seg, offsets, lengths, *, n_cohorts, segment_sum=None):
            aggs = ragged_ops.ragged_median(
                flat, seg, offsets, lengths, n_cohorts=n_cohorts
            )
            return aggs, None, None

        return fn


__all__ = ["CoordinateWiseMedian"]
