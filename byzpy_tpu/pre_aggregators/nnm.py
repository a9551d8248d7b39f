"""NNM: Nearest-Neighbor Mixing (Allouah et al. 2023)
(behavioral parity: ``byzpy/pre_aggregators/nnm.py:21-95``).

The k-nearest mask matmul rides the MXU; pairwise distances come from the
same sharded Gram path as Krum.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops import preagg
from .base import PreAggregator


class NearestNeighborMixing(PreAggregator):
    """Replace each row by the mean of its n - f nearest neighbors (fused Pallas kernel at large d)."""
    name = "pre-agg/nnm"

    def __init__(self, f: int) -> None:
        if f < 0:
            raise ValueError("f must be >= 0")
        self.f = int(f)

    def validate_n(self, n: int) -> None:
        if not 0 <= self.f < n:
            raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={self.f})")

    def _transform_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        return preagg.nnm(x, f=self.f)

    def _transform_stream_matrix(self, xs: jnp.ndarray) -> jnp.ndarray:
        from ..ops.pallas_kernels import nnm_stream_pallas, pallas_serves

        if pallas_serves(xs, stream=True):
            return nnm_stream_pallas(xs, f=self.f)
        return super()._transform_stream_matrix(xs)


__all__ = ["NearestNeighborMixing"]
