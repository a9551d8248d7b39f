"""Pre-aggregation primitives (clip / bucket / mix) as pure JAX functions.

Operate on the stacked ``(n, d)`` gradient matrix; return a transformed
matrix (possibly with fewer rows). TPU notes: row-norm computations contract
the feature axis, so under feature-axis sharding they are local partial
reductions + an ``(n,)``-sized psum; NNM's neighbor mixing is a mask matmul
that rides the MXU.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .pallas_kernels import pallas_serves
from .robust import gram_matrix

Array = jnp.ndarray


@jax.jit
def clip_rows(x: Array, *, threshold: float) -> Array:
    """Static L2-norm clipping of each row to ``threshold``
    (ref: ``byzpy/pre_aggregators/clipping.py``).
    """
    norms = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    factors = jnp.minimum(1.0, threshold / jnp.maximum(norms, 1e-12))
    return x * factors


@partial(jax.jit, static_argnames=("bucket_size",))
def bucket_means(x: Array, perm: Array, *, bucket_size: int) -> Array:
    """Bucketing (Karimireddy et al.): permute rows, split into buckets of
    ``bucket_size`` (last bucket may be smaller), return per-bucket means
    (ref: ``byzpy/pre_aggregators/bucketing.py:101-120``).

    ``perm`` is an explicit permutation of ``range(n)`` so randomness stays
    in caller-owned ``jax.random`` keys (reproducible under jit). Out-of-range
    indices in a traced ``perm`` follow JAX gather clamping semantics; pass a
    real permutation (e.g. ``jax.random.permutation``).
    """
    n = x.shape[0]
    if perm.shape != (n,):
        raise ValueError(f"perm must have shape ({n},); got {perm.shape}")
    nb = math.ceil(n / bucket_size)
    padded_len = nb * bucket_size
    xp = x[perm]
    # Pad with zero rows + a weight mask so the ragged final bucket averages
    # only its real members — keeps shapes static for XLA.
    pad = padded_len - n
    xp = jnp.pad(xp, ((0, pad), (0, 0)))
    weights = jnp.pad(jnp.ones((n,), x.dtype), (0, pad))
    xb = xp.reshape(nb, bucket_size, -1)
    wb = weights.reshape(nb, bucket_size)
    return jnp.sum(xb * wb[:, :, None], axis=1) / jnp.sum(wb, axis=1, keepdims=True)


def nnm(x: Array, *, f: int) -> Array:
    """Nearest-Neighbor Mixing: replace each row by the mean of its
    ``k = n - f`` nearest neighbors (self included)
    (ref: ``byzpy/pre_aggregators/nnm.py:50-95``).

    Non-finite handling: the mixing matmul runs over taint-zeroed data,
    and any mixed row whose selection includes a tainted neighbor (one
    with a non-finite squared norm) is set to NaN afterwards. A plain
    ``mask @ x`` would poison EVERY row (0-weight times NaN is NaN in a
    contraction), which no gather-based implementation does; the one
    divergence from gather semantics is that a row selecting an all-inf
    neighbor yields NaN here instead of ±inf — both non-finite, both
    ranked last by every downstream NaN-aware aggregator in this package.
    On TPU at large ``d`` this dispatches to the fused two-sweep kernel
    (``pallas_kernels.nnm_pallas``); dispatch resolves here, pre-trace."""
    n = x.shape[0]
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    return _nnm_impl(x, f=f, use_kernel=pallas_serves(x))


@partial(jax.jit, static_argnames=("f", "use_kernel"))
def _nnm_impl(x: Array, *, f: int, use_kernel: bool) -> Array:
    if use_kernel:
        from .pallas_kernels import nnm_pallas

        return nnm_pallas(x, f=f)
    n = x.shape[0]
    k = n - f
    gram = gram_matrix(x)  # f32 accumulation for 16-bit floats, f64 for f64
    norms = jnp.diagonal(gram)
    d2 = jnp.maximum(norms[:, None] + norms[None, :] - 2.0 * gram, 0.0)
    # k-nearest mask per row in the accumulation dtype (matching the fused
    # kernel's f32 Gram selection), then one (n,n)@(n,d) matmul mixes.
    idx = jnp.argsort(d2, axis=1)[:, :k]
    mask = jnp.zeros_like(d2).at[jnp.arange(n)[:, None], idx].set(1.0)
    taint = ~jnp.isfinite(norms)
    x_clean = jnp.where(taint[:, None], jnp.zeros((), x.dtype), x)
    acc = gram.dtype
    # this matmul FORMS THE OUTPUT: at the TPU's default precision XLA
    # rounds x to bf16 on the MXU (3.1e-3 max error at 64x1M, 1.1e-2 at
    # 8x11M on v5e against HIGHEST; the fused kernel already asks for it)
    mixed = jnp.einsum(
        "ij,jd->id", mask, x_clean, preferred_element_type=acc,
        precision=jax.lax.Precision.HIGHEST,
    ) / k
    sel_taint = mask @ jnp.where(taint, 1.0, 0.0).astype(acc) > 0.5
    return jnp.where(
        sel_taint[:, None], jnp.asarray(jnp.nan, acc), mixed
    ).astype(x.dtype)


def arc_cut_off(n: int, f: int) -> int:
    """ARC's 1-based rank of the threshold norm: clip the
    ``floor(2f/n * (n-f))`` largest-norm rows to the ``cut_off``-th
    smallest norm. THE single implementation of the formula — the fused
    pipeline kernel (``pallas_kernels.arc_selection_mean_stream_pallas``)
    must clip at exactly the same rank as the materialized path here."""
    nb_clipped = int(math.floor((2.0 * f / n) * (n - f)))
    nb_clipped = max(0, min(nb_clipped, n - 1))
    return max(1, n - nb_clipped)


@partial(jax.jit, static_argnames=("f",))
def arc_clip(x: Array, *, f: int) -> Array:
    """Adaptive Robust Clipping: clip the ``floor(2f/n * (n-f))`` largest-norm
    rows to the norm of the next-largest remaining row
    (ref: ``byzpy/pre_aggregators/arc.py:36-51``).
    """
    n = x.shape[0]
    if f > n:
        raise ValueError(f"f must be <= n (got f={f}, n={n})")
    cut_off = arc_cut_off(n, f)
    norms = jnp.sqrt(jnp.sum(x * x, axis=1))
    threshold = jnp.sort(norms)[cut_off - 1]
    factors = jnp.minimum(1.0, threshold / jnp.maximum(norms, 1e-12))
    return x * factors[:, None]


__all__ = ["clip_rows", "bucket_means", "nnm", "arc_clip", "arc_cut_off"]
