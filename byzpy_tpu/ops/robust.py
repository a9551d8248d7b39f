"""Byzantine-robust aggregation primitives as pure, jit-compatible functions.

Every function here consumes a stacked gradient matrix ``x`` of shape
``(n, d)`` (n = number of nodes, d = flattened model dimension) and static
Python hyper-parameters, and is safe to wrap in ``jax.jit`` /
``shard_map`` / ``pjit``.  This module is the TPU-native data plane that
replaces the reference's host-side subtask chunking over shared memory
(ref: ``byzpy/aggregators/*``):

* coordinate-wise ops (median / trimmed-mean / MeaMed) are pure sorts along
  the node axis — with ``x`` sharded over the feature axis on a device mesh
  they run fully locally per chip, zero communication;
* geometric ops (Krum / MoNNA / MDA / SMEA / NNM) reduce to a Gram matrix
  ``x @ x.T`` — with feature-axis sharding XLA turns the contraction into a
  local matmul + ``psum`` of an ``(n, n)`` block, so cross-chip traffic is
  O(n^2) scalars instead of O(n*d);
* iterative ops (geometric median, centered clipping, CAF) are
  ``lax.while_loop`` / ``fori_loop`` bodies — the reference's barriered
  subtask machinery (ref: ``byzpy/engine/graph/operator.py:50-60``)
  disappears into the compiled program, no host round-trips per iteration.

Behavioral parity with the reference algorithms is pinned by
``tests/test_ops_robust.py`` against NumPy oracles.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import MEAMED_MAX_DIM, MEAMED_MIN_DIM, pallas_serves

Array = jnp.ndarray


def _feature_matmul_dtype(x: Array):
    # Accumulate Gram/norm contractions in f32 even for bf16 inputs: the MXU
    # natively accumulates bf16 matmuls into f32, and distance gaps between
    # nearly-identical gradients underflow in bf16.
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype


# ---------------------------------------------------------------------------
# Pairwise geometry
# ---------------------------------------------------------------------------


def gram_matrix(x: Array) -> Array:
    """``(n, n)`` Gram matrix ``x @ x.T`` with f32 accumulation for bf16."""
    return jnp.einsum(
        "id,jd->ij", x, x, preferred_element_type=_feature_matmul_dtype(x)
    )


def _has_key_sort(x: Array) -> bool:
    # f32, and bf16 / f16 through their exact f32 round-trip
    return jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize in (2, 4)


def sort_rows(x: Array) -> Array:
    """``jnp.sort(x, axis=0)``, served by a monotone int32-key sort for
    f32 (and, via an exact f32 round-trip, 16-bit float) matrices.

    ``lax.sort`` on int32 keys is 3.8–5x faster than the float
    comparator path on XLA:CPU for the grid-row shapes (measured 174 ms
    vs 662 ms at 64x65,536 — the dominant cost of every coordinate-wise
    fallback), and the key map (canonicalize NaN, bitcast, flip the
    magnitude bits of negatives — ``pallas_kernels._float_sort_keys``)
    reproduces ``jnp.sort``'s value ordering including non-finite
    values (-inf < finite < +inf < NaN). Divergences are bit-level
    only, identical to ``sort_columns``'s documented ones: -0.0 keys
    strictly before +0.0 where the stable ``jnp.sort`` preserves input
    order, and NaN payload/sign bits canonicalize to the quiet +NaN
    (pinned in ``tests/test_fused_parity.py``). Other dtypes fall
    through to ``jnp.sort``."""
    from .pallas_kernels import _float_sort_keys, _keys_to_float

    if x.dtype in (jnp.bfloat16, jnp.float16):
        return sort_rows(x.astype(jnp.float32)).astype(x.dtype)
    if x.dtype == jnp.float32:
        return _keys_to_float(
            lax.sort(_float_sort_keys(x), dimension=0), x.dtype
        )
    return jnp.sort(x, axis=0)


def pairwise_sq_dists(x: Array) -> Array:
    """``(n, n)`` squared Euclidean distances via the Gram trick.

    Ref behavior: ``byzpy/aggregators/geometric_wise/krum.py:31-58``.
    Stays on the XLA einsum: its remaining callers are small-``d`` paths
    (MDA/SMEA subset scoring, the XLA fallbacks) where dispatch latency
    dominates. The large-``d`` selection aggregators no longer come
    through here at all — they use the fused two-sweep kernels whose
    in-VMEM Gram reads ``x`` once (``pallas_kernels
    .selection_mean_stream_pallas``; the einsum streams ``x`` twice, as
    lhs and rhs: 0.91 vs 0.31 ms at 64x1M f32 on v5e).
    """
    gram = gram_matrix(x)
    norms = jnp.diagonal(gram)[:, None]
    d2 = norms + norms.T - 2.0 * gram
    return jnp.maximum(d2, 0.0)


# ---------------------------------------------------------------------------
# Coordinate-wise aggregators
# ---------------------------------------------------------------------------


def _median_from_sorted(s: Array) -> Array:
    """``jnp.median(x, axis=0)`` from the already-sorted matrix ``s``
    (float dtypes): midpoint of the middle rows in the input dtype, NaN
    propagated column-wide (NaNs sort last, so a column contains one iff
    its bottom sorted row is NaN) — the exact semantics
    ``pallas_kernels.median_pallas`` pins against the oracle."""
    n = s.shape[0]
    lo, hi = (n - 1) // 2, n // 2
    if lo == hi:
        med = s[lo]
    else:
        med = (s[lo] + s[hi]) * jnp.asarray(0.5, s.dtype)
    return jnp.where(jnp.isnan(s[n - 1]), jnp.asarray(jnp.nan, s.dtype), med)


def coordinate_median(x: Array) -> Array:
    """Coordinate-wise median (ref: ``aggregators/coordinate_wise/median.py``).
    On TPU with small ``n`` and large ``d`` this runs the fused
    sorted-reduce kernel (one HBM read + a (1, d) write; the sorted
    matrix never returns to HBM — ``pallas_kernels
    .sorted_reduce_stream_pallas``), falling back to the int32-key sort
    (:func:`sort_rows` — 3.8x the float sort's throughput on XLA:CPU)
    for float matrices elsewhere. Dispatch resolves here, before any
    jit traces."""
    if pallas_serves(x):
        from .pallas_kernels import sorted_reduce_stream_pallas

        return sorted_reduce_stream_pallas(x[None], mode="median")[0]
    if x.ndim == 2 and _has_key_sort(x):
        return _median_from_sorted(sort_rows(x))
    return jnp.median(x, axis=0)


def coordinate_median_stream(xs: Array) -> Array:
    """Coordinate-wise median over ``K`` stacked rounds ``(K, n, d)`` in
    one fused launch (see ``aggregate_stream`` for why streaming is the
    training-loop shape); XLA scan fallback elsewhere."""
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import sorted_reduce_stream_pallas

        return sorted_reduce_stream_pallas(xs, mode="median")
    return aggregate_stream(coordinate_median, xs)


def trimmed_mean_stream(xs: Array, *, f: int) -> Array:
    """f-trimmed coordinate mean over stacked rounds in one fused launch."""
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import sorted_reduce_stream_pallas

        return sorted_reduce_stream_pallas(xs, mode="trimmed", f=f)
    return aggregate_stream(partial(trimmed_mean, f=f), xs)


def mean_of_medians_stream(xs: Array, *, f: int) -> Array:
    """MeaMed over stacked rounds in one fused launch."""
    if pallas_serves(xs, stream=True, max_dim=MEAMED_MAX_DIM):
        from .pallas_kernels import meamed_stream_pallas

        return meamed_stream_pallas(xs, f=f)
    return aggregate_stream(partial(mean_of_medians, f=f), xs)


def trimmed_mean(x: Array, *, f: int) -> Array:
    """Coordinate-wise trimmed mean: sort per coordinate, drop the ``f``
    smallest and ``f`` largest values, average the middle ``n - 2f``
    (Yin et al. 2018; ref: ``aggregators/coordinate_wise/trimmed_mean.py``).
    Dispatch (Pallas gate, sort flavor) resolves here, pre-trace; the
    XLA fallback sorts int32 keys (:func:`sort_rows`)."""
    n = x.shape[0]
    if not 0 <= 2 * f < n:
        raise ValueError(f"trim parameter f must satisfy 0 <= 2f < n (got n={n}, f={f})")
    if pallas_serves(x):
        from .pallas_kernels import sorted_reduce_stream_pallas

        return sorted_reduce_stream_pallas(x[None], mode="trimmed", f=f)[0]
    return _trimmed_mean_xla(x, f=f)


def attacked_serves(honest: Array, b: int) -> bool:
    """Whether :func:`trimmed_mean_attacked` / :func:`coordinate_median_attacked`
    serve these ``(h, d)`` honest rows (an array or its type): the one
    gate's answer for the ``(h + b, d)`` matrix a caller would have built
    otherwise."""
    h, d = honest.shape
    return pallas_serves(jax.ShapeDtypeStruct((h + b, d), honest.dtype))


def _sorted_reduce_attacked(honest: Array, *, attack, b: int, **reduction) -> Array:
    if not attacked_serves(honest, b):
        raise ValueError(
            f"no kernel serves the ({honest.shape[0]} + {b}, {honest.shape[1]}) "
            f"{honest.dtype} matrix here: write the attack's rows beside the honest ones "
            "and call the aggregator on the matrix")
    from .pallas_kernels import sorted_reduce_stream_pallas

    return sorted_reduce_stream_pallas(honest[None], attack=attack, b=b, **reduction)[0]


def trimmed_mean_attacked(honest: Array, *, f: int, attack, b: int) -> Array:
    """:func:`trimmed_mean` of the ``(h + b, d)`` matrix whose first rows
    are ``honest`` ``(h, d)`` and whose last ``b`` are the rows ``attack``
    makes of them, without that matrix: the sort kernel forms the attack's
    rows block by block from the honest rows it reads
    (``pallas_kernels.sorted_reduce_stream_pallas``, ``attack=``), so the
    round writes none, allocates none and reads ``h`` rows once. Only where
    :func:`attacked_serves`, and only for an attack that
    ``ops/coordinatewise.py`` declares formable in a kernel: a caller asks
    both first (``coordinatewise.attacked_in_kernel``), and elsewhere
    builds the matrix for :func:`trimmed_mean`."""
    return _sorted_reduce_attacked(honest, attack=attack, b=b, mode="trimmed", f=f)


def coordinate_median_attacked(honest: Array, *, attack, b: int) -> Array:
    """:func:`coordinate_median` as :func:`trimmed_mean_attacked` is
    :func:`trimmed_mean`."""
    return _sorted_reduce_attacked(honest, attack=attack, b=b, mode="median")


def _windowed_row_mean(s: Array, count, *, f: int) -> Array:
    """Mean of sorted rows ``[f, count - f)`` via a zero-masked einsum
    row contraction. ``count`` may be a static int or a traced scalar —
    an einsum contraction accumulates sequentially over the row axis, so
    appending zero rows (mask padding) preserves every partial sum
    bit-for-bit, unlike ``jnp.sum``/``jnp.mean`` whose reduction tree
    re-associates as the row count grows (the masked/ragged parity
    contract of the serving tier rests on this; pinned by
    ``tests/test_masked_finalize.py`` up to the bench's bucket cap)."""
    pos = jnp.arange(s.shape[0])[:, None]
    window = (pos >= f) & (pos < count - f)
    kept = jnp.where(window, s, jnp.zeros((), s.dtype))
    ones = jnp.ones((s.shape[0],), s.dtype)
    total = jnp.einsum("n,nd->d", ones, kept)
    denom = count - 2 * f
    if isinstance(denom, int):
        return total / denom
    return total * (jnp.asarray(1.0, total.dtype) / denom.astype(total.dtype))


@partial(jax.jit, static_argnames=("f",))
def _trimmed_mean_xla(x: Array, *, f: int) -> Array:
    n = x.shape[0]
    s = sort_rows(x) if x.ndim == 2 else jnp.sort(x, axis=0)
    return _windowed_row_mean(s, n, f=f)


def mean_of_medians(x: Array, *, f: int) -> Array:
    """MeaMed: per coordinate keep the ``n - f`` values closest to the median
    and average them (ref: ``aggregators/coordinate_wise/mean_of_medians.py:28-82``).

    ONE sort serves both statistics: the ``k`` values closest to the
    median are a contiguous window of the sorted column, so the cut
    deviation (the k-th smallest ``|x - med|``) is the minimum over
    window starts ``s`` of ``max(med - xs[s], xs[s+k-1] - med)`` — no
    second sort of a materialized deviation matrix (the old pipeline
    paid median-sort + deviation-sort, ~7 HBM passes; this is ~4).
    Selection then stays threshold-based (not ``argsort`` + gather,
    measured ~10x slower than its HBM cost at 64x65,536 on v5e): keep
    everything strictly below the cut and break ties AT the cut by node
    order via a cumulative count — exactly the stable-argsort tie rule
    (the cut VALUE is identical, so tie semantics are unchanged).

    Dispatch — with MeaMed's own floor and cap, ``MEAMED_MIN_DIM`` and
    ``MEAMED_MAX_DIM`` — resolves HERE, in Python, before the jitted
    implementation traces. The XLA fallback sorts int32 keys
    (:func:`sort_rows`, 2.4x the old fallback's throughput on XLA:CPU at
    the 64x65,536 grid row), or through the Pallas sort network where
    the generic gate holds (d past MeaMed's cap).
    """
    n = x.shape[0]
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    if pallas_serves(x, min_dim=MEAMED_MIN_DIM, max_dim=MEAMED_MAX_DIM):
        # one fused launch: 1 HBM read + a (1, d) write, vs ~4 passes for
        # the sort/window/mask pipeline below
        from .pallas_kernels import meamed_stream_pallas

        return meamed_stream_pallas(x[None], f=f)[0]
    return _mean_of_medians_xla(x, f=f, use_network=pallas_serves(x))


@partial(jax.jit, static_argnames=("f", "use_network"))
def _mean_of_medians_xla(x: Array, *, f: int, use_network: bool) -> Array:
    n = x.shape[0]
    k = n - f
    from .pallas_kernels import sort_columns

    if not jnp.issubdtype(x.dtype, jnp.floating):
        # jnp.median promotes ints to float; a literal 0.5 in an int
        # dtype would silently truncate the midpoint to zero
        x = x.astype(
            jax.eval_shape(
                lambda a: jnp.median(a, axis=0),
                jax.ShapeDtypeStruct(x.shape, x.dtype),
            ).dtype
        )
    if use_network:
        xs = sort_columns(x)
    elif x.ndim == 2:
        xs = sort_rows(x)
    else:
        xs = jnp.sort(x, axis=0)
    lo, hi = (n - 1) // 2, n // 2
    if lo == hi:
        med = xs[lo]  # odd n: the element itself — no sum to overflow
    else:
        # 0.5*a + 0.5*b, not (a+b)*0.5: the sum of two near-max values
        # overflows f32/bf16 where the true median is representable
        half = jnp.asarray(0.5, x.dtype)
        med = xs[lo] * half + xs[hi] * half
    # NaNs sort last: the middle rows would read finite, but the
    # reference's jnp.median semantics propagate NaN column-wide
    med = jnp.where(jnp.isnan(xs[n - 1]), jnp.asarray(jnp.nan, x.dtype), med)
    # k-th smallest deviation via the contiguous-window identity
    # (|xs[s]-med| = med - xs[s] and |xs[s+k-1]-med| = xs[s+k-1] - med
    # are the same f32 subtractions as |x - med|, so the cut is
    # bit-identical to sorting the deviations)
    radius = jnp.maximum(
        med[None, :] - xs[: n - k + 1], xs[k - 1 :] - med[None, :]
    )
    dev = jnp.abs(x - med[None, :])
    # a NON-finite median breaks the window arithmetic (inf - inf = NaN
    # inside radius); there every deviation is inf-or-NaN, so the k-th
    # smallest is inf iff at least k deviations are non-NaN — the old
    # deviation-sort cut (finite x vs an inf median selects the k
    # finite-deviation rows, matching the gather-based reference)
    cut_nonfinite = jnp.where(
        jnp.sum(jnp.where(jnp.isnan(dev), 0, 1), axis=0) >= k,
        jnp.asarray(jnp.inf, x.dtype),
        jnp.asarray(jnp.nan, x.dtype),
    )
    cut = jnp.where(
        jnp.isfinite(med), jnp.min(radius, axis=0), cut_nonfinite
    )
    below = dev < cut[None, :]
    at = dev == cut[None, :]
    # how many at-cut entries still fit, filled in node order (stable ties)
    quota = k - jnp.sum(below, axis=0)
    take_at = at & (jnp.cumsum(at, axis=0) <= quota[None, :])
    mask = below | take_at
    sel = jnp.where(mask, x, jnp.zeros((), x.dtype))
    # einsum row contraction, not jnp.sum: sequential accumulation over
    # the row axis is what makes the masked/ragged mirror
    # (masked_mean_of_medians) bit-identical at the padded shape
    ones = jnp.ones((n,), x.dtype)
    out = jnp.einsum("n,nd->d", ones, sel) / jnp.asarray(k, x.dtype)
    if jnp.issubdtype(x.dtype, jnp.floating):
        # cut is NaN iff fewer than k finite deviations exist (NaNs sort
        # last) — the gather-based selection would have returned NaN there
        out = jnp.where(jnp.isnan(cut), jnp.asarray(jnp.nan, x.dtype), out)
    return out


# ---------------------------------------------------------------------------
# Geometric aggregators
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("f",))
def krum_scores(x: Array, *, f: int) -> Array:
    """Krum score per node: sum of squared distances to its ``n - f - 1``
    nearest neighbors, self excluded
    (ref: ``aggregators/geometric_wise/krum.py:183-190``).
    """
    n = x.shape[0]
    if not 0 <= f < n - 1:
        raise ValueError(f"f must satisfy 0 <= f < n-1 (got n={n}, f={f})")
    d2 = pairwise_sq_dists(x)
    # Sorting each row puts the self-distance (0) first; the reference takes
    # columns [1, n-f) of the argsort. Summing the sorted row over that same
    # slice is identical and avoids the gather.
    row_sorted = jnp.sort(d2, axis=1)
    return jnp.sum(row_sorted[:, 1 : n - f], axis=1)


def _nan_last_ranks(scores: Array) -> Array:
    """Per-row rank of ``scores`` under the stable argsort order every
    selection path shares: ascending scores, ties broken by row index,
    NaN scores LAST. The two-level (isnan, score) key matters: plain
    comparisons would rank a NaN-score row first (all comparisons
    against NaN are False), letting an adversarial NaN gradient into
    the selection.

    Computed as a three-key ``lax.sort`` + rank scatter — O(n log n).
    The previous pairwise-comparison-matrix formulation was O(n²) in
    both FLOPs and memory, invisible at grid cohort sizes but ~2.3 s
    of the sharded root's merge at the 32k-row merged buckets the
    hierarchical fold serves (ISSUE 12); the integer ranks are
    IDENTICAL under both formulations (rank = #rows strictly before
    under the (isnan, score, index) lexicographic key), so every
    selection, aggregate bit, and pinned digest is unchanged."""
    n = scores.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    isnan = jnp.isnan(scores)
    s = jnp.where(isnan, jnp.zeros_like(scores), scores)
    # canonicalize -0.0 → +0.0: lax.sort orders floats by TOTAL order
    # (-0.0 < +0.0) while the comparison-matrix formulation used IEEE
    # == (zeros tie, index breaks) — without this a ±0.0 score pair
    # would rank differently than before the rewrite
    s = jnp.where(s == 0, jnp.zeros_like(s), s)
    _, _, order = lax.sort(
        (isnan.astype(jnp.int32), s, idx), num_keys=3
    )
    return jnp.zeros((n,), jnp.int32).at[order].set(idx)


def ranked_mean(x: Array, scores: Array, q: int) -> Array:
    """Mean of the ``q`` lowest-score rows of ``x`` without a row gather.

    Equivalent to ``jnp.mean(x[jnp.argsort(scores)[:q]], axis=0)`` (stable
    ties broken by row index, NaN scores last — :func:`_nan_last_ranks`),
    but selection happens through a masked matvec: XLA's dynamic row
    gather on TPU measured ~7x slower than its HBM cost (1.45 ms vs
    ~0.2 ms for 12 rows of a 64x1M f32 matrix on v5e), while the
    rank-mask contraction streams ``x`` once at full bandwidth on the
    MXU.
    """
    acc = _feature_matmul_dtype(x)
    selected = _nan_last_ranks(scores) < q
    w = jnp.where(selected, 1.0 / q, 0.0).astype(acc)
    # Zero non-selected rows before the contraction: 0-weight times a NaN/inf
    # gradient is NaN in the matvec, whereas a gather physically excludes the
    # row. Selected rows keep their values, so non-finite data that IS chosen
    # still propagates faithfully. The select fuses into the einsum's read.
    xm = jnp.where(selected[:, None], x, jnp.zeros((), x.dtype))
    out = jnp.einsum("n,nd->d", w, xm, preferred_element_type=acc)
    return out.astype(x.dtype)


def _selection_mean_xla(
    x: Array, scores: Array, q: int, any_bad: Array
) -> Array:
    """Mean of the ``q`` lowest-score rows on the XLA fallback path, with
    the same ranking as :func:`ranked_mean` (stable ties by row index,
    NaN scores last) but the masked-copy pass made CONDITIONAL: the
    ``jnp.where(selected, x, 0)`` materialization exists only to keep
    ``0 * inf = NaN`` out of the contraction, yet it costs a full
    (n, d) write+read — 9 of the 17 ms of the Multi-Krum grid row on
    XLA:CPU. ``any_bad`` (a scalar the caller derives for free from its
    score pipeline, e.g. non-finite Gram diagonal — conservative: f32
    norm overflow of a finite row also routes to the masked path) gates
    a ``lax.cond``: finite data takes the single-pass ``w @ x``
    contraction, non-finite data the exact masked path. Results are
    identical in both branches for finite data (same contraction, the
    mask is then a no-op)."""
    return _selected_rows_mean(x, _nan_last_ranks(scores) < q, q, any_bad)


def _selected_rows_mean(
    x: Array, selected: Array, q, any_bad: Array
) -> Array:
    """``mean(x[selected])`` for exactly ``q`` selected rows, as the
    conditional-mask contraction shared by :func:`_selection_mean_xla`
    (static ``q``) and :func:`masked_selection_mean` (traced ``q`` —
    the reciprocal weight divides in f32 exactly like the unpadded
    path's divide-by-constant rewrite). See ``_selection_mean_xla``'s
    docstring for the any_bad/lax.cond rationale — keep both callers'
    bit-parity in mind before touching the masking rule or the
    accumulation dtype."""
    acc = _feature_matmul_dtype(x)
    w = jnp.where(selected, 1.0 / q, 0.0).astype(acc)

    def masked(_):
        xm = jnp.where(selected[:, None], x, jnp.zeros((), x.dtype))
        return jnp.einsum("n,nd->d", w, xm, preferred_element_type=acc)

    def fast(_):
        return jnp.einsum("n,nd->d", w, x, preferred_element_type=acc)

    return lax.cond(any_bad, masked, fast, None).astype(x.dtype)


def multi_krum(x: Array, *, f: int, q: int) -> Array:
    """Multi-Krum: mean of the ``q`` lowest-score nodes
    (ref: ``aggregators/geometric_wise/krum.py:147-242``). Dispatch
    resolves pre-trace; the XLA fallback computes the Gram ONCE (scores
    via :func:`krum_scores_from_gram`) and selects through the
    conditional-mask contraction (:func:`_selection_mean_xla`) — 1.3x
    the old score+masked-mean pipeline on XLA:CPU at the 80x65,536 grid
    row."""
    n = x.shape[0]
    if not 1 <= q <= n - f:
        raise ValueError(f"q must satisfy 1 <= q <= n - f (got n={n}, f={f}, q={q})")
    if pallas_serves(x):
        from .pallas_kernels import selection_mean_pallas

        return selection_mean_pallas(x, f=f, q=q, mode="krum")
    return _multi_krum_xla(x, f=f, q=q)


@partial(jax.jit, static_argnames=("f", "q"))
def _multi_krum_xla(x: Array, *, f: int, q: int) -> Array:
    gram = gram_matrix(x)
    scores = krum_scores_from_gram(gram, f=f)
    # a non-finite row shows up as a non-finite squared norm on the Gram
    # diagonal (NaN -> NaN, inf -> inf; f32 overflow of a finite row is
    # flagged too — conservative), so the guard costs nothing extra
    any_bad = ~jnp.all(jnp.isfinite(jnp.diagonal(gram)))
    return _selection_mean_xla(x, scores, q, any_bad)


def multi_krum_stream(xs: Array, *, f: int, q: int) -> Array:
    """Multi-Krum over a stream of ``K`` stacked rounds ``xs: (K, n, d)``
    in one dispatch (the training-loop / replay shape — see
    ``aggregate_stream``). On TPU at large ``d`` this is ONE fused kernel
    launch with ``2 K`` HBM sweeps and zero per-round slice copies
    (``pallas_kernels.selection_mean_stream_pallas``; an XLA-level scan
    materializes each round's 256 MB slice before the Gram can read it —
    measured 1.23 ms vs 0.85 ms per 64x1M f32 round on v5e)."""
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import selection_mean_stream_pallas

        return selection_mean_stream_pallas(xs, f=f, q=q, mode="krum")
    return aggregate_stream(partial(multi_krum, f=f, q=q), xs)


def krum(x: Array, *, f: int) -> Array:
    """Classic Krum = Multi-Krum with ``q=1``."""
    return multi_krum(x, f=f, q=1)


def nnm_multi_krum(x: Array, *, f_nnm: int, f: int, q: int) -> Array:
    """The canonical robust pipeline — Nearest-Neighbor Mixing feeding
    Multi-Krum (NNM is designed as exactly this pre-mixer; ref:
    ``byzpy/pre_aggregators/nnm.py`` composed with
    ``aggregators/geometric_wise/krum.py``) — fused when the dispatch
    gates allow: the mixed matrix never materializes, its Gram derives
    from the raw Gram in VMEM (``Gm = Aᵀ G̃ A / k²``) and the final mean
    collapses to source-space weights, so the whole pipeline costs the
    2 HBM sweeps of a lone aggregator instead of the two-step path's ~5
    (``pallas_kernels.nnm_selection_mean_stream_pallas``)."""
    if pallas_serves(x):
        from .pallas_kernels import nnm_selection_mean_stream_pallas

        return nnm_selection_mean_stream_pallas(
            x[None], f_nnm=f_nnm, f=f, q=q, mode="krum"
        )[0]
    from .preagg import nnm

    return multi_krum(nnm(x, f=f_nnm), f=f, q=q)


def nnm_multi_krum_stream(xs: Array, *, f_nnm: int, f: int, q: int) -> Array:
    """``nnm_multi_krum`` over ``K`` stacked rounds ``(K, n, d)`` in one
    dispatch (the training-loop / replay shape; see ``aggregate_stream``)."""
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import nnm_selection_mean_stream_pallas

        return nnm_selection_mean_stream_pallas(
            xs, f_nnm=f_nnm, f=f, q=q, mode="krum"
        )
    return aggregate_stream(partial(nnm_multi_krum, f_nnm=f_nnm, f=f, q=q), xs)


def clipped_multi_krum(x: Array, *, tau: float, f: int, q: int) -> Array:
    """Static L2 clipping feeding Multi-Krum, fused when the dispatch
    gates allow — the diagonal instance of the Gram-collapse that fuses
    NNM (see ``nnm_multi_krum``): the clip factors come off the Gram
    diagonal, the clipped Gram is ``c_i c_j G_ij`` in VMEM, and the
    selected mean collapses to weights ``w_sel * c``
    (``pallas_kernels.clip_selection_mean_stream_pallas``)."""
    if not tau > 0:
        # validate BEFORE dispatch: the fallback's clip_rows would accept
        # tau <= 0 and silently sign-flip/zero every row
        raise ValueError(f"tau must be positive (got {tau})")
    if pallas_serves(x):
        from .pallas_kernels import clip_selection_mean_stream_pallas

        return clip_selection_mean_stream_pallas(
            x[None], tau=tau, f=f, q=q, mode="krum"
        )[0]
    from .preagg import clip_rows

    return multi_krum(clip_rows(x, threshold=tau), f=f, q=q)


def clipped_multi_krum_stream(
    xs: Array, *, tau: float, f: int, q: int
) -> Array:
    """``clipped_multi_krum`` over ``K`` stacked rounds ``(K, n, d)`` in
    one dispatch (see ``aggregate_stream``)."""
    if not tau > 0:
        raise ValueError(f"tau must be positive (got {tau})")
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import clip_selection_mean_stream_pallas

        return clip_selection_mean_stream_pallas(
            xs, tau=tau, f=f, q=q, mode="krum"
        )
    return aggregate_stream(partial(clipped_multi_krum, tau=tau, f=f, q=q), xs)


def arc_multi_krum(x: Array, *, f_arc: int, f: int, q: int) -> Array:
    """Adaptive Robust Clipping feeding Multi-Krum, fused when the
    dispatch gates allow — ARC's factors are norm-derived like static
    clipping's (its threshold is the ``cut_off``-th smallest norm,
    rank-counted in VMEM), so the same Gram-collapse applies
    (``pallas_kernels.arc_selection_mean_stream_pallas``)."""
    if not 0 <= f_arc <= x.shape[0]:
        # validate BEFORE dispatch: the fallback's arc_clip would clamp a
        # negative f_arc to "no clipping" silently
        raise ValueError(
            f"f_arc must satisfy 0 <= f_arc <= n (got {f_arc}, n={x.shape[0]})"
        )
    if pallas_serves(x):
        from .pallas_kernels import arc_selection_mean_stream_pallas

        return arc_selection_mean_stream_pallas(
            x[None], f_arc=f_arc, f=f, q=q, mode="krum"
        )[0]
    from .preagg import arc_clip

    return multi_krum(arc_clip(x, f=f_arc), f=f, q=q)


def arc_multi_krum_stream(xs: Array, *, f_arc: int, f: int, q: int) -> Array:
    """``arc_multi_krum`` over ``K`` stacked rounds ``(K, n, d)`` in one
    dispatch (see ``aggregate_stream``)."""
    if not 0 <= f_arc <= xs.shape[-2]:
        raise ValueError(
            f"f_arc must satisfy 0 <= f_arc <= n (got {f_arc}, "
            f"n={xs.shape[-2]})"
        )
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import arc_selection_mean_stream_pallas

        return arc_selection_mean_stream_pallas(
            xs, f_arc=f_arc, f=f, q=q, mode="krum"
        )
    return aggregate_stream(partial(arc_multi_krum, f_arc=f_arc, f=f, q=q), xs)


def geometric_median(
    x: Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 256,
    eps: float = 1e-12,
    init: str = "median",
) -> Array:
    """Geometric median via Weiszfeld iterations as a ``lax.while_loop``
    (ref: ``aggregators/geometric_wise/geometric_median.py:69-104``; the
    reference's per-iteration subtask fan-out over shm chunks becomes a
    single compiled loop whose reductions shard over the mesh). The
    Pallas gate for the fused iteration kernel resolves here, pre-trace.
    """
    if init not in {"median", "mean"}:
        raise ValueError("init must be 'median' or 'mean'")
    return _geometric_median_impl(
        x, tol=tol, max_iter=max_iter, eps=eps, init=init,
        use_kernel=pallas_serves(x),
    )


@partial(
    jax.jit,
    static_argnames=("tol", "max_iter", "eps", "init", "use_kernel"),
)
def _geometric_median_impl(
    x: Array,
    *,
    tol: float,
    max_iter: int,
    eps: float,
    init: str,
    use_kernel: bool,
) -> Array:
    z0 = jnp.median(x, axis=0) if init == "median" else _row_mean_einsum(x)
    # The loop carry tracks the previous center instead of a scalar delta:
    # every carry component is then derived from ``x``, which keeps the
    # varying-manual-axes types consistent when this runs inside a
    # ``shard_map`` region (a constant-initialized carry would be
    # unvarying on input but varying on output and fail to trace).
    # Iteration 1 is forced by the it==0 disjunct — NOT by offsetting
    # zprev0, which floating-point absorbs whenever |z0| is large enough
    # (f32: 2^24), silently skipping every Weiszfeld step.

    def cond(state):
        z, zprev, it = state
        delta = jnp.sqrt(jnp.sum((z - zprev) ** 2))
        return ((it == 0) | (delta > tol)) & (it < max_iter)

    def body(state):
        z, _, it = state
        if use_kernel:
            # fused two-sweep step: 2 reads of x per iteration vs ~4
            # passes for the materialized diff/norm/weighted-sum below
            from .pallas_kernels import weighted_center_step_pallas

            z_new = weighted_center_step_pallas(
                x, z, mode="weiszfeld", eps=eps
            )
        else:
            diff = x - z[None, :]
            dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
            w = (1.0 / jnp.maximum(dist, eps)).astype(x.dtype)
            # einsum row contractions (see _windowed_row_mean): the
            # masked mirror reproduces each step bit-for-bit at the
            # padded shape
            num = jnp.einsum("n,nd->d", w, x)
            den = jnp.einsum("n,n->", w, jnp.ones_like(w))
            z_new = num / den
        return z_new, z, it + 1

    z, _, _ = lax.while_loop(cond, body, (z0, z0, 0))
    return z


def centered_clipping(
    x: Array,
    *,
    c_tau: float,
    M: int = 10,
    eps: float = 1e-12,
    init: str = "mean",
) -> Array:
    """Centered clipping (Karimireddy et al. 2021):
    ``v <- v + mean_i clip(x_i - v, c_tau)`` for ``M`` iterations
    (ref: ``aggregators/norm_wise/center_clipping.py:29-120``). The
    Pallas gate for the fused iteration kernel resolves here, pre-trace.
    """
    if init not in {"mean", "median", "zero"}:
        raise ValueError("init must be one of {'mean','median','zero'}")
    return _centered_clipping_impl(
        x, c_tau=c_tau, M=M, eps=eps, init=init,
        use_kernel=pallas_serves(x),
    )


@partial(
    jax.jit, static_argnames=("c_tau", "M", "eps", "init", "use_kernel")
)
def _centered_clipping_impl(
    x: Array,
    *,
    c_tau: float,
    M: int,
    eps: float,
    init: str,
    use_kernel: bool,
) -> Array:
    if init == "mean":
        v0 = _row_mean_einsum(x)
    elif init == "median":
        v0 = jnp.median(x, axis=0)
    else:
        v0 = jnp.zeros((x.shape[1],), x.dtype)
    n = x.shape[0]

    def body(_, v):
        if use_kernel:
            from .pallas_kernels import weighted_center_step_pallas

            return weighted_center_step_pallas(
                x, v, mode="clip", eps=eps, c_tau=c_tau
            )
        diff = x - v[None, :]
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
        scale = jnp.minimum(1.0, c_tau / jnp.maximum(dist, eps))
        # einsum row contraction (see _windowed_row_mean) so the masked
        # mirror matches bit-for-bit at the padded shape
        step = jnp.einsum("n,nd->d", scale.astype(x.dtype), diff)
        return v + step / n

    return lax.fori_loop(0, M, body, v0)


def cge_stream(xs: Array, *, f: int) -> Array:
    """CGE over ``K`` stacked rounds in one fused launch (see
    ``multi_krum_stream``)."""
    n = xs.shape[-2]
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import selection_mean_stream_pallas

        return selection_mean_stream_pallas(xs, f=0, q=n - f, mode="cge")
    return aggregate_stream(partial(cge, f=f), xs)


def monna_stream(xs: Array, *, f: int, reference_index: int = 0) -> Array:
    """MoNNA over ``K`` stacked rounds in one fused launch."""
    n = xs.shape[-2]
    if 2 * f >= n:
        raise ValueError(f"Cannot tolerate 2f >= n (got n={n}, f={f})")
    if pallas_serves(xs, stream=True):
        from .pallas_kernels import selection_mean_stream_pallas

        return selection_mean_stream_pallas(
            xs, f=0, q=n - f, mode="monna", reference_index=reference_index
        )
    return aggregate_stream(partial(monna, f=f, reference_index=reference_index), xs)


def cge(x: Array, *, f: int) -> Array:
    """Comparative gradient elimination: drop the ``f`` largest-L2-norm
    vectors, average the rest
    (ref: ``aggregators/norm_wise/comparative_gradient_elimination.py``).
    Dispatch resolves pre-trace; the XLA fallback selects through the
    conditional-mask contraction (the norms themselves are the
    non-finite guard — see :func:`_selection_mean_xla`)."""
    n = x.shape[0]
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    if pallas_serves(x):
        from .pallas_kernels import selection_mean_pallas

        return selection_mean_pallas(x, f=0, q=n - f, mode="cge")
    return _cge_xla(x, f=f)


@partial(jax.jit, static_argnames=("f",))
def _cge_xla(x: Array, *, f: int) -> Array:
    n = x.shape[0]
    norms = jnp.sum(x * x, axis=1)
    # a row with non-finite data has a non-finite squared norm (0-weight
    # times such a row would NaN the fast contraction)
    any_bad = ~jnp.all(jnp.isfinite(norms))
    return _selection_mean_xla(x, norms, n - f, any_bad)


def monna(x: Array, *, f: int, reference_index: int = 0) -> Array:
    """MoNNA: mean of the ``n - f`` nearest neighbors (by squared distance,
    self included) of a trusted reference node
    (ref: ``aggregators/geometric_wise/monna.py:36-83``). Dispatch
    resolves pre-trace; the XLA fallback selects through the
    conditional-mask contraction (:func:`_selection_mean_xla`)."""
    n = x.shape[0]
    if 2 * f >= n:
        raise ValueError(f"Cannot tolerate 2f >= n (got n={n}, f={f})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index must be in [0, {n}) (got {reference_index})")
    if pallas_serves(x):
        from .pallas_kernels import selection_mean_pallas

        return selection_mean_pallas(
            x, f=0, q=n - f, mode="monna", reference_index=reference_index
        )
    return _monna_xla(x, f=f, reference_index=reference_index)


@partial(jax.jit, static_argnames=("f", "reference_index"))
def _monna_xla(x: Array, *, f: int, reference_index: int) -> Array:
    n = x.shape[0]
    diff = x - x[reference_index][None, :]
    dists = jnp.sum(diff * diff, axis=1)
    # any non-finite row (or a non-finite reference) yields a non-finite
    # distance, so the distances themselves are the guard
    any_bad = ~jnp.all(jnp.isfinite(dists))
    return _selection_mean_xla(x, dists, n - f, any_bad)


@partial(jax.jit, static_argnames=("f", "power_iters"))
def caf(x: Array, *, f: int, power_iters: int = 3, seed: int = 0) -> Array:
    """Covariance-bound-Agnostic Filter: iteratively down-weight points along
    the dominant residual direction until at most ``n - 2f`` total weight
    remains; return the mean seen at the smallest dominant eigenvalue
    (ref: ``aggregators/norm_wise/caf.py:140-185``).

    Data-dependent iteration count -> ``lax.while_loop``; each pass removes
    the max-leverage point so the loop is bounded by ``n`` iterations.
    """
    n, d = x.shape
    if 2 * f >= n:
        raise ValueError(f"Cannot tolerate 2f >= n (got n={n}, f={f})")

    v_init = jax.random.normal(jax.random.PRNGKey(seed), (d,), dtype=x.dtype)
    v_init = v_init / jnp.maximum(jnp.linalg.norm(v_init), 1e-12)

    def dominant_eigenpair(diffs, w):
        def pi_body(_, vec):
            proj = diffs @ vec
            nxt = jnp.sum((w * proj)[:, None] * diffs, axis=0)
            nn = jnp.linalg.norm(nxt)
            return jnp.where(nn > 1e-12, nxt / jnp.maximum(nn, 1e-30), vec)

        vec = lax.fori_loop(0, power_iters, pi_body, v_init)
        proj = diffs @ vec
        eig = jnp.sum(w * proj * proj) / jnp.maximum(jnp.sum(w), 1e-12)
        return eig, vec

    big = jnp.asarray(jnp.finfo(jnp.float32).max, x.dtype)

    def cond(state):
        w, _, _, stop, it = state
        return (~stop) & (jnp.sum(w) > n - 2 * f) & (it < 4 * n)

    def body(state):
        w, best_mu, best_lam, _, it = state
        total = jnp.sum(w)
        mu = jnp.sum(w[:, None] * x, axis=0) / total
        diffs = x - mu[None, :]
        lam, vec = dominant_eigenpair(diffs, w)
        better = lam < best_lam
        best_lam = jnp.where(better, lam, best_lam)
        best_mu = jnp.where(better, mu, best_mu)
        proj = diffs @ vec
        tau = proj * proj
        # Leverage is compared among surviving points only: a zero-weight
        # outlier's huge tau would otherwise dominate tau_max and make the
        # survivors' update factors round to 1.0 (loop never terminates).
        # Restricting to w > 0 zeroes the max-leverage survivor every pass,
        # so the loop takes at most n iterations.
        tau_alive = jnp.where(w > 0.0, tau, -jnp.inf)
        tau_max = jnp.max(tau_alive)
        degenerate = tau_max <= 1e-12
        w_new = jnp.clip(w * (1.0 - tau / jnp.maximum(tau_max, 1e-30)), 0.0, None)
        w = jnp.where(degenerate, w, w_new)
        stop = degenerate | (jnp.sum(w) <= 0.0)
        return w, best_mu, best_lam, stop, it + 1

    state0 = (jnp.ones((n,), x.dtype), jnp.mean(x, axis=0), big, jnp.asarray(False), 0)
    _, best_mu, _, _, _ = lax.while_loop(cond, body, state0)
    return best_mu


# ---------------------------------------------------------------------------
# Subset-search aggregators (MDA / SMEA). Subset enumeration is combinatorial
# and stays on the host (ref keeps it on the coordinator too:
# ``aggregators/geometric_wise/minimum_diameter_average.py``); scoring is
# batched on device over an int32 ``(n_combos, m)`` index array.
# ---------------------------------------------------------------------------


@jax.jit
def subset_diameters(d2: Array, combos: Array) -> Array:
    """Diameter (max pairwise squared distance) of each row-index subset.

    ``d2``: ``(n, n)`` pairwise squared distances; ``combos``: ``(c, m)``.
    """
    sub = d2[combos[:, :, None], combos[:, None, :]]  # (c, m, m)
    return jnp.max(sub, axis=(1, 2))


@jax.jit
def subset_max_eigvals(gram: Array, combos: Array) -> Array:
    """SMEA score per subset: largest eigenvalue of the centered Gram block
    divided by ``m`` (ref: ``aggregators/geometric_wise/smea.py:63-88``).
    """
    m = combos.shape[1]

    def one(combo):
        sub = gram[combo[:, None], combo[None, :]]  # (m, m)
        h = jnp.eye(m, dtype=sub.dtype) - jnp.full((m, m), 1.0 / m, dtype=sub.dtype)
        centered = h @ sub @ h
        vals = jnp.linalg.eigvalsh(centered)
        return jnp.maximum(vals[-1], 0.0) / m

    return jax.vmap(one)(combos)


def _parallel_jacobi_schedule(m: int):
    """Round-robin (circle-method) rotation schedule: ``m_pad - 1``
    rounds of ``m_pad // 2`` DISJOINT (p, q) pairs covering every pair
    exactly once per sweep. Disjointness lets one loop step apply all
    its rotations at once — m=11 runs 11 vectorized steps per sweep
    instead of 55 sequential ones. Odd ``m`` pads with a dummy player;
    the bye pair is encoded ``(b, b)`` with valid=0 (its rotation is
    forced to the identity, and ``b`` appears nowhere else that round,
    so the row/col scatters never collide)."""
    m_pad = m + (m & 1)
    half = m_pad // 2
    players = list(range(m_pad))
    p_rounds, q_rounds, valid = [], [], []
    for _ in range(m_pad - 1):
        ps, qs, vs = [], [], []
        for i in range(half):
            a_, b_ = players[i], players[m_pad - 1 - i]
            lo, hi = min(a_, b_), max(a_, b_)
            if hi >= m:  # bye: partner sits this round out
                ps.append(lo)
                qs.append(lo)
                vs.append(0.0)
            else:
                ps.append(lo)
                qs.append(hi)
                vs.append(1.0)
        p_rounds.append(ps)
        q_rounds.append(qs)
        valid.append(vs)
        players = [players[0]] + [players[-1]] + players[1:-1]
    import numpy as np

    return (
        np.asarray(p_rounds, np.int32),
        np.asarray(q_rounds, np.int32),
        np.asarray(valid, np.float32),
    )


@partial(jax.jit, static_argnames=("sweeps",))
def subset_max_eigvals_jacobi(gram: Array, combos: Array, *, sweeps: int = 8) -> Array:
    """SMEA score per subset — identical quantity to
    ``subset_max_eigvals`` — computed with batched parallel-order Jacobi
    instead of ``eigvalsh``.

    XLA lowers ``eigvalsh`` on TPU to a serialized QR iteration: 380 ms
    for the C(16,11)=4368 batch of 11x11 problems in the reference's SMEA
    workload. Jacobi sweeps are batched VPU work instead; rotations are
    scheduled round-robin (``_parallel_jacobi_schedule``) so each loop
    step applies ``m // 2`` disjoint rotations at once — the sequential
    rotation count, which bounds the wall time of the ``fori_loop``,
    drops from m(m-1)/2 to m-1 per sweep (55 -> 11 at m=11). ``sweeps``
    sweeps give quadratic convergence — 8 reach f32 precision at m <= 32
    under both cyclic and parallel orderings, pinned against the LAPACK
    oracle in tests. Subsets touching a non-finite Gram row score
    ``+inf`` (an adversary must not crash — or win — the selection; same
    rule as the host path in ``aggregators/geometric_wise/smea.py``).
    """
    m = combos.shape[1]
    acc = jnp.float32 if gram.dtype in (jnp.bfloat16, jnp.float16) else gram.dtype
    sub = gram[combos[:, :, None], combos[:, None, :]].astype(acc)  # (c, m, m)
    if m < 2:
        # The centered 1x1 (or empty) Gram is identically zero — no
        # rotation schedule exists, and building one would index an empty
        # pair array. Non-finite singleton rows still score +inf.
        zeros = jnp.zeros((combos.shape[0],), dtype=gram.dtype)
        if m == 0:
            return zeros
        bad1 = ~jnp.isfinite(sub[:, 0, 0])
        return jnp.where(bad1, jnp.inf, zeros).astype(gram.dtype)
    h = jnp.eye(m, dtype=acc) - jnp.full((m, m), 1.0 / m, dtype=acc)
    a = h @ sub @ h
    bad = ~jnp.all(jnp.isfinite(a), axis=(1, 2))
    a = jnp.where(bad[:, None, None], jnp.eye(m, dtype=acc), a)

    # Static round-robin schedule walked by a fori_loop: each step applies
    # ALL of one round's disjoint rotations as (c, P)-batched vector ops —
    # the loop's sequential depth (what bounds wall time on the chip) is
    # sweeps * (m_pad - 1) instead of the cyclic order's
    # sweeps * m(m-1)/2. Unrolling inline instead would explode TPU
    # compile time (~1.8k update ops at m=11, sweeps=8).
    p_r, q_r, v_r = _parallel_jacobi_schedule(m)
    p_r, q_r, v_r = jnp.asarray(p_r), jnp.asarray(q_r), jnp.asarray(v_r)
    n_rounds = p_r.shape[0]

    def rotate_round(i, a):
        # One parallel Jacobi round (Golub & Van Loan 8.4 rotations over
        # disjoint pairs): stable c/s from the quadratic in t, rows and
        # columns updated through gather/scatter on the pair vectors.
        r = i % n_rounds
        p = lax.dynamic_index_in_dim(p_r, r, keepdims=False)  # (P,)
        q = lax.dynamic_index_in_dim(q_r, r, keepdims=False)
        v = lax.dynamic_index_in_dim(v_r, r, keepdims=False)
        app = a[:, p, p]  # (c, P)
        aqq = a[:, q, q]
        apq = a[:, p, q]
        safe = (jnp.abs(apq) > 1e-30) & (v > 0.5)
        tau = (aqq - app) / jnp.where(safe, 2.0 * apq, 1.0)
        # sign(0) must be +1 here: tau == 0 (app == aqq) wants a 45-degree
        # rotation, not the identity jnp.sign's zero would produce.
        sgn = jnp.where(tau >= 0.0, 1.0, -1.0)
        t = sgn / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
        t = jnp.where(safe, t, 0.0)
        c = 1.0 / jnp.sqrt(1.0 + t * t)
        s = t * c
        c_ = c[:, :, None]  # (c, P, 1)
        s_ = s[:, :, None]
        rp = a[:, p, :]  # (c, P, m)
        rq = a[:, q, :]
        # within a round p ∪ q has no duplicates (bye pairs repeat their
        # index only across the two separate scatters), so the updates
        # can't collide
        a = a.at[:, p, :].set(c_ * rp - s_ * rq)
        a = a.at[:, q, :].set(s_ * rp + c_ * rq)
        cp = a[:, :, p]  # (c, m, P)
        cq = a[:, :, q]
        c2 = c[:, None, :]
        s2 = s[:, None, :]
        a = a.at[:, :, p].set(c2 * cp - s2 * cq)
        a = a.at[:, :, q].set(s2 * cp + c2 * cq)
        return a

    a = lax.fori_loop(0, sweeps * n_rounds, rotate_round, a)
    top = jnp.max(jnp.diagonal(a, axis1=1, axis2=2), axis=1)
    scores = jnp.maximum(top, 0.0) / m
    return jnp.where(bad, jnp.inf, scores).astype(gram.dtype)


@jax.jit
def subset_mean(x: Array, combo: Array) -> Array:
    """Mean of the rows selected by ``combo``."""
    return jnp.mean(x[combo], axis=0)


def best_subset_by_score(scores: Array) -> Array:
    """Index of the minimum score (first on ties, matching the host loop)."""
    return jnp.argmin(scores)


# ---------------------------------------------------------------------------
# Incremental (arrival-order) fold primitives. These back the streaming
# ``fold``/``fold_finalize`` hooks on the aggregator classes: each update
# consumes ONE gradient row the moment it arrives, so the work hides in
# the straggler window of an overlapped round (engine.overlap) instead of
# running after the gather barrier. The batched ``*_stream`` ops above
# remain the fused shape for replaying already-buffered rounds.
# ---------------------------------------------------------------------------


def extremes_fold_update(buf: Array, row: Array, *, largest: bool) -> Array:
    """Fold ``row`` into a per-coordinate running buffer of the ``f``
    smallest (``largest=False``) or largest values seen so far.

    ``buf``: ``(f, d)``, initialized to ``+inf`` (smallest) / ``-inf``
    (largest) filler rows that real values displace. One ``(f+1, d)``
    sort per arrival — O(f·d) work per gradient, so a trimmed mean's
    sort cost streams over the round instead of spiking at the barrier.
    Assumes finite inputs (NaNs sort last and would corrupt the
    buffers); callers keep raw rows and fall back to the exact sorted
    path when a non-finite value was seen.
    """
    if buf.shape[0] == 0:
        return buf
    cat = jnp.concatenate([buf, row[None, :]], axis=0)
    s = jnp.sort(cat, axis=0)
    return s[1:] if largest else s[:-1]


def trimmed_mean_from_extremes(
    total: Array, low: Array, high: Array, n: int, *, f: int
) -> Array:
    """f-trimmed coordinate mean from a running sum and the folded
    extreme buffers: ``(Σx − Σ f smallest − Σ f largest) / (n − 2f)``.

    Same quantity as :func:`trimmed_mean` on the stacked matrix, but the
    summation order follows arrival order — parity with the barrier path
    is to float tolerance, not bit-identical (pinned in
    ``tests/test_overlap_stream.py``).
    """
    if not 0 <= 2 * f < n:
        raise ValueError(f"trim parameter f must satisfy 0 <= 2f < n (got n={n}, f={f})")
    kept = total
    if f > 0:
        kept = kept - jnp.sum(low, axis=0) - jnp.sum(high, axis=0)
    return kept / jnp.asarray(n - 2 * f, total.dtype)


@partial(jax.jit, donate_argnums=(0,))
def fold_add_donated(total: Array, row: Array) -> Array:
    """``total + row`` with the old ``total`` buffer DONATED to XLA, so
    the running coordinate sum of a streaming fold updates in place
    instead of allocating a fresh ``(d,)`` buffer per arrival (at 1M-dim
    f32 that is 4 MB of allocator traffic per gradient, 256 MB per
    64-node round, all inside the straggler window)."""
    return total + row


@partial(jax.jit, static_argnames=("largest",), donate_argnums=(0,))
def extremes_fold_update_donated(buf: Array, row: Array, *, largest: bool) -> Array:
    """:func:`extremes_fold_update` with the running extreme buffer
    donated — XLA reuses the ``(f, d)`` allocation across arrivals."""
    return extremes_fold_update(buf, row, largest=largest)


@partial(jax.jit, donate_argnums=(0, 1))
def gram_fold_update(
    buffer: Array, gram: Array, row: Array, index
) -> Tuple[Array, Array]:
    """Fold one arriving gradient into streaming-Gram state, in place.

    ``buffer`` is the ``(n, d)`` staging matrix (zero rows for slots not
    yet arrived), ``gram`` the ``(n, n)`` f32 accumulator, ``row`` the
    arriving ``(d,)`` gradient, ``index`` its canonical slot. One donated
    dispatch per arrival: the row lands in the staging buffer via an
    in-place dynamic-update-slice (donation kills the full-matrix copy a
    functional update would pay — 20 MB per arrival at 80x65,536), ONE
    matvec computes its dot products against every staged row
    (not-yet-arrived slots are zero rows whose entries later arrivals
    overwrite), and the Gram's row+column ``index`` are written. This
    replaces the old per-arrival list of k separate einsum dispatches
    (O(n^2) host dispatches per round -> O(n)) and the finalize-time
    O(n) ``.at[].set`` Gram assembly. Accumulation is f32 for 16-bit
    rows (same policy as the barrier path)."""
    rowc = row.astype(buffer.dtype)
    buffer = lax.dynamic_update_slice(buffer, rowc[None, :], (index, 0))
    g = jnp.einsum(
        "nd,d->n", buffer, rowc, preferred_element_type=gram.dtype
    ).astype(gram.dtype)
    gram = lax.dynamic_update_slice(gram, g[None, :], (index, 0))
    gram = lax.dynamic_update_slice(gram, g[:, None], (0, index))
    return buffer, gram


def gram_block(a, b):
    """The canonical HOST-side Gram block contraction of the sharded
    tier's block-contraction contract: ``(a @ b.T)`` as float32 over
    float32 contiguous operands, under the NaN/overflow-tolerant
    errstate the family extras use. Every producer and verifier of a
    partial fold's Gram extras — the shard's local diagonal block
    (``MultiKrum._partial_extras``), the merge tree's cross-block
    assembly (``combine_partials`` → ``Aggregator.combined_extras``),
    the root's incremental merge accumulator
    (``MultiKrum.fold_merge_add``), and the ``extras_policy='verify'``
    recompute (``Aggregator.segmented_extras_reference``) — MUST call
    this one function on the same row bits: a Gram entry is then the
    same dot program on both sides, so the cross-check is EXACT bit
    equality, not "matmul tolerance" (a full-matrix sgemm and a
    blocked sgemm may legally disagree in the last ulp because kernel
    selection depends on operand shape). Contiguity is normalized here
    so a verifier reading a sliced view of a concatenated frame feeds
    BLAS the same layout the producer did."""
    import numpy as np

    ac = np.ascontiguousarray(np.asarray(a, np.float32))
    bc = np.ascontiguousarray(np.asarray(b, np.float32))
    with np.errstate(invalid="ignore", over="ignore"):
        return (ac @ bc.T).astype(np.float32)


def krum_scores_from_gram(gram: Array, *, f: int) -> Array:
    """Krum score per node from a precomputed ``(n, n)`` Gram matrix —
    the finalize step of the incremental Gram fold, where each arriving
    gradient contributed its dot products against the rows already in
    hand. Same math as :func:`krum_scores` (norms off the diagonal,
    clamped squared distances, sorted-row sum)."""
    n = gram.shape[0]
    if not 0 <= f < n - 1:
        raise ValueError(f"f must satisfy 0 <= f < n-1 (got n={n}, f={f})")
    norms = jnp.diagonal(gram)
    d2 = jnp.maximum(norms[:, None] + norms[None, :] - 2.0 * gram, 0.0)
    row_sorted = jnp.sort(d2, axis=1)
    # windowed einsum contraction (not a slice + jnp.sum): keeps the
    # masked/ragged mirror (masked_krum_scores_from_gram) bit-identical
    # under zero padding — see _windowed_row_mean
    pos = jnp.arange(n)[None, :]
    window = (pos >= 1) & (pos < n - f)
    kept = jnp.where(window, row_sorted, jnp.zeros((), row_sorted.dtype))
    return jnp.einsum("nk,k->n", kept, jnp.ones((n,), kept.dtype))


def multi_krum_from_gram(x: Array, gram: Array, *, f: int, q: int) -> Array:
    """Multi-Krum selection given the stacked matrix AND its Gram (built
    incrementally by the streaming fold): scores from the Gram, mean of
    the ``q`` best rows. Skips the Gram recompute that
    :func:`multi_krum` would pay. On TPU at large ``d`` this is ONE
    fused Pallas pass (``pallas_kernels.selection_mean_from_gram_pallas``:
    scores→selection→weighted-mean with a single HBM read of ``x`` —
    pairwise distances never materialize in HBM); elsewhere the
    conditional-mask XLA contraction (non-finite guard free off the
    Gram diagonal)."""
    n = x.shape[0]
    if not 1 <= q <= n - f:
        raise ValueError(f"q must satisfy 1 <= q <= n - f (got n={n}, f={f}, q={q})")
    if pallas_serves(x):
        from .pallas_kernels import selection_mean_from_gram_pallas

        return selection_mean_from_gram_pallas(x, gram, f=f, q=q, mode="krum")
    return _multi_krum_from_gram_xla(x, gram, f=f, q=q)


@partial(jax.jit, static_argnames=("f", "q"))
def _multi_krum_from_gram_xla(
    x: Array, gram: Array, *, f: int, q: int
) -> Array:
    scores = krum_scores_from_gram(gram, f=f)
    any_bad = ~jnp.all(jnp.isfinite(jnp.diagonal(gram)))
    return _selection_mean_xla(x, scores, q, any_bad)


# ---------------------------------------------------------------------------
# Masked / ragged aggregation. The serving tier (``byzpy_tpu.serving``)
# closes rounds with whatever cohort arrived in the window, then pads the
# cohort into one of a few BUCKET shapes so jit caches stay warm: every
# function here consumes the padded ``(n, d)`` matrix (zero rows for
# absent slots), an ``(n,)`` boolean validity mask with ``m`` True
# entries, and computes the EXACT size-``m`` aggregate of the valid rows
# — bit-for-bit equal (f32, finite inputs) to the corresponding unpadded
# function on the compacted ``(m, d)`` matrix, for any ``m`` at ONE
# compiled program per bucket (``m`` is traced, never a shape).
#
# The bit-parity recipe (pinned by ``tests/test_masked_finalize.py``):
#
# * zero-padded reductions: XLA:CPU/TPU reduce rows in order, and adding
#   exact zeros preserves every partial sum, so a masked row-sum over
#   ``n`` rows equals the unpadded sum over ``m``;
# * division by a traced count must be written ``x * (1.0 / m)``: XLA
#   rewrites the unpadded ``x / const`` into a reciprocal multiply, so a
#   literal traced division would round differently;
# * sorts pad with ``+inf`` (after every finite value, before NaN) and
#   read dynamic positions with masked positional sums or gathers;
# * selection ranks count only valid competitors
#   (:func:`_masked_nan_last_ranks`), reproducing the compacted matrix's
#   stable tie order exactly.
#
# Contract: ``x`` is floating (the fold states cast on ingest), invalid
# rows are finite (the fold buffers keep them zero), and the VALID rows
# are finite — a NaN/inf gradient sorts differently against the +inf
# padding than against real data, so ``Aggregator.fold_finalize_masked``
# detects non-finite cohorts and falls back to the exact subset path.
# ``masked_coordinate_median`` alone keeps exact NaN column semantics.
# ---------------------------------------------------------------------------


def _masked_count(valid: Array, dtype=jnp.int32) -> Array:
    """Number of valid rows ``m`` as a traced scalar."""
    return jnp.sum(valid.astype(dtype))


def _row_mean_einsum(x: Array) -> Array:
    """``jnp.mean(x, axis=0)`` as an einsum row contraction — the
    padding-stable reduction every masked mirror shares (see
    :func:`_windowed_row_mean`)."""
    ones = jnp.ones((x.shape[0],), x.dtype)
    return jnp.einsum("n,nd->d", ones, x) / x.shape[0]


def _masked_recip(count: Array, dtype) -> Array:
    """``1 / count`` as the same single-rounded reciprocal XLA's
    divide-by-constant rewrite produces for the unpadded program."""
    one = jnp.asarray(1.0, dtype)
    return one / count.astype(dtype)


def masked_mean(x: Array, valid: Array) -> Array:
    """Mean of the valid rows at the padded shape — bit-for-bit against
    :func:`_row_mean_einsum` on the compacted matrix."""
    m = _masked_count(valid)
    w = valid.astype(x.dtype)
    s = jnp.einsum("n,nd->d", w, jnp.where(valid[:, None], x, 0.0))
    return s * _masked_recip(m, s.dtype)


def _masked_sorted(x: Array, valid: Array) -> Array:
    """Sort columns with invalid rows replaced by ``+inf`` (they land
    after every finite valid value), via the same :func:`sort_rows` the
    unpadded coordinate-wise fallbacks use — sorted VALUES of the valid
    prefix are identical to sorting the compacted matrix."""
    filled = jnp.where(
        valid[:, None], x, jnp.asarray(jnp.inf, x.dtype)
    )
    return sort_rows(filled) if x.ndim == 2 else jnp.sort(filled, axis=0)


def _masked_rows_at(s: Array, pos: Array) -> Array:
    """Row of the sorted matrix at traced position ``pos`` (dynamic
    per-column gather; ``pos`` broadcasts over columns)."""
    idx = jnp.broadcast_to(pos, (1, s.shape[1]))
    return jnp.take_along_axis(s, idx, axis=0)[0]


def _masked_mid_rows(s: Array, m: Array) -> Tuple[Array, Array, Array]:
    """The two middle rows of a sorted matrix at traced count ``m``:
    ``(s[(m-1)//2], s[m//2], lo == hi)``. Shared by every masked median
    gather; the MIDPOINT rule stays at each call site on purpose — it
    must bit-match that site's unpadded mirror, and the mirrors differ
    (``jnp.median`` computes ``(a+b)*0.5``; ``_mean_of_medians_xla``
    deliberately uses ``a*0.5 + b*0.5`` against near-max overflow)."""
    lo, hi = (m - 1) // 2, m // 2
    return _masked_rows_at(s, lo), _masked_rows_at(s, hi), lo == hi


def masked_coordinate_median(x: Array, valid: Array) -> Array:
    """Coordinate-wise median of the valid rows (exact
    :func:`coordinate_median` semantics including column-wide NaN
    propagation), at the padded shape."""
    m = _masked_count(valid)
    s = _masked_sorted(x, valid)
    s_lo, s_hi, single = _masked_mid_rows(s, m)
    med = jnp.where(
        single, s_lo, (s_lo + s_hi) * jnp.asarray(0.5, s.dtype)
    )
    nan_col = jnp.any(jnp.isnan(x) & valid[:, None], axis=0)
    return jnp.where(nan_col, jnp.asarray(jnp.nan, s.dtype), med)


def masked_trimmed_mean(x: Array, valid: Array, *, f: int) -> Array:
    """f-trimmed coordinate mean of the valid rows — the masked mirror
    of :func:`_trimmed_mean_xla`, sharing its windowed einsum reduction
    with the cohort size traced (callers guarantee ``2f < m``)."""
    m = _masked_count(valid)
    s = _masked_sorted(x, valid)
    return _windowed_row_mean(s, m, f=f)


def masked_mean_of_medians(x: Array, valid: Array, *, f: int) -> Array:
    """MeaMed over the valid rows — the masked mirror of
    :func:`_mean_of_medians_xla`: the ``k = m - f`` values closest to
    the median per coordinate still form a contiguous window of the
    sorted column, and the number of candidate window STARTS is ``f+1``
    regardless of ``m``, so only the window END moves with the traced
    cohort size."""
    n, d = x.shape
    m = _masked_count(valid)
    k = m - f
    s = _masked_sorted(x, valid)
    s_lo, s_hi, single = _masked_mid_rows(s, m)
    half = jnp.asarray(0.5, s.dtype)
    med = jnp.where(single, s_lo, s_lo * half + s_hi * half)
    nan_col = jnp.any(jnp.isnan(x) & valid[:, None], axis=0)
    med = jnp.where(nan_col, jnp.asarray(jnp.nan, s.dtype), med)
    # window starts 0..f (static count); ends s + k - 1 (traced gather)
    starts = s[: f + 1]
    end_pos = jnp.arange(f + 1)[:, None] + (k - 1)
    ends = jnp.take_along_axis(s, jnp.broadcast_to(end_pos, (f + 1, d)), axis=0)
    radius = jnp.maximum(med[None, :] - starts, ends - med[None, :])
    dev = jnp.abs(x - med[None, :])
    finite_dev = jnp.where(jnp.isnan(dev) | ~valid[:, None], 0, 1)
    cut_nonfinite = jnp.where(
        jnp.sum(finite_dev, axis=0) >= k,
        jnp.asarray(jnp.inf, s.dtype),
        jnp.asarray(jnp.nan, s.dtype),
    )
    cut = jnp.where(
        jnp.isfinite(med), jnp.min(radius, axis=0), cut_nonfinite
    )
    below = (dev < cut[None, :]) & valid[:, None]
    at = (dev == cut[None, :]) & valid[:, None]
    quota = k - jnp.sum(below, axis=0)
    take_at = at & (jnp.cumsum(at, axis=0) <= quota[None, :])
    sel = jnp.where(below | take_at, x, jnp.zeros((), x.dtype))
    ones = jnp.ones((n,), x.dtype)
    out = jnp.einsum("n,nd->d", ones, sel) * _masked_recip(k, s.dtype)
    return jnp.where(jnp.isnan(cut), jnp.asarray(jnp.nan, s.dtype), out)


def _masked_nan_last_ranks(scores: Array, valid: Array) -> Array:
    """Selection rank counting only VALID competitors, under the same
    (isnan, score, index) key as :func:`_nan_last_ranks` — for valid
    rows this reproduces the compacted matrix's rank exactly (compaction
    preserves index order); invalid rows rank ``n`` and are never
    selected, whatever their score.

    O(n log n) four-key sort (invalid-last, then the shared key) + rank
    scatter, replacing the former O(n²) comparison matrix — see
    :func:`_nan_last_ranks` for the rationale and the identical-ranks
    argument; with invalid rows sorted after every valid one, a valid
    row's sorted position counts exactly its valid predecessors."""
    n = scores.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    isnan = jnp.isnan(scores)
    s = jnp.where(isnan, jnp.zeros_like(scores), scores)
    # -0.0 → +0.0 (see _nan_last_ranks: IEEE-== tie semantics, not the
    # sort's total order)
    s = jnp.where(s == 0, jnp.zeros_like(s), s)
    _, _, _, order = lax.sort(
        ((~valid).astype(jnp.int32), isnan.astype(jnp.int32), s, idx),
        num_keys=4,
    )
    pos = jnp.zeros((n,), jnp.int32).at[order].set(idx)
    return jnp.where(valid, pos, n)


def masked_selection_mean(
    x: Array, scores: Array, valid: Array, q: Array, any_bad: Array
) -> Array:
    """Mean of the ``q`` lowest-score VALID rows — the masked mirror of
    :func:`_selection_mean_xla`, sharing its contraction via
    :func:`_selected_rows_mean` (``q`` traced here)."""
    return _selected_rows_mean(
        x, _masked_nan_last_ranks(scores, valid) < q, q, any_bad
    )


def masked_krum_scores_from_gram(
    gram: Array, valid: Array, *, f: int
) -> Array:
    """Krum score per VALID row from the padded Gram matrix (zero
    rows/columns for absent slots): invalid columns are pushed to
    ``+inf`` before the row sort, so each valid row's sorted prefix
    matches the compacted matrix's, and the sum of its ``m - f - 1``
    nearest squared distances reads through a masked positional window
    instead of a static slice. Invalid rows score ``+inf``."""
    n = gram.shape[0]
    m = _masked_count(valid)
    norms = jnp.diagonal(gram)
    d2 = jnp.maximum(norms[:, None] + norms[None, :] - 2.0 * gram, 0.0)
    d2 = jnp.where(valid[None, :], d2, jnp.asarray(jnp.inf, d2.dtype))
    row_sorted = jnp.sort(d2, axis=1)
    pos = jnp.arange(n)[None, :]
    window = (pos >= 1) & (pos < m - f)
    kept = jnp.where(window, row_sorted, jnp.zeros((), d2.dtype))
    s = jnp.einsum("nk,k->n", kept, jnp.ones((n,), kept.dtype))
    return jnp.where(valid, s, jnp.asarray(jnp.inf, d2.dtype))


def masked_multi_krum(x: Array, valid: Array, *, f: int, q: int) -> Array:
    """Multi-Krum over the valid rows at the padded shape — the masked
    mirror of :func:`_multi_krum_xla` (callers guarantee ``f < m - 1``
    and ``q <= m - f``)."""
    gram = gram_matrix(x)
    scores = masked_krum_scores_from_gram(gram, valid, f=f)
    diag_ok = jnp.where(valid, jnp.isfinite(jnp.diagonal(gram)), True)
    any_bad = ~jnp.all(diag_ok)
    return masked_selection_mean(x, scores, valid, q, any_bad)


def masked_cge(x: Array, valid: Array, *, f: int) -> Array:
    """CGE over the valid rows at the padded shape — the masked mirror
    of :func:`_cge_xla`; the keep-count ``m - f`` is traced, so one
    program serves every cohort size in the bucket."""
    m = _masked_count(valid)
    norms = jnp.sum(x * x, axis=1)
    any_bad = ~jnp.all(jnp.where(valid, jnp.isfinite(norms), True))
    return masked_selection_mean(x, norms, valid, m - f, any_bad)


def masked_monna(
    x: Array, valid: Array, *, f: int, reference_index: int = 0
) -> Array:
    """MoNNA over the valid rows at the padded shape: the trusted
    reference is the ``reference_index``-th VALID row (matching the
    compacted matrix the unpadded :func:`_monna_xla` sees). Callers
    guarantee ``reference_index < m`` (``MoNNA.validate_n`` raises
    host-side; ``m`` is traced here, so the cumsum/argmax gather would
    otherwise silently fall back to slot 0 — an arbitrary, possibly
    Byzantine, row as the trusted node)."""
    m = _masked_count(valid)
    # slot holding the (reference_index+1)-th valid row
    ref_slot = jnp.argmax(jnp.cumsum(valid.astype(jnp.int32)) == reference_index + 1)
    ref = lax.dynamic_index_in_dim(x, ref_slot, axis=0, keepdims=False)
    diff = x - ref[None, :]
    dists = jnp.sum(diff * diff, axis=1)
    any_bad = ~jnp.all(jnp.where(valid, jnp.isfinite(dists), True))
    return masked_selection_mean(x, dists, valid, m - f, any_bad)


def _masked_median_rows(x: Array, valid: Array) -> Array:
    """``jnp.median(compacted, axis=0)`` at the padded shape (the
    iterative aggregators' ``init="median"`` center — no NaN column
    rewrite, mirroring ``jnp.median``)."""
    m = _masked_count(valid)
    s = jnp.sort(
        jnp.where(valid[:, None], x, jnp.asarray(jnp.inf, x.dtype)), axis=0
    )
    s_lo, s_hi, single = _masked_mid_rows(s, m)
    return jnp.where(
        single, s_lo, (s_lo + s_hi) * jnp.asarray(0.5, s.dtype)
    )


def masked_geometric_median(
    x: Array,
    valid: Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 256,
    eps: float = 1e-12,
    init: str = "median",
) -> Array:
    """Geometric median of the valid rows at the padded shape — the
    masked mirror of :func:`_geometric_median_impl` (XLA path): every
    per-row weight is zeroed for invalid slots, so each Weiszfeld step
    reproduces the compacted iteration bit-for-bit and the while-loop
    trip count matches."""
    if init not in {"median", "mean"}:
        raise ValueError("init must be 'median' or 'mean'")
    z0 = (
        _masked_median_rows(x, valid)
        if init == "median"
        else masked_mean(x, valid)
    )
    vcol = valid[:, None]

    def cond(state):
        z, zprev, it = state
        delta = jnp.sqrt(jnp.sum((z - zprev) ** 2))
        return ((it == 0) | (delta > tol)) & (it < max_iter)

    def body(state):
        z, _, it = state
        diff = x - z[None, :]
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
        w = jnp.where(valid, 1.0 / jnp.maximum(dist, eps), 0.0).astype(x.dtype)
        num = jnp.einsum("n,nd->d", w, x)
        den = jnp.einsum("n,n->", w, jnp.ones_like(w))
        z_new = num / den
        return z_new, z, it + 1

    z, _, _ = lax.while_loop(cond, body, (z0, z0, 0))
    return z


def masked_centered_clipping(
    x: Array,
    valid: Array,
    *,
    c_tau: float,
    M: int = 10,
    eps: float = 1e-12,
    init: str = "mean",
) -> Array:
    """Centered clipping of the valid rows at the padded shape — the
    masked mirror of :func:`_centered_clipping_impl` (XLA path)."""
    if init not in {"mean", "median", "zero"}:
        raise ValueError("init must be one of {'mean','median','zero'}")
    m = _masked_count(valid)
    if init == "mean":
        v0 = masked_mean(x, valid)
    elif init == "median":
        v0 = _masked_median_rows(x, valid)
    else:
        v0 = jnp.zeros((x.shape[1],), x.dtype)
    inv = _masked_recip(m, x.dtype)

    def body(_, v):
        diff = x - v[None, :]
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
        scale = jnp.minimum(1.0, c_tau / jnp.maximum(dist, eps))
        w = jnp.where(valid, scale, 0.0).astype(x.dtype)
        # invalid rows: diff = -v (finite), weight exactly 0
        step = jnp.einsum("n,nd->d", w, diff)
        return v + step * inv

    return lax.fori_loop(0, M, body, v0)


def aggregate_stream(agg_fn, xs: Array) -> Array:
    """Apply ``agg_fn`` to a stream of ``K`` stacked gradient matrices
    ``xs: (K, n, d)`` inside ONE compiled program (``lax.scan``), returning
    ``(K, d)`` aggregates.

    In a real training loop the aggregator runs once per round inside a
    compiled step; calling it as a standalone dispatch instead pays the
    host->device launch latency every round. Streaming K rounds per
    dispatch amortizes that, which is the shape for replaying buffered
    rounds (what a launch costs on the co-located chip is not measured
    yet — ROADMAP S1).
    """
    def body(carry, xi):
        return carry, agg_fn(xi)

    _, ys = lax.scan(body, None, xs)
    return ys


__all__ = [
    "gram_matrix",
    "pairwise_sq_dists",
    "sort_rows",
    "coordinate_median",
    "coordinate_median_stream",
    "trimmed_mean_stream",
    "mean_of_medians_stream",
    "trimmed_mean",
    "attacked_serves",
    "trimmed_mean_attacked",
    "coordinate_median_attacked",
    "mean_of_medians",
    "krum_scores",
    "ranked_mean",
    "multi_krum",
    "multi_krum_stream",
    "nnm_multi_krum",
    "nnm_multi_krum_stream",
    "clipped_multi_krum",
    "clipped_multi_krum_stream",
    "arc_multi_krum",
    "arc_multi_krum_stream",
    "krum",
    "geometric_median",
    "centered_clipping",
    "cge",
    "cge_stream",
    "monna",
    "monna_stream",
    "caf",
    "subset_diameters",
    "subset_max_eigvals",
    "subset_max_eigvals_jacobi",
    "subset_mean",
    "best_subset_by_score",
    "aggregate_stream",
    "extremes_fold_update",
    "extremes_fold_update_donated",
    "fold_add_donated",
    "gram_fold_update",
    "gram_block",
    "trimmed_mean_from_extremes",
    "krum_scores_from_gram",
    "multi_krum_from_gram",
    "masked_mean",
    "masked_coordinate_median",
    "masked_trimmed_mean",
    "masked_mean_of_medians",
    "masked_selection_mean",
    "masked_krum_scores_from_gram",
    "masked_multi_krum",
    "masked_cge",
    "masked_monna",
    "masked_geometric_median",
    "masked_centered_clipping",
]
