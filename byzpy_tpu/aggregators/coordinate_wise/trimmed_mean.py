"""Coordinate-wise trimmed mean (Yin et al. 2018)
(behavioral parity: ``byzpy/aggregators/coordinate_wise/trimmed_mean.py:27-211``).
"""

from __future__ import annotations

from typing import Any

import numpy as np
import jax.numpy as jnp

from ...ops import robust
from ...utils import placement
from ..base import Aggregator, SlotFoldState
from ..chunked import FeatureChunkedAggregator


def _trimmed_mean_chunk(chunk: np.ndarray, *, f: int) -> jnp.ndarray:
    return robust.trimmed_mean(jnp.asarray(chunk), f=f)


class _TrimmedMeanFoldState:
    """Incremental trimmed-mean state: running coordinate sum + folded
    ``f``-smallest/``f``-largest buffers (``ops.robust
    .extremes_fold_update``), so per-arrival work is O(f·d) and finalize
    is O(f·d) — the sort cost streams over the round. Raw rows are kept
    in a slot buffer as the exact fallback: a non-finite gradient (an
    adversary's NaN/inf) would corrupt the extreme buffers, so finalize
    detects it (one flag, no per-arrival host sync) and reruns the
    barrier-identical sorted path on the kept rows."""

    __slots__ = ("slots", "total", "low", "high", "nonfinite")

    def __init__(self, n: int) -> None:
        self.slots = SlotFoldState(n)
        self.total = None
        self.low = None
        self.high = None
        self.nonfinite = None


class CoordinateWiseTrimmedMean(FeatureChunkedAggregator, Aggregator):
    """Drop the f largest and f smallest values per coordinate, average the rest."""
    name = "coordinate-wise-trimmed-mean"
    _chunk_fn = staticmethod(_trimmed_mean_chunk)

    def __init__(self, f: int, *, chunk_size: int = 8192) -> None:
        if f < 0:
            raise ValueError("f must be >= 0")
        if chunk_size <= 0:
            raise ValueError("chunk_size must be > 0")
        self.f = int(f)
        self.chunk_size = int(chunk_size)

    def validate_n(self, n: int) -> None:
        if 2 * self.f >= n:
            raise ValueError(
                f"trim parameter f must satisfy 0 <= 2f < n (got n={n}, f={self.f})"
            )

    def _chunk_params(self):
        return {"f": self.f}

    supports_masked_finalize = True

    def _aggregate_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        return robust.trimmed_mean(x, f=self.f)

    def _aggregate_matrix_masked(
        self, x: jnp.ndarray, valid: jnp.ndarray
    ) -> jnp.ndarray:
        return robust.masked_trimmed_mean(x, valid, f=self.f)

    def _masked_view(self, state):
        # the incremental fold keeps raw rows in a slot buffer precisely
        # for exact fallbacks; the masked finalize reads the same buffer
        # (the base class's finite check then routes a NaN/inf round to
        # the exact sorted path, like the extremes fold does)
        return Aggregator._masked_view(self, state.slots)

    def _aggregate_stream_matrix(self, xs: jnp.ndarray) -> jnp.ndarray:
        return robust.trimmed_mean_stream(xs, f=self.f)

    def ragged_matrix_fn(self):
        """Ragged program with the sort strategy resolved HERE, before
        any trace (the PR-2 wrapper pattern): on TPU the specialized
        segmented program (ONE two-key sort serves every cohort in the
        batch, ``ops.ragged.ragged_trimmed_mean``); on the XLA
        fallback the per-cohort masked program — XLA:CPU's
        multi-operand ``lax.sort`` measured 3.4× the single-key sort
        at the same shape, so the shared sort loses there (and
        ``ragged_coalesce`` is False: one cohort per call, still ONE
        compiled program across every cohort size)."""
        from ...ops import ragged as ragged_ops
        from ...ops.pallas_kernels import targets_tpu

        f = self.f
        if not targets_tpu():
            return super().ragged_matrix_fn()

        def fn(flat, seg, offsets, lengths, *, n_cohorts, segment_sum=None):
            aggs = ragged_ops.ragged_trimmed_mean(
                flat, seg, offsets, lengths, f=f, n_cohorts=n_cohorts,
                segment_sum=segment_sum,
            )
            return aggs, None, None

        return fn

    #: Coordinate cap for the host-side clip-fraction evidence: past
    #: this, the per-coordinate rank pass samples an evenly-strided
    #: subset (evidence is a screening signal, not the aggregate).
    _EVIDENCE_MAX_COORDS = 65536

    def round_evidence(self, matrix, valid, *, aggregate=None):
        """Per-row clip counts: the fraction of a row's coordinates
        that fell in the trimmed ``f``-smallest/``f``-largest window
        (host-side ranks; stable order matches the sort the aggregate
        trims with). An honest row is clipped on ~``2f/m`` of
        coordinates by symmetry; a directional attacker concentrates
        near 1.0. No binary selection (``keep`` is None) — trimming is
        per-coordinate."""
        pre = self._evidence_rows(matrix, valid)
        if pre is None:
            return None
        rows, idx, n = pre
        m, d = rows.shape
        if self.f == 0:
            return self._evidence_view(
                "trim_fraction", n, idx, np.zeros((m,), np.float32)
            )
        cols = rows
        if d > self._EVIDENCE_MAX_COORDS:
            sample = np.linspace(
                0, d - 1, self._EVIDENCE_MAX_COORDS, dtype=np.int64
            )
            cols = rows[:, sample]
        order = np.argsort(cols, axis=0, kind="stable")
        ranks = np.empty_like(order)
        np.put_along_axis(
            ranks, order, np.arange(m, dtype=order.dtype)[:, None], axis=0
        )
        trimmed = (ranks < self.f) | (ranks >= m - self.f)
        frac = trimmed.mean(axis=1).astype(np.float32)
        return self._evidence_view("trim_fraction", n, idx, frac)

    # -- hierarchical partial fold (sharded serving tier) -----------------

    def _partial_extras(self, rows) -> dict:
        """Sublinear streaming summary of one shard's discounted rows:
        the running coordinate sum plus the ``f``-smallest/``f``-largest
        extreme buffers (±inf-padded below ``f`` rows, exactly like the
        streaming fold's init), and a finite flag. Extreme buffers merge
        EXACTLY across shards (order statistics of a multiset compose),
        so the root can maintain the same O(f·d) streaming state the
        overlapped fold keeps — and cross-check a shard's claim against
        the rows it shipped (deterministic recompute)."""
        d = rows.shape[1] if rows.ndim == 2 else 0
        extras: dict = {
            "total": rows.sum(axis=0, dtype=np.float32),
            "finite": bool(np.isfinite(rows).all()),
        }
        if self.f > 0:
            lo_pad = np.full((self.f, d), np.inf, np.float32)
            hi_pad = np.full((self.f, d), -np.inf, np.float32)
            extras["low"] = np.sort(
                np.concatenate([rows, lo_pad], axis=0), axis=0
            )[: self.f]
            extras["high"] = np.sort(
                np.concatenate([rows, hi_pad], axis=0), axis=0
            )[-self.f:]
        return extras

    def _merge_extras(self, extras_list, partials) -> dict:
        """Exact root merge: totals left-fold in shard order; the
        merged extreme buffers are the per-coordinate ``f`` smallest/
        largest of the concatenated shard buffers — bit-equal to the
        extremes of the full concatenated cohort (multiset order
        statistics). A shard that shipped no extras has them recomputed
        from its rows (extras are deterministic summaries)."""
        import functools

        fixed = [
            e if e else self._partial_extras(
                np.asarray(p["rows"], np.float32)
            )
            for e, p in zip(extras_list, partials, strict=True)
        ]
        merged: dict = {
            "total": functools.reduce(
                np.add, [np.asarray(e["total"], np.float32) for e in fixed]
            ),
            "finite": all(bool(e.get("finite", True)) for e in fixed),
        }
        if self.f > 0:
            merged["low"] = np.sort(
                np.concatenate([e["low"] for e in fixed], axis=0), axis=0
            )[: self.f]
            merged["high"] = np.sort(
                np.concatenate([e["high"] for e in fixed], axis=0), axis=0
            )[-self.f:]
        return merged

    # -- arrival-order streaming fold ------------------------------------

    def fold_init(self, n: int) -> Any:
        return _TrimmedMeanFoldState(n)

    def fold(self, state: Any, index: int, gradient: Any) -> None:
        row = state.slots.insert(index, gradient)
        f = self.f
        with placement.on(placement.compute_device(row)):
            if state.total is None:
                # a COPY, not `row` itself: the donated add below deletes
                # its first argument, and `row` is shared with the slot
                # buffer the exact fallback reads
                state.total = jnp.array(row, copy=True)
            else:
                state.total = robust.fold_add_donated(state.total, row)
            bad = ~jnp.all(jnp.isfinite(row))
            state.nonfinite = (
                bad if state.nonfinite is None else state.nonfinite | bad
            )
            if f > 0:
                if state.low is None:
                    d = row.shape[0]
                    state.low = jnp.full((f, d), jnp.inf, row.dtype)
                    state.high = jnp.full((f, d), -jnp.inf, row.dtype)
                state.low = robust.extremes_fold_update_donated(
                    state.low, row, largest=False
                )
                state.high = robust.extremes_fold_update_donated(
                    state.high, row, largest=True
                )

    def fold_finalize(self, state: Any) -> Any:
        n = state.slots.filled
        self.validate_n(n)
        if state.nonfinite is None or bool(state.nonfinite):
            # exact sorted path on the kept rows (matches the barrier's
            # NaN-propagation / inf-trimming semantics bit for bit)
            return Aggregator.fold_finalize(self, state.slots)
        with placement.on(
            placement.compute_device(state.slots.placement_source())
        ):
            vec = robust.trimmed_mean_from_extremes(
                state.total, state.low, state.high, n, f=self.f
            )
            return state.slots.unravel(vec)


__all__ = ["CoordinateWiseTrimmedMean"]
