"""Aggregator base class (API parity: ``byzpy/aggregators/base.py:11-103``).

An aggregator reduces a sequence of per-node gradients (pytrees, arrays, or
an already-stacked ``(n, d)`` matrix) to a single aggregated gradient with
the structure of one input. Subclasses implement ``_aggregate_matrix`` — a
pure function on the stacked matrix that jit-compiles and shards over a
device mesh (see ``byzpy_tpu.ops.robust``).

Unlike the reference, parallelism does not require host-side chunking: the
matrix computation is one XLA program. Chunked ``create_subtasks`` paths are
still provided by the mixins in ``chunked.py`` for running on heterogeneous
actor pools (the reference's shm-chunk pattern, minus the shm).
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from functools import partial
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

from ..engine.graph.operator import OpContext, Operator
from ..utils import placement
from ..utils.trees import stack_gradients


@partial(jax.jit, donate_argnums=(0,))
def _slot_insert(buffer: jnp.ndarray, row: jnp.ndarray, index) -> jnp.ndarray:
    """Park one flattened gradient in its canonical slot of the ``(n, d)``
    ingest buffer, IN PLACE: the buffer is donated, so XLA reuses the
    allocation instead of copying the whole matrix per arrival. This is
    what makes finalize's "stack" free — the matrix already exists."""
    return lax.dynamic_update_slice(buffer, row[None, :], (index, 0))


def ravel_gradient(gradient: Any) -> tuple:
    """Flatten one gradient pytree/array to a ``(d,)`` row the way
    :func:`~byzpy_tpu.utils.trees.stack_gradients` would, deciding host/
    device placement from this gradient alone (streaming ingestion sees
    one gradient at a time; the barrier path decides from the full
    list). Returns ``(row, unravel)``."""
    with placement.on(placement.compute_device(gradient)):
        row, unravel = ravel_pytree(gradient)
        if not jnp.issubdtype(row.dtype, jnp.floating):
            row = row.astype(jnp.float32)
    return row, unravel


#: Marker stored in ``SlotFoldState.rows`` for a slot whose gradient
#: lives in the donated ingest buffer (the row reference itself is
#: dropped so fold-state memory stays ~1x the matrix, not 2x).
_STAGED = object()


class SlotFoldState:
    """Default streaming-fold state: an arrival-order ingestion buffer.

    Each gradient is flattened the moment it arrives (``fold``) and
    parked in its canonical node slot; ``fold_finalize`` stacks the
    filled slots *in slot order* and runs the normal matrix aggregate.
    Because the stacked matrix is identical to the barrier path's —
    same per-row flatten, same order — the result is bit-identical for
    every aggregator, regardless of arrival order.

    Ingestion is donated: every arrival lands in a preallocated
    ``(n, d)`` device buffer through an in-place dynamic-update-slice
    (:func:`_slot_insert`) and the per-row reference is dropped
    (``rows`` keeps a :data:`_STAGED` marker), so the per-gradient host
    work (pytree ravel, dtype cast, placement) AND the matrix assembly
    bytes all happen inside the straggler window at ~1x the matrix's
    memory — a full round's finalize reads the already-built matrix
    with zero copies where the barrier path pays an n·d stack after
    the last straggler. A mixed-dtype round (rare) falls back to real
    row references + a finalize stack, rebuilding the already-staged
    rows from the buffer.
    """

    __slots__ = ("n", "rows", "unravel", "dim", "filled", "buffer")

    def __init__(self, n: int) -> None:
        # the one capacity guard for every fold state (the incremental
        # folds all embed a slot buffer)
        if n <= 0:
            raise ValueError(f"fold_init needs n >= 1 (got {n})")
        self.n = n
        self.rows: list = [None] * n
        self.unravel: Optional[Callable[[jnp.ndarray], Any]] = None
        self.dim: Optional[int] = None
        self.filled = 0
        #: donated (n, d) ingest buffer; None until the first row, or
        #: permanently None after a dtype mismatch (stack fallback)
        self.buffer: Optional[jnp.ndarray] = None

    def insert(self, index: int, gradient: Any) -> jnp.ndarray:
        """Flatten ``gradient`` into slot ``index``; returns the row."""
        if not 0 <= index < self.n:
            raise IndexError(f"slot {index} outside [0, {self.n})")
        if self.rows[index] is not None:
            raise ValueError(f"slot {index} folded twice")
        row, unravel = ravel_gradient(gradient)
        if self.dim is None:
            self.dim = int(row.shape[0])
            self.unravel = unravel
        elif int(row.shape[0]) != self.dim:
            raise ValueError(
                f"all gradients must flatten to the same length "
                f"(got {row.shape[0]} != {self.dim})"
            )
        with placement.on(placement.compute_device(row)):
            if self.filled == 0:
                self.buffer = jnp.zeros((self.n, self.dim), row.dtype)
            if self.buffer is not None and row.dtype == self.buffer.dtype:
                self.buffer = _slot_insert(self.buffer, row, index)
                self.rows[index] = _STAGED
            else:
                if self.buffer is not None:
                    # mixed dtypes: rebuild real references for the
                    # already-staged slots (buffer rows ARE the exact
                    # values), then stack at finalize
                    for i, r in enumerate(self.rows):
                        if r is _STAGED:
                            self.rows[i] = self.buffer[i]
                    self.buffer = None
                self.rows[index] = row
        self.filled += 1
        return row

    def placement_source(self) -> Any:
        """The value placement decisions should inspect: the ingest
        buffer when staging is active, else the held rows."""
        return self.buffer if self.buffer is not None else self.rows

    def stacked(self) -> tuple:
        """``(matrix, unravel)`` over the filled slots, in slot order.
        A complete round returns the donated ingest buffer directly
        (bit-identical to the stack — the buffer holds the exact rows);
        partial rounds gather the filled slots from it (same values);
        the mixed-dtype fallback stacks the held rows."""
        if self.filled == 0:
            raise ValueError("fold_finalize before any gradient was folded")
        if self.buffer is not None:
            if self.filled == self.n:
                return self.buffer, self.unravel
            idx = jnp.asarray(
                [i for i, r in enumerate(self.rows) if r is not None],
                jnp.int32,
            )
            return self.buffer[idx], self.unravel
        return (
            jnp.stack([r for r in self.rows if r is not None], axis=0),
            self.unravel,
        )


class Aggregator(Operator, ABC):
    """Robust gradient aggregator ABC: subclasses map an (n, d) stack of per-node gradients to one (d,) vector via ``aggregate`` / ``aggregate_stream``, and schedule on graphs/pools as Operators."""

    name = "aggregator"
    input_key = "gradients"

    #: Arrival-order streaming capability: when True the orchestrators
    #: may feed gradients through ``fold``/``fold_finalize`` as they
    #: land instead of barriering on the full list. The base
    #: implementation (slot buffer + canonical-order stack) is
    #: bit-identical to ``aggregate`` for any subclass; subclasses with
    #: genuinely incremental math (running sums, extreme buffers, Gram
    #: rows) override the hooks. Set False to force the barrier path.
    supports_streaming: bool = True

    def compute(self, inputs: Mapping[str, Any], *, context: OpContext) -> Any:
        if self.input_key not in inputs:
            raise KeyError(f"{self.name} expects input key {self.input_key!r}")
        gradients = inputs[self.input_key]
        if not isinstance(gradients, Sequence) and not hasattr(gradients, "ndim"):
            raise TypeError(f"{self.name} expects a sequence at {self.input_key!r}")
        return self.aggregate(gradients)

    def aggregate(self, gradients: Sequence[Any]) -> Any:
        """Reduce a sequence of gradients to one aggregated gradient.

        Placement: small host-resident inputs (actor-mode nodes hand over
        numpy arrays) run on the CPU backend instead of paying a
        host->accelerator round-trip; see ``utils.placement``.
        """
        with placement.on(placement.compute_device(gradients)):
            matrix, unravel = stack_gradients(gradients)
            self.validate_n(matrix.shape[0])
            return unravel(self._aggregate_matrix(matrix))

    def aggregate_stream(self, rounds: Sequence[Sequence[Any]]) -> list:
        """Aggregate ``K`` buffered rounds in ONE device dispatch.

        ``rounds``: K sequences of per-node gradients (same structure per
        round). Every dispatch costs a launch and a sync, so replay/
        buffered-round aggregation should batch: subclasses whose math has
        a fused stream kernel (Multi-Krum, CW median, ...) override
        ``_aggregate_stream_matrix``; the default runs the per-round
        matrix function under ``lax.scan``
        (``ops.robust.aggregate_stream``)."""
        if not rounds:
            return []
        with placement.on(placement.compute_device(rounds)):
            stacked = []
            unravel = None
            for grads in rounds:
                matrix, unravel = stack_gradients(grads)
                self.validate_n(matrix.shape[0])
                stacked.append(matrix)
            xs = jnp.stack(stacked)
            ys = self._aggregate_stream_matrix(xs)
            return [unravel(ys[i]) for i in range(ys.shape[0])]

    def _aggregate_stream_matrix(self, xs: jnp.ndarray) -> jnp.ndarray:
        """Aggregate stacked rounds ``(K, n, d)`` to ``(K, d)``."""
        from ..ops import robust

        return robust.aggregate_stream(self._aggregate_matrix, xs)

    # -- arrival-order streaming (overlapped rounds) ----------------------

    def fold_init(self, n: int) -> Any:
        """Create streaming-fold state for up to ``n`` gradients.

        Slots are canonical node positions (honest nodes first, then
        byzantine, matching the barrier path's list order), NOT arrival
        ranks — finalize reassembles canonical order so selection tie
        rules see the same row indices as ``aggregate``.
        """
        return SlotFoldState(n)

    def fold(self, state: Any, index: int, gradient: Any) -> None:
        """Ingest one gradient the moment it arrives (slot ``index``)."""
        state.insert(index, gradient)

    def fold_finalize(self, state: Any) -> Any:
        """Finish the round: aggregate everything folded so far.

        The default stacks the filled slots in canonical order and runs
        ``_aggregate_matrix`` — bit-identical to ``aggregate`` on the
        same gradients in slot order, for any arrival order.
        """
        with placement.on(placement.compute_device(state.placement_source())):
            matrix, unravel = state.stacked()
            self.validate_n(matrix.shape[0])
            return unravel(self._aggregate_matrix(matrix))

    # -- masked / ragged finalize (serving-tier bucketed cohorts) ---------

    #: True when the subclass ships a masked matrix program
    #: (``_aggregate_matrix_masked``): a fold declared for bucket size
    #: ``n`` can then finalize an actual cohort of ``m <= n`` rows at the
    #: BUCKET's compiled shape via a validity mask — one jit cache entry
    #: per bucket instead of one per distinct cohort size. Subclasses
    #: without one (subset-enumeration aggregators, whose combination
    #: count is a function of ``m``) fall back to the exact-subset
    #: ``fold_finalize`` path.
    supports_masked_finalize: bool = False

    def _aggregate_matrix_masked(
        self, x: jnp.ndarray, valid: jnp.ndarray
    ) -> jnp.ndarray:
        """Aggregate the VALID rows of the padded ``(n, d)`` matrix to a
        ``(d,)`` vector — exact size-``m`` semantics at the bucket shape
        (``m`` traced; see ``ops.robust`` masked section). Only called
        when :attr:`supports_masked_finalize` is True."""
        raise NotImplementedError(
            f"{type(self).__name__} has no masked matrix program"
        )

    def masked_matrix_fn(self) -> Optional[Callable]:
        """The bare masked ``(matrix, valid) -> vector`` function for
        embedding in jitted bucketed steps (serving parameter server),
        or ``None`` when the aggregator has no masked program."""
        if not self.supports_masked_finalize:
            return None
        return self._aggregate_matrix_masked

    def _masked_view(self, state: Any) -> Optional[tuple]:
        """``(buffer, valid_rows, unravel)`` exposing the fold state's
        padded ingest buffer for a masked finalize, or ``None`` when the
        state cannot provide one (mixed-dtype fallback, custom states).
        ``valid_rows`` is a host-side list/array of booleans per slot."""
        if isinstance(state, SlotFoldState) and state.buffer is not None:
            return (
                state.buffer,
                [r is not None for r in state.rows],
                state.unravel,
            )
        return None

    def _masked_jitted(self) -> Callable:
        fn = getattr(self, "_masked_jit_cache", None)
        if fn is None:
            fn = jax.jit(self._aggregate_matrix_masked)
            self._masked_jit_cache = fn
        return fn

    def _masked_jitted_donated(self) -> Callable:
        """The masked program as a PERSISTENT donated-buffer jit: the
        padded ``(bucket, d)`` matrix argument is donated, so a root
        that finalizes every round at a small set of ladder bucket
        shapes reuses one device allocation per bucket instead of
        paying an alloc + copy per close (jit's shape-keyed cache IS
        the per-bucket program table). Donation is an accelerator
        feature — on the CPU backend XLA ignores donations (with a
        warning), so this resolves to the plain :meth:`_masked_jitted`
        program there: same bits either way, the donated path only
        changes buffer reuse."""
        fn = getattr(self, "_masked_donated_jit_cache", None)
        if fn is None:
            if jax.default_backend() == "cpu":
                fn = self._masked_jitted()
            else:
                fn = jax.jit(
                    self._aggregate_matrix_masked, donate_argnums=(0,)
                )
            self._masked_donated_jit_cache = fn
        return fn

    def aggregate_masked(self, matrix: Any, valid: Any) -> jnp.ndarray:
        """Exact aggregate of the VALID rows of an already-padded
        ``(n, d)`` matrix, at the padded shape — the batch door into the
        same masked program (and per-bucket jit cache) that
        :meth:`fold_finalize_masked` uses, for callers that assembled
        the padded cohort in one pass (the serving front end) instead of
        folding rows as they arrived. Semantics match ``aggregate`` on
        the valid rows bit-for-bit (f32): finite cohorts run the masked
        program; non-finite cohorts — and aggregators without a masked
        program — take the exact compacted-subset path."""
        import numpy as np

        valid_rows = [bool(v) for v in np.asarray(valid)]
        m = sum(valid_rows)
        if m == 0:
            # validate_n is a no-op for f=0 aggregators (e.g. median),
            # and the masked programs' (m-1)//2-style gathers would wrap
            # to a padding row — garbage, not an error — on m=0
            raise ValueError("aggregate_masked requires at least one valid row")
        self.validate_n(m)
        if isinstance(matrix, np.ndarray):
            finite = bool(np.isfinite(matrix).all())
        else:
            finite = bool(jnp.all(jnp.isfinite(matrix)))
        if self.supports_masked_finalize and finite:
            return self._masked_jitted()(
                jnp.asarray(matrix), jnp.asarray(valid_rows, bool)
            )
        rows = [matrix[i] for i, v in enumerate(valid_rows) if v]
        return self.aggregate(rows)

    def fold_finalize_masked(self, state: Any) -> Any:
        """Finish a round at the BUCKET's compiled shape: aggregate the
        ``m`` folded gradients of a fold declared for ``n >= m`` slots
        through the masked matrix program, keeping the ``(n, d)`` jit
        cache entry warm for every cohort size in the bucket. Exact: the
        result is bit-identical (f32) to ``aggregate`` on the same ``m``
        gradients. Falls back to :meth:`fold_finalize` (the exact-subset
        path, which compiles per distinct ``m``) when the subclass has
        no masked program, the state exposes no padded buffer, or the
        cohort contains non-finite values (adversarial NaN/inf rows sort
        differently against the mask padding — the fallback preserves
        the barrier path's exact non-finite semantics)."""
        view = None
        if self.supports_masked_finalize:
            view = self._masked_view(state)
        if view is None:
            return self.fold_finalize(state)
        buffer, valid_rows, unravel = view
        m = sum(bool(v) for v in valid_rows)
        if m == 0:
            raise ValueError("fold_finalize before any gradient was folded")
        self.validate_n(m)
        with placement.on(placement.compute_device(buffer)):
            # invalid rows are zero (finite) in every fold buffer, so one
            # all-reduce answers "is the cohort finite" — the only case
            # the masked programs do not reproduce bit-for-bit
            if not bool(jnp.all(jnp.isfinite(buffer))):
                return self.fold_finalize(state)
            valid = jnp.asarray(valid_rows, bool)
            return unravel(self._masked_jitted()(buffer, valid))

    # -- ragged multi-cohort aggregation (serving-tier flat batches) ------

    #: Score family published by :meth:`ragged_matrix_fn`'s fused
    #: evidence outputs ("" = the ragged program publishes no per-row
    #: scores; the forensics plane then falls back to the host
    #: :meth:`round_evidence` pass).
    ragged_score_kind: str = ""

    #: Whether multiple cohorts should COALESCE into one ragged device
    #: call for this aggregator on the XLA fallback. True only where
    #: the ragged program genuinely shares work across the batch (the
    #: selection families: ONE Gram / norm pass scores every cohort —
    #: measured cheaper than separate dispatches). Sort-based
    #: coordinate-wise programs share nothing on XLA and sorting the
    #: union is superlinear in rows, so they serve one cohort per call
    #: — still through ONE compiled program (the ladder kill is
    #: independent of coalescing). The Pallas path batches everything
    #: with fill-skip; what it does on a chip is not measured (ROADMAP S4).
    ragged_coalesce: bool = False

    @property
    def supports_ragged(self) -> bool:
        """True when this aggregator can serve the flat-rows ragged
        door (``ops.ragged``): any aggregator with a masked program
        can — the generic per-cohort masked loop is always available —
        while the hot families override :meth:`ragged_matrix_fn` with
        programs that share the segmented sort / Gram / norm pass
        across the whole batch."""
        return self.supports_masked_finalize

    def ragged_group_key(self) -> tuple:
        """Hashable compatibility key for cross-tenant batching: two
        tenants' cohorts may share one ragged device call only when
        their aggregators trace the SAME program (same class, same
        static hyperparameters). The gradient dimension joins the key
        at the dispatcher (it is a property of the arrays, not the
        aggregator)."""
        statics = tuple(
            sorted(
                (k, v)
                for k, v in vars(self).items()
                if isinstance(v, (int, float, str, bool))
            )
        )
        return (type(self).__qualname__, statics)

    def ragged_matrix_fn(self) -> Optional[Callable]:
        """The bare ragged multi-cohort program ``(flat, seg, offsets,
        lengths, *, n_cohorts, segment_sum=None) -> (aggregates,
        score, keep)`` for embedding in one jitted batch dispatch
        (``serving.ragged``), or ``None`` when the aggregator has no
        masked program. Pure and trace-safe — no dispatch reads; the
        caller resolves Pallas/tile pre-trace and passes
        ``segment_sum``. The default reuses the masked program per
        cohort (single compile / single dispatch, no shared work, no
        fused evidence); subclasses with specialized ragged kernels
        override. Results are bit-identical per cohort to the unpadded
        ``aggregate`` under the masked contract's preconditions
        (finite rows, admissible ``m`` — the serving door enforces
        both)."""
        if not self.supports_masked_finalize:
            return None
        masked = self._aggregate_matrix_masked

        def generic(flat, seg, offsets, lengths, *, n_cohorts,
                    segment_sum=None):
            from ..ops import ragged as ragged_ops

            aggs = ragged_ops.ragged_via_masked(
                masked, flat, seg, n_cohorts=n_cohorts
            )
            return aggs, None, None

        return generic

    # -- hierarchical partial folds (sharded serving tier) -----------------

    #: Every aggregator can serve the hierarchical two-level fold
    #: (``serving.sharded``): the default partial carries one shard's
    #: compacted, staleness-discounted rows and the merged finalize runs
    #: the SAME masked door the single frontend uses — bit-identical to
    #: the single-frontend aggregate by the masked-finalize contract.
    #: Streaming families additionally attach their sublinear fold
    #: accumulators (:meth:`_partial_extras`): trimmed-mean running sum
    #: + extreme buffers, Multi-Krum's local Gram block, CGE's squared
    #: norms — merged exactly at the root (order-stat merge, cross-block
    #: Gram assembly, concatenation) and reused for the root's
    #: forensics score view (:meth:`merged_score_view`) and the
    #: compromised-shard consistency cross-check (extras are
    #: deterministic functions of the rows they summarize).
    @property
    def supports_fold_merge(self) -> bool:
        """Whether :meth:`fold_partial`/:meth:`fold_merge`/
        :meth:`fold_merge_finalize` are available (always True: the
        row-carrying default is universal — aggregators without a
        masked program finalize through the exact-subset door)."""
        return True

    def fold_partial(
        self, matrix: Any, valid: Any, weights: Any = None
    ) -> dict:
        """Extract one shard's wire-compact partial fold from its local
        cohort: ``{"rows": (m, d) float32, "m": int[, "extras": ...]}``.

        ``rows`` are the VALID rows of the padded ``matrix`` in
        admission (slot) order, scaled by their staleness ``weights``
        when any differ from 1.0 — elementwise, so scaling per shard is
        bit-identical to scaling the concatenated cohort. ``extras``
        (streaming families) are the sublinear fold accumulators
        computed from those discounted rows."""
        import numpy as np

        valid_arr = np.asarray(valid, bool)
        rows = np.ascontiguousarray(
            np.asarray(matrix, np.float32)[valid_arr]
        )
        if weights is not None and rows.shape[0]:
            w = np.asarray(weights, np.float32)[valid_arr]
            if bool((w != 1.0).any()):
                rows = rows * w[:, None]
        partial: dict = {"rows": rows, "m": int(rows.shape[0])}
        extras = self._partial_extras(rows)
        if extras:
            partial["extras"] = extras
        return partial

    def _partial_extras(self, rows: Any) -> dict:
        """Family-specific sublinear fold accumulators over one shard's
        discounted rows (empty for aggregators whose fold state is the
        rows themselves). Must be a DETERMINISTIC function of ``rows``
        — the sharded tier's root recomputes it to cross-check a
        shard's claimed extras against the rows it shipped."""
        return {}

    def fold_merge(self, partials: Sequence[Mapping[str, Any]]) -> dict:
        """Merge shard partials, IN SHARD ORDER, into one root fold
        state: ``{"rows": (Σm, d), "m": int, "offsets": per-shard row
        starts[, "extras": merged accumulators]}``. Row order is the
        canonical sharded cohort order (shard index, then admission
        order within the shard) — the order the single-frontend parity
        reference uses."""
        import numpy as np

        mats = [np.asarray(p["rows"], np.float32) for p in partials]
        if not mats:
            raise ValueError("fold_merge needs at least one partial")
        dims = {m.shape[1] for m in mats if m.ndim == 2}
        if len(dims) > 1:
            raise ValueError(
                f"partials disagree on gradient dimension: {sorted(dims)}"
            )
        rows = np.concatenate(mats, axis=0)
        offsets = np.cumsum([0] + [m.shape[0] for m in mats])[:-1]
        merged: dict = {
            "rows": rows,
            "m": int(rows.shape[0]),
            "offsets": [int(o) for o in offsets],
        }
        extras_list = [p.get("extras") for p in partials]
        if any(e for e in extras_list):
            merged["extras"] = self._merge_extras(extras_list, partials)
        return merged

    def _merge_extras(
        self,
        extras_list: Sequence[Optional[Mapping[str, Any]]],
        partials: Sequence[Mapping[str, Any]],
    ) -> dict:
        """Merge the shards' sublinear accumulators (family-specific;
        the base class carries none)."""
        return {}

    # -- combined-frame extras (merge-tree internal nodes) -----------------

    def combined_extras(
        self,
        children: Sequence[Tuple[Tuple[Tuple[int, int, int], ...], Any,
                                 Optional[Mapping[str, Any]]]],
    ) -> dict:
        """Extras for a COMBINED partial (a merge-tree internal node)
        from its children's ``(leaf segment spans, rows, extras)``
        triples, in shard order. The default is the full recompute over
        the concatenated rows — exactly what ``combine_partials`` did
        before the incremental assembly landed, and exactly what the
        default :meth:`segmented_extras_reference` recomputes, so the
        parent's ``extras_policy='verify'`` cross-check stays an exact
        bit comparison. Families whose extras admit cheaper blockwise
        assembly (Multi-Krum's Gram) override BOTH methods with the
        same block program (:func:`ops.robust.gram_block`) — the
        block-contraction contract."""
        import numpy as np

        if not any(e for _sp, _r, e in children):
            return {}
        rows = np.concatenate(
            [np.asarray(r, np.float32) for _sp, r, _e in children], axis=0
        )
        return self._partial_extras(rows)

    def segmented_extras_reference(
        self, rows: Any, spans: Sequence[Tuple[int, int, int]]
    ) -> dict:
        """The VERIFIER's recompute program for a segmented (combined)
        frame's extras — the other half of the block-contraction
        contract: whatever block structure :meth:`combined_extras`
        assembled, this method must reproduce from the frame's rows and
        ``(shard, row_lo, row_hi)`` spans with the SAME per-block dot
        program, so ``extras_policy='verify'`` compares exact bits.
        Default: the flat :meth:`_partial_extras` recompute (matches
        the default :meth:`combined_extras`)."""
        import numpy as np

        return self._partial_extras(np.asarray(rows, np.float32))

    # -- incremental (arrival-order) merge accumulator ---------------------

    def fold_merge_begin(self) -> dict:
        """Open an incremental merge accumulator for a STREAMING root:
        verified shard partials are parked as they arrive — in any
        order — and :meth:`fold_merge_finish` concatenates them in
        canonical shard order. The accumulator exists so an
        arrival-driven close can absorb each partial the moment its
        verification lands while keeping the published aggregate
        BIT-IDENTICAL to the barrier ``fold_merge`` of the same
        partials sorted by shard (pinned by
        ``tests/test_streaming_root.py``)."""
        return {"parked": {}}

    def fold_merge_add(
        self, state: dict, shard: int, partial: Mapping[str, Any]
    ) -> None:
        """Park one verified partial under its (unique) shard key.
        Arrival order is deliberately irrelevant — the canonical row
        order is re-established at :meth:`fold_merge_finish`, so an
        out-of-order arrival never has to wait for its predecessor.

        This is also the accumulator's ARRIVAL-TRANSFORM hook: a family
        whose extras merge needs per-partial heavy work (Multi-Krum's
        cross-Gram blocks against the partials already parked) does it
        HERE, on the arrival thread, so :meth:`fold_merge_finish` keeps
        only the cheap sorted-shard-order reduction — the close-path
        paydown. Overrides count their work into the state
        (``cross_blocks``/``transforms``) and surface it as
        ``merged["merge_stats"]`` at finish, which the sharded root
        folds into its ``gram_cross_blocks``/``partial_transforms``
        counters (the zero-redundant-recompute assert reads them)."""
        key = int(shard)
        if key in state["parked"]:
            raise ValueError(f"shard {key} already parked in this merge")
        state["parked"][key] = partial

    def fold_merge_finish(self, state: dict) -> dict:
        """Close the accumulator: merge the parked partials in shard
        order through :meth:`fold_merge` — the exact call the barrier
        close makes, so streaming-then-finish is bit-identical to
        gather-all-then-merge by construction."""
        parked = state["parked"]
        if not parked:
            raise ValueError("fold_merge_finish on an empty accumulator")
        return self.fold_merge([parked[s] for s in sorted(parked)])

    def fold_merge_finalize(
        self,
        merged: Mapping[str, Any],
        *,
        bucket: Optional[int] = None,
        donate: bool = False,
    ) -> jnp.ndarray:
        """Finalize a merged root fold to the ``(d,)`` aggregate —
        BIT-IDENTICAL (f32, finite cohorts) to the single-frontend
        aggregate of the concatenated cohort: the merged rows run
        through the same :meth:`aggregate_masked` door (same masked
        program, same jit cache, same exact-subset and non-finite
        fallbacks) the one-frontend serving path uses. ``bucket``
        (optional, ≥ the merged row count) zero-pads the merged matrix
        to a ladder shape first, so a root serving many distinct merged
        sizes keeps one compiled program per bucket instead of one per
        size — exactness is the masked contract's padding invariance.

        Merged cohorts reach 10⁴–10⁵ rows, so the host-side gates run
        once over the COMPACT rows (the padding is zeros this method
        wrote itself): one f64 sum screens finiteness in a single pass
        (a sum stays finite iff every addend is — an inf never cancels
        without producing NaN first), and the masked program is invoked
        directly — the same per-aggregator jit cache and bit semantics
        as :meth:`aggregate_masked`, minus its full padded-matrix
        ``isfinite`` rescan.

        ``donate=True`` runs the OFF-PATH finalize variant: the same
        masked program through the persistent donated-buffer jit
        (:meth:`_masked_jitted_donated`, keyed by bucket shape), and
        the call returns the UNMATERIALIZED device array the moment the
        program is dispatched — the root kicks the device step the
        instant the last partial settles and overlaps its host-side
        score view with the device work, materializing (``np.asarray``)
        only when the digest needs the bits. Bit-identical to the
        synchronous path: same program, same inputs."""
        import numpy as np

        rows = np.ascontiguousarray(np.asarray(merged["rows"], np.float32))
        m = int(rows.shape[0])
        if m == 0:
            raise ValueError("fold_merge_finalize on an empty merge")
        self.validate_n(m)
        finite = bool(np.isfinite(rows.sum(dtype=np.float64)))
        if not (self.supports_masked_finalize and finite):
            # the exact compacted-subset path aggregate_masked would
            # take for the same inputs (non-finite cohorts, families
            # without a masked program)
            return self.aggregate(list(rows))
        if bucket is not None and bucket > m:
            padded = np.zeros((bucket, rows.shape[1]), np.float32)
            padded[:m] = rows
            valid = np.zeros((bucket,), bool)
            valid[:m] = True
        else:
            padded = rows
            valid = np.ones((m,), bool)
        fn = self._masked_jitted_donated() if donate else self._masked_jitted()
        return fn(jnp.asarray(padded), jnp.asarray(valid))

    #: True when :meth:`merged_score_view` reads ONLY the merged fold
    #: state (rows + published extras) whenever extras are present —
    #: i.e. it never needs the round ``aggregate``. The root's
    #: off-path finalize overlaps the host score pass with the device
    #: program ONLY for such families (the view runs between the
    #: device dispatch and its materialization; a view that wants the
    #: aggregate would force the materialization first and the overlap
    #: would be a lie).
    merged_view_from_extras: bool = False

    def merged_score_view(
        self, merged: Mapping[str, Any], *, aggregate: Any = None
    ) -> Optional[dict]:
        """Per-row ``{"kind", "scores", "keep"}`` view of the MERGED
        cohort for the root's forensics fan-out (sliced per shard and
        fed to each shard plane as ``precomputed``), reusing the merged
        extras where the family published them (Gram blocks, norms)
        instead of paying the host score pass again. Falls back to
        :meth:`round_evidence` on the merged rows. ``None`` when the
        aggregator publishes no per-row scores."""
        import numpy as np

        rows = np.asarray(merged["rows"], np.float32)
        if rows.shape[0] == 0:
            return None
        return self.round_evidence(
            rows, np.ones((rows.shape[0],), bool), aggregate=aggregate
        )

    # -- forensics evidence (per-row score view) ---------------------------

    #: True when :meth:`round_evidence` publishes a binary keep set
    #: (selection aggregators: Krum families, CGE, MoNNA). Lets
    #: selection-only consumers (``chaos.influence.selection_mask``)
    #: skip the score computation entirely for aggregators whose view
    #: carries scores but no selection (e.g. trimmed-mean clip
    #: fractions — an O(m·d·log m) host pass that would be discarded).
    evidence_selects: bool = False

    def round_evidence(
        self, matrix: Any, valid: Any, *, aggregate: Any = None
    ) -> Optional[dict]:
        """Per-row score/selection view of one (padded) cohort for the
        forensics plane (``byzpy_tpu.forensics``), or ``None`` when the
        aggregator publishes no per-row scores (or the valid cohort is
        empty/inadmissible — no defined selection).

        Returns ``{"kind": str, "scores": (n,) float array, "keep":
        (n,) bool array or None}`` aligned to PADDED slot positions
        (invalid rows carry NaN scores / False keeps). Computed
        HOST-SIDE from the same published score programs the aggregate
        uses (``ops.robust.krum_scores``, per-row norms, …) — never
        inside the aggregation program, so round aggregates stay
        digest-identical with forensics on or off. ``aggregate`` (the
        round's broadcast) is only needed by center-seeking aggregators
        (geomed/clipping) whose scores are distances to the output."""
        return None

    def _evidence_rows(self, matrix: Any, valid: Any) -> Optional[tuple]:
        """Shared preamble for ``round_evidence`` overrides: the
        compacted valid rows as float32 numpy, their padded indices,
        and the padded shape — or ``None`` when the valid cohort is
        empty or inadmissible (``validate_n`` rejects ``m``)."""
        import numpy as np

        valid = np.asarray(valid, bool)
        idx = np.flatnonzero(valid)
        m = int(idx.size)
        if m == 0:
            return None
        try:
            self.validate_n(m)
        except ValueError:
            return None
        rows = np.asarray(matrix, np.float32)[idx]
        return rows, idx, valid.shape[0]

    @staticmethod
    def _evidence_view(
        kind: str, n: int, idx, scores, keep_local=None
    ) -> dict:
        """Scatter compacted per-row ``scores`` (and an optional local
        keep index set) back to padded positions."""
        import numpy as np

        full = np.full((n,), np.nan, np.float32)
        full[idx] = np.asarray(scores, np.float32)
        keep = None
        if keep_local is not None:
            keep = np.zeros((n,), bool)
            keep[idx[np.asarray(keep_local)]] = True
        return {"kind": kind, "scores": full, "keep": keep}

    def validate_n(self, n: int) -> None:
        """Hook for subclasses to validate hyperparameters against n."""

    @abstractmethod
    def _aggregate_matrix(self, x: jnp.ndarray) -> jnp.ndarray:
        """Aggregate the stacked ``(n, d)`` matrix to a ``(d,)`` vector."""

    def matrix_fn(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """The bare matrix->vector function, for embedding in jitted
        training steps (SPMD parameter server, gossip loops)."""
        return self._aggregate_matrix


__all__ = ["Aggregator", "SlotFoldState", "ravel_gradient"]
