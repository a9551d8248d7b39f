"""Ragged serving dispatch: kill the ladder, batch tenants, one compile.

The bucket ladder (``serving.buckets``) keeps jit caches warm by
padding every cohort into one of ``log2(cap)+1`` shapes — each tenant
compiles a ladder of programs, every non-full cohort pays padded FLOPs,
and every tenant's round serializes on the frontend's device lock one
dispatch at a time. This module is the ragged replacement built on
``ops.ragged``'s flat-rows programs:

* :class:`RaggedExecutor` — ONE jitted program per tenant *group*
  (same aggregator class + static hyperparameters + gradient dim):
  static shapes are the group's row capacity and cohort-count cap, so
  the jit cache holds exactly one entry per group no matter how cohort
  sizes are distributed — compile count == tenant count when every
  tenant aggregates differently (pinned via the ``serving.ragged``
  jitstats site), vs ``tenants × ladder`` on the bucket path.
* :class:`RaggedBatcher` — the cross-tenant coalescer: tenant
  schedulers hand their closed cohorts to a shared dispatcher task
  which drains everything currently pending and issues ONE device call
  per compatible group (the Podracer economics: while one batch runs on
  the device, the next batch accumulates). Multiple tenants' cohorts
  ride one dispatch instead of serializing on the lock.
* fused forensics — selection aggregators' dispatches return the
  per-row score/keep view (it rides the aggregation math for free), so
  the forensics plane skips the host-side O(m²·d) score pass
  (``Aggregator.round_evidence``) entirely; per-row norm/cosine
  feature outputs are additionally available per executor
  (``with_evidence=True`` — extra HBM passes, compiled in only for
  consumers that read them).

Bit-parity contract: per-cohort aggregates are bit-identical (f32,
finite rows) to the exact unpadded ``aggregate`` AND to the bucket
path's masked finalize, for any batch composition — the serving digest
pins (chaos wall, WAL continuity) hold with either door. Non-finite or
inadmissible cohorts never enter a batch: the frontend routes them
through the guarded ``aggregate_masked`` door exactly as before.

Dispatch gates (resolved pre-trace, the PR-2 wrapper pattern; both read
at frontend construction):

* ``BYZPY_TPU_RAGGED=0`` — escape hatch: disable the ragged door
  entirely and serve every tenant through the bucket ladder (default
  ragged wherever the aggregator supports it — i.e. it has a masked
  program; others fall back to the ladder automatically).
* ``BYZPY_TPU_RAGGED_PALLAS=1`` — opt-in: route the final segment-sum
  contraction through the fused Pallas kernel
  (``pallas_kernels.ragged_segment_sum_pallas``). Off by default: the
  XLA program is the authoritative bit-parity path; Mosaic agrees at
  ~ulp on a v5e (``chip_smoke.py`` checks the dense, int8 and fp8
  kernels every run; the s4 kernel does not lower and raises).
"""

from __future__ import annotations

import asyncio
import logging
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import jitstats as obs_jitstats
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing
from ..ops import ragged as ragged_ops
from .cohort import Cohort

_LOG = logging.getLogger("byzpy_tpu.serving")

#: jitstats dispatch site for every ragged executor's compile cache —
#: over a mixed-size swarm ``byzpy_jit_compiles_total{site=
#: "serving.ragged"}`` equals the tenant-group count (== tenant count
#: when every tenant aggregates differently), the ladder-free compile
#: economics the tier promises.
RAGGED_SITE = "serving.ragged"


def ragged_enabled() -> bool:
    """The serving-tier ragged door switch (``BYZPY_TPU_RAGGED``;
    default ON). Read at frontend construction — flipping the env var
    changes the next frontend built, not a live one."""
    return os.environ.get("BYZPY_TPU_RAGGED", "1") != "0"


def ragged_segment_sum_fn(
    rows: int, dim: int
) -> Optional[Callable]:
    """Pre-trace dispatch for the ragged contraction kernel: the fused
    Pallas segment sum on explicit opt-in
    (``BYZPY_TPU_RAGGED_PALLAS=1``), else ``None`` (the XLA per-cohort
    einsum contraction — the authoritative bit-parity path). Resolved
    here, in Python, before the executor's program traces; the tile
    itself resolves inside the kernel wrapper (family ``"ragged"``)."""
    if os.environ.get("BYZPY_TPU_RAGGED_PALLAS", "0") != "1":
        return None
    from ..ops.pallas_kernels import ragged_segment_sum_pallas

    def segment_sum(x, weights):
        return ragged_segment_sum_pallas(x, weights)

    return segment_sum


def ragged_segment_dequant_fn(mode: str, block: int) -> Optional[Callable]:
    """Pre-trace dispatch for the FUSED-dequant contraction kernel
    (``ops.pallas_kernels.ragged_segment_sum_dequant_pallas``): same
    explicit opt-in as :func:`ragged_segment_sum_fn`, additionally
    keyed by the batch's wire codec spec. ``None`` keeps the XLA
    mirror (``flat_dequantize`` at program entry + einsum contraction
    — the authoritative bit-parity path)."""
    if os.environ.get("BYZPY_TPU_RAGGED_PALLAS", "0") != "1":
        return None
    if mode == "s4" and block % 2:
        return None
    from ..ops.pallas_kernels import ragged_segment_sum_dequant_pallas

    def seg_dequant(codes, scales, weights, *, d):
        return ragged_segment_sum_dequant_pallas(
            codes, scales, weights, mode=mode, block=block, d=d
        )

    return seg_dequant


@dataclass(frozen=True)
class RaggedView:
    """One cohort's slice of a ragged dispatch: the aggregate vector
    plus the fused forensics outputs (``scores``/``keep`` are ``None``
    for non-selection aggregators; ``norms``/``cos`` are computed on
    the discounted rows the fold aggregated, and are ``None`` when the
    cohort took the exact non-finite fallback instead of the kernel)."""

    vector: np.ndarray
    score_kind: str
    scores: Optional[np.ndarray]
    keep: Optional[np.ndarray]
    norms: Optional[np.ndarray]
    cos: Optional[np.ndarray]

    def precomputed(self) -> Optional[dict]:
        """The ``ForensicsPlane.prepare(precomputed=...)`` payload —
        ``None`` when this aggregator family publishes no score view
        (the plane then runs its host pass as before)."""
        if self.scores is None:
            return None
        return {
            "kind": self.score_kind,
            "scores": self.scores,
            "keep": self.keep,
        }


class RaggedExecutor:
    """One tenant group's compiled ragged program.

    Static shape contract: ``row_capacity`` flat rows × ``max_cohorts``
    cohorts of dimension ``dim`` — one jit cache entry serves every
    batch this group can produce (each tenant has at most one round in
    flight, so a batch holds at most one cohort per group member and at
    most the sum of their cohort caps in rows). The program applies the
    per-row staleness discounts in-jit (``weight == 1.0`` rows are
    bit-identical, matching the bucket path's host-side scaling),
    aggregates every cohort, and emits the fused evidence outputs."""

    def __init__(
        self,
        aggregator: Any,
        dim: int,
        row_capacity: int,
        max_cohorts: int,
        with_evidence: bool = True,
    ) -> None:
        fn = aggregator.ragged_matrix_fn()
        if fn is None:
            raise ValueError(
                f"{type(aggregator).__name__} has no ragged program"
            )
        self.dim = int(dim)
        self.rows = int(row_capacity)
        self.max_cohorts = int(max_cohorts)
        self.score_kind = aggregator.ragged_score_kind
        self.dispatches = 0
        self.cohorts_dispatched = 0
        #: largest number of cohorts one device call carried
        self.max_batch = 0
        #: device dispatches whose rows entered the program as wire
        #: codes (no host f32 materialization of the batch)
        self.quantized_dispatches = 0
        self._fn = fn
        self._with_evidence = bool(with_evidence)
        self._segment_sum = ragged_segment_sum_fn(self.rows, self.dim)
        #: one lazily-built jitted program per wire codec spec the
        #: batched ingress actually admits ((mode, block) keys; in
        #: practice a deployment pins ONE wire precision, so this adds
        #: a single extra compile-cache entry, accounted like the rest)
        self._jitted_q: Dict[tuple, Any] = {}
        segment_sum = self._segment_sum
        n_cohorts = self.max_cohorts

        def program(flat, seg, offsets, lengths, weights):
            with jax.named_scope("serving.ragged_scale"):
                scaled = flat * weights[:, None].astype(flat.dtype)
            with jax.named_scope("serving.ragged_aggregate"):
                aggs, score, keep = fn(
                    scaled, seg, offsets, lengths,
                    n_cohorts=n_cohorts, segment_sum=segment_sum,
                )
            # the selection families' score/keep ride the aggregation
            # math for free; the norm/cosine features are EXTRA passes
            # compiled in only on request (with_evidence) — no frontend
            # consumer reads them today, so production executors leave
            # them out and pay nothing for attribution nobody reads
            if not with_evidence:
                return aggs, score, keep, None, None
            with jax.named_scope("serving.ragged_evidence"):
                norm, cos = ragged_ops.ragged_evidence(
                    scaled, seg, aggs, n_cohorts=n_cohorts
                )
            return aggs, score, keep, norm, cos

        self._jitted = jax.jit(program)

    def _jitted_quant(self, mode: str, block: int):
        """The quantized-entry twin of the dense program, per wire
        codec spec: consumes the flat batch as stacked codes + scales,
        dequantizes as the FIRST traced op
        (``ops.ragged.flat_dequantize`` — bit-identical to the host
        wire codec), and runs the identical aggregation body, so a
        quantized round's aggregate is bit-for-bit the dense program's
        on the ingress-decoded rows. Under ``BYZPY_TPU_RAGGED_PALLAS=1``
        the trailing segment-sum contraction additionally fuses the
        dequant INTO the kernel (codes travel to the MXU tile), with
        staleness weights folded into the per-cohort weight rows —
        the Pallas path's documented ulp-level contract."""
        key = (mode, block)
        jitted = self._jitted_q.get(key)
        if jitted is not None:
            return jitted
        fn = self._fn
        n_cohorts = self.max_cohorts
        dim = self.dim
        with_evidence = self._with_evidence
        base_segment_sum = self._segment_sum
        fused = (
            ragged_segment_dequant_fn(mode, block)
            if base_segment_sum is not None else None
        )

        def program_q(codes, scales_q, seg, offsets, lengths, weights):
            with jax.named_scope("serving.ragged_dequant"):
                flat = ragged_ops.flat_dequantize(
                    codes, scales_q, mode=mode, block=block, d=dim
                )
            with jax.named_scope("serving.ragged_scale"):
                scaled = flat * weights[:, None].astype(flat.dtype)
            segment_sum = base_segment_sum
            if fused is not None:
                def segment_sum(x, w):
                    # `x is scaled` resolves at TRACE time: only the
                    # contraction over the scaled flat rows may take
                    # the fused kernel (sorted/derived operands keep
                    # the dense kernel — their bits are not wire codes)
                    if x is scaled:
                        return fused(
                            codes, scales_q,
                            w * weights[None, :].astype(w.dtype), d=dim,
                        )
                    return base_segment_sum(x, w)
            with jax.named_scope("serving.ragged_aggregate"):
                aggs, score, keep = fn(
                    scaled, seg, offsets, lengths,
                    n_cohorts=n_cohorts, segment_sum=segment_sum,
                )
            if not with_evidence:
                return aggs, score, keep, None, None
            with jax.named_scope("serving.ragged_evidence"):
                norm, cos = ragged_ops.ragged_evidence(
                    scaled, seg, aggs, n_cohorts=n_cohorts
                )
            return aggs, score, keep, norm, cos

        jitted = self._jitted_q[key] = jax.jit(program_q)
        return jitted

    @staticmethod
    def _quant_spec(cohorts: Sequence[Cohort]) -> Optional[tuple]:
        """The shared wire codec spec when EVERY cohort in the batch is
        still quantized with identical layout — the precondition for
        the quantized-entry program; mixed batches densify (lazily,
        bit-identically) and take the dense program."""
        c0 = cohorts[0]
        if not c0.quantized:
            return None
        spec = (
            c0.qmode, c0.qblock,
            int(c0.qcodes.shape[1]), int(c0.qscales.shape[1]),
        )
        for c in cohorts[1:]:
            if not c.quantized or (
                c.qmode, c.qblock,
                int(c.qcodes.shape[1]), int(c.qscales.shape[1]),
            ) != spec:
                return None
        return spec

    def cache_size(self) -> Optional[int]:
        try:
            return int(self._jitted._cache_size()) + sum(
                int(j._cache_size()) for j in self._jitted_q.values()
            )
        except Exception:  # noqa: BLE001 — introspection API drift
            return None

    def expected_compiles(self) -> int:
        """Compile-cache entries this executor legitimately owns: the
        dense program plus one per wire codec spec seen."""
        return 1 + len(self._jitted_q)

    def aggregate(
        self, cohorts: Sequence[Cohort], tenants: Sequence[str]
    ) -> List[RaggedView]:
        """ONE device dispatch for ``cohorts`` (≤ ``max_cohorts``, rows
        summing to ≤ ``row_capacity``); returns one :class:`RaggedView`
        per cohort, in order. Callers guarantee each cohort is finite
        and admissible (the frontend's door checks)."""
        n = len(cohorts)
        if not 1 <= n <= self.max_cohorts:
            raise ValueError(
                f"batch of {n} cohorts exceeds max_cohorts={self.max_cohorts}"
            )
        sizes = [c.m for c in cohorts]
        fill = sum(sizes)
        if fill > self.rows:
            raise ValueError(
                f"batch of {fill} rows exceeds row capacity {self.rows}"
            )
        seg = np.full((self.rows,), self.max_cohorts, np.int32)
        weights = np.zeros((self.rows,), np.float32)
        offsets = np.full((self.max_cohorts,), fill, np.int32)
        lengths = np.zeros((self.max_cohorts,), np.int32)
        off = 0
        for c, cohort in enumerate(cohorts):
            m = sizes[c]
            weights[off:off + m] = cohort.weights[:m]
            seg[off:off + m] = c
            offsets[c] = off
            lengths[c] = m
            off += m
        qspec = self._quant_spec(cohorts)
        if qspec is not None:
            # batched-ingress hot path: the flat batch stays WIRE codes
            # on host; f32 rows first exist inside the jitted program
            mode, block, ncodes, nb = qspec
            codes = np.zeros((self.rows, ncodes), cohorts[0].qcodes.dtype)
            scales = np.zeros((self.rows, nb), np.float32)
            off = 0
            for c, cohort in enumerate(cohorts):
                m = sizes[c]
                codes[off:off + m] = cohort.qcodes[:m]
                scales[off:off + m] = cohort.qscales[:m]
                off += m
            jitted = self._jitted_quant(mode, block)
            rows_args = (jnp.asarray(codes), jnp.asarray(scales))
            self.quantized_dispatches += 1
        else:
            flat = np.zeros((self.rows, self.dim), np.float32)
            off = 0
            for c, cohort in enumerate(cohorts):
                m = sizes[c]
                flat[off:off + m] = cohort.matrix[:m]
                off += m
            jitted = self._jitted
            rows_args = (jnp.asarray(flat),)
        label = tenants[0] if len(tenants) == 1 else ",".join(tenants)
        track = f"tenant:{tenants[0]}" if len(tenants) == 1 else None
        with obs_tracing.span(
            "serving.fold", track=track, tenant=label,
            cohorts=n, rows=fill, quantized=qspec is not None,
        ):
            with obs_tracing.device_span(
                "serving.device_step", track=track, tenant=label,
                cohorts=n, rows=fill, ragged=True,
            ):
                aggs, score, keep, norm, cos = jitted(
                    *rows_args, jnp.asarray(seg),
                    jnp.asarray(offsets), jnp.asarray(lengths),
                    jnp.asarray(weights),
                )
        aggs = np.asarray(aggs)
        score = None if score is None else np.asarray(score)
        keep = None if keep is None else np.asarray(keep)
        norm = None if norm is None else np.asarray(norm)
        cos = None if cos is None else np.asarray(cos)
        self.dispatches += 1
        self.cohorts_dispatched += n
        self.max_batch = max(self.max_batch, n)
        views = []
        off = 0
        for c, m in enumerate(sizes):
            views.append(
                RaggedView(
                    vector=aggs[c],
                    score_kind=self.score_kind,
                    scores=(
                        None if score is None else score[off:off + m]
                    ),
                    keep=None if keep is None else keep[off:off + m],
                    norms=None if norm is None else norm[off:off + m],
                    cos=None if cos is None else cos[off:off + m],
                )
            )
            off += m
        return views


class RaggedRuntime:
    """The frontend's ragged plane: tenant grouping, per-group
    executors, the cross-tenant batcher, and compile-cache accounting.

    Groups are computed once at construction: tenants sharing an
    aggregator signature (``Aggregator.ragged_group_key``) AND gradient
    dimension share one executor — their cohorts may coalesce into one
    device call. Tenants whose aggregator has no ragged program (no
    masked program: MDA/SMEA/CAF) are simply absent here and keep the
    bucket-ladder path."""

    def __init__(self, tenant_cfgs: Sequence[Any]) -> None:
        self._groups: Dict[tuple, dict] = {}
        self._by_tenant: Dict[str, tuple] = {}
        for cfg in tenant_cfgs:
            agg = cfg.aggregator
            if not getattr(agg, "supports_ragged", False):
                continue
            if agg.ragged_matrix_fn() is None:  # pragma: no cover
                continue
            key = (agg.ragged_group_key(), int(cfg.dim))
            g = self._groups.setdefault(
                key,
                {"aggregator": agg, "dim": int(cfg.dim), "caps": [],
                 "names": [], "executor": None},
            )
            g["caps"].append(int(cfg.cohort_cap))
            g["names"].append(cfg.name)
            self._by_tenant[cfg.name] = key
        self._batcher: Optional["RaggedBatcher"] = None
        #: ragged compiles already warned about (each NEW excess size
        #: warns once, mirroring the bucket ladder's recompile alarm)
        self._warn_high = 0

    # -- introspection ---------------------------------------------------

    def serves(self, tenant: str) -> bool:
        return tenant in self._by_tenant

    def executor_for(self, tenant: str) -> Optional[RaggedExecutor]:
        key = self._by_tenant.get(tenant)
        if key is None:
            return None
        g = self._groups[key]
        if g["executor"] is None:
            # the program's row capacity is the group's LARGEST tenant
            # cap — the compiled shape a full cohort needs anyway. The
            # XLA fallback pays the full static capacity per dispatch
            # (only the Pallas path skips unfilled row tiles), so
            # coalescing packs other tenants' cohorts into capacity a
            # lone cohort would leave empty: strictly more work per
            # call at the same per-call cost. Full cohorts fill the
            # capacity alone and serialize — at exactly the ladder's
            # top-bucket cost. Non-coalescing families (sort-based:
            # nothing shared on XLA) serve one cohort per call.
            coalesce = bool(
                getattr(g["aggregator"], "ragged_coalesce", False)
            )
            g["executor"] = RaggedExecutor(
                g["aggregator"], g["dim"],
                row_capacity=max(g["caps"]),
                max_cohorts=len(g["caps"]) if coalesce else 1,
                # the production plane consumes only the score/keep
                # view (which rides the aggregation math for free);
                # the norm/cos feature passes are extra HBM sweeps no
                # frontend consumer reads, so they stay compiled out —
                # direct RaggedExecutor users opt in per instance
                with_evidence=False,
            )
        return g["executor"]

    def snapshot(self) -> dict:
        """JSON-ready accounting for ``ServingFrontend.stats()``."""
        execs = [
            g["executor"]
            for g in self._groups.values()
            if g["executor"] is not None
        ]
        batched = self._batcher
        return {
            "groups": len(self._groups),
            "tenants": sorted(self._by_tenant),
            "dispatches": sum(e.dispatches for e in execs),
            "cohorts_dispatched": sum(e.cohorts_dispatched for e in execs),
            # dispatches whose rows entered the program as wire codes
            # (device-side dequant; no host f32 batch was built)
            "quantized_dispatches": sum(
                e.quantized_dispatches for e in execs
            ),
            "compile_entries": sum(
                e.cache_size() or 0 for e in execs
            ),
            "batched_calls": 0 if batched is None else batched.batched_calls,
            # largest number of cohorts ONE device call carried (>= 2 =
            # cross-tenant batching happened)
            "max_batch": max(
                [e.max_batch for e in execs],
                default=0,
            ),
        }

    # -- compile-cache accounting ----------------------------------------

    def note_compiles(self) -> None:
        """Report the summed ragged jit-cache size to the
        ``serving.ragged`` jitstats site and warn (once per excess
        size) if it ever exceeds one entry per group — the ragged
        door's whole point is ONE compile per tenant group, so growth
        past that is the same silent latency cliff the bucket ladder's
        alarm watches for."""
        execs = [
            g["executor"]
            for g in self._groups.values()
            if g["executor"] is not None
        ]
        sizes = [e.cache_size() for e in execs]
        if any(s is None for s in sizes):
            return
        total = sum(sizes)
        obs_jitstats.note_cache_size(RAGGED_SITE, total)
        expected = sum(e.expected_compiles() for e in execs)
        if total > expected and total > self._warn_high:
            self._warn_high = total
            obs_metrics.registry().counter(
                "byzpy_serving_ragged_recompile_warnings_total",
                help="ragged-program compiles beyond one per tenant group",
            ).inc()
            _LOG.warning(
                "ragged serving door has %d compiled programs for %d "
                "tenant groups — an unexpected recompile happened "
                "(shape or dtype drift); every extra entry is a silent "
                "latency cliff",
                total, expected,
            )

    # -- dispatch doors --------------------------------------------------

    def aggregate_sync(
        self, tenant: str, cohort: Cohort
    ) -> Optional[RaggedView]:
        """Single-cohort synchronous dispatch (the virtual-time round
        closer's door); ``None`` when the tenant is not ragged-served."""
        ex = self.executor_for(tenant)
        if ex is None:
            return None
        (view,) = ex.aggregate([cohort], [tenant])
        self.note_compiles()
        return view

    async def start(self, device_lock: asyncio.Lock) -> None:
        self._batcher = RaggedBatcher(self, device_lock)
        await self._batcher.start()

    async def close(self) -> None:
        if self._batcher is not None:
            await self._batcher.close()
            self._batcher = None

    async def aggregate_async(
        self, tenant: str, cohort: Cohort, fallback: Any = None
    ) -> RaggedView:
        """Enqueue one closed cohort for batched dispatch and await its
        view (the async scheduler's door; requires :meth:`start`).
        ``fallback`` (a :class:`~byzpy_tpu.serving.cohort.
        CohortAggregator`) serves non-finite cohorts through the exact
        guarded door — the finite gate runs on the dispatch executor
        thread, never on the event loop."""
        assert self._batcher is not None, "RaggedRuntime.start() first"
        return await self._batcher.submit(tenant, cohort, fallback)


def _dispatch_group(
    ex: RaggedExecutor,
    items: Sequence[Tuple[str, Cohort, Any]],
) -> List[Any]:
    """One group's device call, on the dispatch EXECUTOR thread: gate
    each cohort's finiteness (an O(rows·d) host pass that must not run
    on the event loop), send the finite ones through the ragged program
    in ONE dispatch, and route non-finite cohorts through their
    tenant's exact guarded door (``CohortAggregator.aggregate`` — the
    same fallback stance as ``fold_finalize_masked``). Returns one
    ``RaggedView`` or ``Exception`` per item, in order."""
    finite_items: List[Tuple[int, str, Cohort]] = []
    results: List[Any] = [None] * len(items)
    for i, (tenant, cohort, fallback) in enumerate(items):
        # Cohort.finite() == isfinite(matrix).all(), but decided from
        # codes × scales for quantized cohorts — the gate must not be
        # the thing that forces a host dequant of the batched path
        if cohort.finite():
            finite_items.append((i, tenant, cohort))
        else:
            try:
                if fallback is None:
                    raise ValueError(
                        "non-finite cohort and no fallback aggregator"
                    )
                vec = np.asarray(fallback.aggregate(cohort))
                results[i] = RaggedView(
                    vector=vec, score_kind="", scores=None, keep=None,
                    norms=None, cos=None,
                )
            except Exception as exc:  # noqa: BLE001 — poisoned cohort:
                # ITS round fails, the rest of the batch still serves
                results[i] = exc
    # greedy chunking against the program's static capacity: a
    # non-coalescing executor (max_cohorts=1) naturally serves one
    # cohort per call; coalescing ones pack as many as fit
    chunk: List[Tuple[int, str, Cohort]] = []
    rows = 0
    chunks: List[List[Tuple[int, str, Cohort]]] = []
    for item in finite_items:
        m = item[2].m
        if chunk and (
            len(chunk) == ex.max_cohorts or rows + m > ex.rows
        ):
            chunks.append(chunk)
            chunk, rows = [], 0
        chunk.append(item)
        rows += m
    if chunk:
        chunks.append(chunk)
    for chunk in chunks:
        try:
            views = ex.aggregate(
                [c for _, _, c in chunk], [t for _, t, _ in chunk]
            )
        except Exception as exc:  # noqa: BLE001
            for i, _, _ in chunk:
                results[i] = exc
        else:
            for (i, _, _), view in zip(chunk, views, strict=True):
                results[i] = view
    return results


class RaggedBatcher:
    """Cross-tenant cohort coalescer: one dispatcher task owns the
    device lock while a batch runs, and drains EVERYTHING pending the
    moment it reacquires it — cohorts that closed while the previous
    batch was on the device ride the next call together instead of
    serializing one dispatch per cohort."""

    def __init__(
        self, runtime: RaggedRuntime, device_lock: asyncio.Lock
    ) -> None:
        self._runtime = runtime
        self._lock = device_lock
        self._pending: List[Tuple[str, Cohort, Any, asyncio.Future]] = []
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        #: dispatcher wake-ups that reached the device (device-call
        #: counts and per-call batch sizes live on the executors)
        self.batched_calls = 0

    async def start(self) -> None:
        self._task = asyncio.create_task(
            self._run(), name="serving-ragged-batcher"
        )

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._task = None
        for _, _, _, fut in self._pending:
            if not fut.done():
                fut.cancel()
        self._pending = []

    async def submit(
        self, tenant: str, cohort: Cohort, fallback: Any = None
    ) -> RaggedView:
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending.append((tenant, cohort, fallback, fut))
        self._wake.set()
        return await fut

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            await self._wake.wait()
            self._wake.clear()
            if not self._pending:
                continue
            # one yield so tenant loops whose windows expired in the
            # same scheduler pass can close their cohorts too — they
            # join THIS batch instead of trailing it by a device call
            await asyncio.sleep(0)
            batch: List[Tuple[str, Cohort, Any, asyncio.Future]] = []
            try:
                async with self._lock:
                    # drain at lock ACQUISITION: everything that closed
                    # while the previous batch held the device coalesces
                    batch, self._pending = self._pending, []
                    if not batch:
                        continue
                    by_exec: Dict[int, dict] = {}
                    for tenant, cohort, fallback, fut in batch:
                        ex = self._runtime.executor_for(tenant)
                        assert ex is not None, tenant
                        slot = by_exec.setdefault(
                            id(ex), {"ex": ex, "items": []}
                        )
                        slot["items"].append(
                            (tenant, cohort, fallback, fut)
                        )
                    for slot in by_exec.values():
                        ex = slot["ex"]
                        items = slot["items"]
                        results = await loop.run_in_executor(
                            None, _dispatch_group, ex,
                            [(t, c, fb) for t, c, fb, _ in items],
                        )
                        self.batched_calls += 1
                        for (_, _, _, fut), res in zip(
                            items, results, strict=True
                        ):
                            if fut.done():
                                continue
                            if isinstance(res, Exception):
                                fut.set_exception(res)
                            else:
                                fut.set_result(res)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 — the dispatcher
                # must outlive ANY failure (executor construction,
                # shutdown races, grouping bugs): fail the drained
                # batch's rounds (their tenant loops crash-guard each
                # as a failed_round) and keep serving — a dead
                # dispatcher would hang every ragged tenant forever
                for _, _, _, fut in batch:
                    if not fut.done():
                        fut.set_exception(exc)
            self._runtime.note_compiles()


__all__ = [
    "RAGGED_SITE",
    "RaggedBatcher",
    "RaggedExecutor",
    "RaggedRuntime",
    "RaggedView",
    "ragged_enabled",
    "ragged_segment_dequant_fn",
    "ragged_segment_sum_fn",
]
