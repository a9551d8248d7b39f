"""Ragged serving dispatch: bit parity, compile economics, batching.

The PR-11 contract (ISSUE 11): the flat-rows ragged door
(``ops.ragged`` + ``serving.ragged``) replaces the bucket ladder as the
serving tier's default dispatch — ONE compiled program per tenant
group, cross-tenant cohorts coalesced into one device call, forensics
score views riding the kernel — while every cohort's aggregate stays
BIT-IDENTICAL (f32, finite rows) to the exact unpadded aggregate and
therefore to the bucket path's masked finalize. The ladder remains the
escape hatch (``BYZPY_TPU_RAGGED=0``) and the automatic fallback for
aggregators without a masked program.
"""

import asyncio

import numpy as np
import pytest

from byzpy_tpu.aggregators import (
    CAF,
    CenteredClipping,
    ComparativeGradientElimination,
    CoordinateWiseMedian,
    CoordinateWiseTrimmedMean,
    GeometricMedian,
    Krum,
    MeanOfMedians,
    MoNNA,
    MultiKrum,
)
from byzpy_tpu.observability import jitstats as obs_jitstats
from byzpy_tpu.observability import metrics as obs_metrics
from byzpy_tpu.serving import ServingFrontend, TenantConfig
from byzpy_tpu.serving.cohort import build_cohort
from byzpy_tpu.serving.queue import Submission
from byzpy_tpu.serving.ragged import (
    RAGGED_SITE,
    RaggedExecutor,
    RaggedRuntime,
    ragged_enabled,
)
from byzpy_tpu.serving.staleness import StalenessPolicy

N = 8
D = 193

#: Every masked-program aggregator serves the ragged door (the
#: specialized families AND the generic per-cohort masked loop).
RAGGED_CASES = [
    (lambda: CoordinateWiseMedian(), "median"),
    (lambda: CoordinateWiseTrimmedMean(f=0), "trimmed-f0"),
    (lambda: CoordinateWiseTrimmedMean(f=1), "trimmed-f1"),
    (lambda: MeanOfMedians(f=0), "meamed-f0"),
    (lambda: MeanOfMedians(f=2), "meamed-f2"),
    (lambda: MultiKrum(f=1, q=2), "multikrum"),
    (lambda: Krum(f=1), "krum"),
    (lambda: ComparativeGradientElimination(f=0), "cge-f0"),
    (lambda: ComparativeGradientElimination(f=1), "cge-f1"),
    (lambda: MoNNA(f=1), "monna"),
    (lambda: GeometricMedian(), "geomed"),
    (lambda: CenteredClipping(c_tau=1.0), "clip"),
]
IDS = [name for _, name in RAGGED_CASES]


def _grads(n=N, d=D, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return [
        (rng.normal(size=d) * s * scale).astype(np.float32)
        for s in rng.uniform(0.1, 50.0, n)
    ]


def _admissible(agg, m):
    try:
        agg.validate_n(m)
        return True
    except ValueError:
        return False


def _cohort(grads, *, server_round=0, rounds_submitted=None,
            staleness=None):
    rounds_submitted = rounds_submitted or [server_round] * len(grads)
    subs = [
        Submission(client=f"c{i}", round_submitted=r, gradient=g,
                   arrived_s=float(i))
        for i, (g, r) in enumerate(
            zip(grads, rounds_submitted, strict=True)
        )
    ]
    return build_cohort(
        subs, server_round, None, staleness or StalenessPolicy()
    )


# ---------------------------------------------------------------------------
# ops-level / executor-level bit parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_agg", [c for c, _ in RAGGED_CASES], ids=IDS)
@pytest.mark.parametrize("m", [1, N // 2, N - 1, N])
def test_single_cohort_ragged_vs_masked_vs_exact_bitwise(make_agg, m):
    """The satellite grid: every streaming aggregator × m ∈
    {1, n/2, n−1, n} through a capacity-padded ragged dispatch equals
    the masked finalize AND the exact subset aggregate bit-for-bit."""
    agg = make_agg()
    assert agg.supports_ragged
    if not _admissible(agg, m):
        pytest.skip(f"m={m} inadmissible")
    grads = _grads()[:m]
    exact = np.asarray(agg.aggregate(grads))
    padded = np.zeros((N, D), np.float32)
    padded[:m] = np.stack(grads)
    valid = np.zeros(N, bool)
    valid[:m] = True
    masked = np.asarray(agg.aggregate_masked(padded, valid))
    ex = RaggedExecutor(agg, D, row_capacity=N + 5, max_cohorts=1)
    (view,) = ex.aggregate([_cohort(grads)], ["t0"])
    np.testing.assert_array_equal(view.vector, exact, err_msg=agg.name)
    np.testing.assert_array_equal(view.vector, masked, err_msg=agg.name)


@pytest.mark.parametrize("make_agg", [c for c, _ in RAGGED_CASES], ids=IDS)
def test_mixed_batch_every_cohort_bitwise(make_agg):
    """A cross-tenant-shaped batch — three cohorts of different sizes
    and magnitudes in ONE dispatch — reproduces each cohort's exact
    aggregate bit-for-bit (batch composition must not leak between
    segments)."""
    agg = make_agg()
    sizes = [5, 6, 8]
    if not all(_admissible(agg, m) for m in sizes):
        pytest.skip("sizes inadmissible")
    cohorts, exacts = [], []
    for i, m in enumerate(sizes):
        grads = _grads(n=m, seed=10 + i, scale=(0.3, 1.0, 20.0)[i])
        cohorts.append(_cohort(grads))
        exacts.append(np.asarray(agg.aggregate(grads)))
    ex = RaggedExecutor(
        agg, D, row_capacity=sum(sizes) + 7, max_cohorts=len(sizes) + 1
    )
    views = ex.aggregate(cohorts, [f"t{i}" for i in range(len(sizes))])
    assert ex.dispatches == 1
    for view, exact in zip(views, exacts, strict=True):
        np.testing.assert_array_equal(view.vector, exact, err_msg=agg.name)


def test_staleness_discounts_bitwise_through_ragged():
    """Discounted rows scale in-jit on the ragged path; parity vs the
    hand-scaled exact aggregate (the bucket path's own pin)."""
    agg = CoordinateWiseTrimmedMean(f=0)
    grads = _grads(seed=19)[:4]
    pol = StalenessPolicy(kind="exponential", gamma=0.5)
    cohort = _cohort(
        grads, server_round=6, rounds_submitted=[6, 5, 4, 6],
        staleness=pol,
    )
    ex = RaggedExecutor(agg, D, row_capacity=8, max_cohorts=1)
    (view,) = ex.aggregate([cohort], ["t0"])
    scaled = [
        grads[0], grads[1] * np.float32(0.5),
        grads[2] * np.float32(0.25), grads[3],
    ]
    np.testing.assert_array_equal(
        view.vector, np.asarray(agg.aggregate(scaled))
    )


def test_pallas_segment_sum_opt_in_parity(monkeypatch):
    """The opt-in fused Pallas contraction (interpret mode off-TPU)
    reproduces the XLA ragged program to ~1 ulp — which is exactly why
    it stays opt-in: the XLA program is the authoritative bit-parity
    path (see ``ragged_segment_sum_pallas``'s docstring)."""
    agg = MultiKrum(f=1, q=3)
    grads = _grads(seed=23)
    exact = np.asarray(agg.aggregate(grads))
    monkeypatch.setenv("BYZPY_TPU_RAGGED_PALLAS", "1")
    ex = RaggedExecutor(agg, D, row_capacity=N + 3, max_cohorts=1)
    (view,) = ex.aggregate([_cohort(grads)], ["t0"])
    np.testing.assert_allclose(view.vector, exact, rtol=2e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# escape hatch / automatic fallback
# ---------------------------------------------------------------------------


def _run_rounds(make_agg, *, dim=D, rounds=3, name="m0"):
    """Drive one tenant through the sync closer; returns per-round
    aggregates + the frontend."""
    fe = ServingFrontend(
        [
            TenantConfig(
                name=name, aggregator=make_agg(), dim=dim,
                cohort_cap=16, min_bucket=2,
            )
        ]
    )
    rng = np.random.default_rng(7)
    out = []
    for r in range(rounds):
        m = (5, 9, 16)[r % 3]
        rows = [rng.normal(size=dim).astype(np.float32) for _ in range(m)]
        for i, g in enumerate(rows):
            ok, reason = fe.submit(name, f"c{i}", r, g)
            assert ok, reason
        closed = fe.close_round_nowait(name)
        assert closed is not None
        out.append((rows, np.asarray(closed[2])))
    return out, fe


def test_escape_hatch_and_default_are_bit_identical(monkeypatch):
    """BYZPY_TPU_RAGGED=0 (ladder) and the default ragged door produce
    bit-identical aggregates — and both match the exact subset path."""
    monkeypatch.setenv("BYZPY_TPU_RAGGED", "0")
    assert not ragged_enabled()
    ladder_rounds, fe0 = _run_rounds(lambda: MultiKrum(f=1, q=2))
    assert not fe0.stats()["m0"]["ragged_served"]
    monkeypatch.delenv("BYZPY_TPU_RAGGED")
    assert ragged_enabled()
    ragged_rounds, fe1 = _run_rounds(lambda: MultiKrum(f=1, q=2))
    assert fe1.stats()["m0"]["ragged_served"]
    agg = MultiKrum(f=1, q=2)
    for (rows_l, vec_l), (rows_r, vec_r) in zip(
        ladder_rounds, ragged_rounds, strict=True
    ):
        np.testing.assert_array_equal(vec_l, vec_r)
        np.testing.assert_array_equal(
            vec_r, np.asarray(agg.aggregate(rows_r))
        )


def test_no_masked_program_falls_back_to_ladder():
    """CAF has no masked program → not ragged-served, ladder door as
    before (automatic fallback, no config needed)."""
    rounds, fe = _run_rounds(lambda: CAF(f=1), rounds=1)
    assert not fe.stats()["m0"]["ragged_served"]
    assert fe.stats()["m0"]["frontend"]["ragged"]["groups"] == 0


def test_nonfinite_cohort_routes_to_exact_door():
    """A NaN gradient leaves the ragged batch and takes the guarded
    exact path — same answer as the unpadded aggregate, and the ragged
    executor never dispatches."""
    fe = ServingFrontend(
        [
            TenantConfig(
                name="m0", aggregator=CoordinateWiseMedian(), dim=D,
                cohort_cap=16,
            )
        ]
    )
    rng = np.random.default_rng(5)
    rows = [rng.normal(size=D).astype(np.float32) for _ in range(5)]
    rows[2][7] = np.nan
    for i, g in enumerate(rows):
        ok, _ = fe.submit("m0", f"c{i}", 0, g)
        assert ok
    closed = fe.close_round_nowait("m0")
    assert closed is not None
    agg = CoordinateWiseMedian()
    np.testing.assert_array_equal(
        np.asarray(closed[2]), np.asarray(agg.aggregate(rows))
    )
    assert fe.stats()["m0"]["frontend"]["ragged"]["dispatches"] == 0


# ---------------------------------------------------------------------------
# compile economics (the jitstats satellite)
# ---------------------------------------------------------------------------


def test_compile_count_equals_tenant_count_over_mixed_swarm():
    """The headline economics: a mixed-cohort-size swarm over tenants
    with distinct programs compiles EXACTLY one ragged program per
    tenant (site ``serving.ragged``), and neither recompile alarm —
    the PR-10 bucket-ladder one nor the ragged one — fires."""
    obs_jitstats.reset()
    tenants = [
        TenantConfig(
            name="a", aggregator=CoordinateWiseTrimmedMean(f=1), dim=24,
            cohort_cap=16,
        ),
        TenantConfig(
            name="b", aggregator=MultiKrum(f=1, q=2), dim=32,
            cohort_cap=16,
        ),
    ]
    fe = ServingFrontend(tenants)
    rng = np.random.default_rng(11)
    for r in range(6):
        for name, dim in (("a", 24), ("b", 32)):
            m = (4, 7, 11, 5, 16, 9)[r]
            for i in range(m):
                ok, _ = fe.submit(
                    name, f"c{i}", r,
                    rng.normal(size=dim).astype(np.float32),
                )
                assert ok
            assert fe.close_round_nowait(name) is not None
    # one compiled ragged program per tenant, across 5 distinct cohort
    # sizes each — the ladder would have compiled ~log2(cap)+1 per
    # tenant and the naive path one per distinct size
    assert obs_jitstats.compiles_seen(RAGGED_SITE) == 2
    snap = fe.stats()["a"]["frontend"]["ragged"]
    assert snap["groups"] == 2 and snap["compile_entries"] == 2
    reg = obs_metrics.registry()
    for name in ("a", "b"):
        warn = reg.counter(
            "byzpy_serving_recompile_warnings_total",
            labels={"tenant": name},
        )
        assert warn.value == 0, name
    assert (
        reg.counter(
            "byzpy_serving_ragged_recompile_warnings_total"
        ).value == 0
    )


def test_ragged_ps_step_one_compile_and_bucket_parity():
    """The ragged serving update step: ONE compiled program across
    cohort sizes, params bit-identical to the bucketed masked step."""
    from jax.flatten_util import ravel_pytree

    from byzpy_tpu.models import mnist_mlp
    from byzpy_tpu.parallel.ps import (
        jit_ragged_serving_ps_step,
        jit_serving_ps_step,
    )

    bundle = mnist_mlp()
    agg = CoordinateWiseTrimmedMean(f=1)
    d = ravel_pytree(bundle.params)[0].shape[0]
    cap = 16
    step_r, opt_r = jit_ragged_serving_ps_step(
        bundle, agg.ragged_matrix_fn(), row_capacity=cap
    )
    step_b, opt_b = jit_serving_ps_step(bundle, agg.masked_matrix_fn())
    rng = np.random.default_rng(0)
    params_r, params_b = bundle.params, bundle.params
    state_r, state_b = opt_r, opt_b
    for m, bucket in ((5, 8), (3, 8), (9, 16), (16, 16)):
        rows = rng.normal(size=(m, d)).astype(np.float32)
        flat = np.zeros((cap, d), np.float32)
        flat[:m] = rows
        w = np.zeros(cap, np.float32)
        w[:m] = 1.0
        params_r, state_r, metrics = step_r(
            params_r, state_r, flat,
            np.zeros(1, np.int32), np.asarray([m], np.int32), w,
        )
        assert int(metrics["cohort_m"]) == m
        matrix = np.zeros((bucket, d), np.float32)
        matrix[:m] = rows
        valid = np.zeros(bucket, bool)
        valid[:m] = True
        params_b, state_b, _ = step_b(
            params_b, state_b, matrix, valid, valid.astype(np.float32)
        )
    # FOUR distinct cohort sizes: one ragged compile, two bucket ones
    assert step_r._cache_size() == 1
    assert step_b._cache_size() == 2
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(params_r)[0]),
        np.asarray(ravel_pytree(params_b)[0]),
    )


# ---------------------------------------------------------------------------
# fused forensics view
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "make_agg",
    [lambda: MultiKrum(f=1, q=2), lambda: ComparativeGradientElimination(f=2)],
    ids=["multikrum", "cge"],
)
def test_fused_score_view_matches_round_evidence(make_agg):
    """The kernel's score/keep outputs reproduce the host
    ``round_evidence`` pass: identical keep sets (same stable tie
    rule), scores equal to float tolerance (slice-sum vs windowed
    einsum accumulation)."""
    agg = make_agg()
    grads = _grads(seed=31)
    ex = RaggedExecutor(agg, D, row_capacity=N + 2, max_cohorts=1)
    (view,) = ex.aggregate([_cohort(grads)], ["t0"])
    assert view.score_kind == agg.ragged_score_kind
    matrix = np.stack(grads)
    host = agg.round_evidence(matrix, np.ones(N, bool))
    assert host["kind"] == view.score_kind
    np.testing.assert_array_equal(view.keep, host["keep"])
    np.testing.assert_allclose(
        view.scores, host["scores"], rtol=1e-5, atol=1e-4
    )
    # fused features: norms/cosines of the aggregated rows
    np.testing.assert_allclose(
        view.norms, np.linalg.norm(matrix, axis=1), rtol=1e-5
    )
    assert view.cos.shape == (N,)


def test_plane_precomputed_matches_host_pass():
    """Feeding the plane the kernel's precomputed view produces the
    same selection verdicts and flags as the host score pass."""
    from byzpy_tpu.forensics.plane import ForensicsPlane

    agg = MultiKrum(f=1, q=2)
    grads = _grads(seed=37)
    matrix = np.stack(grads)
    valid = np.ones(N, bool)
    clients = [f"c{i}" for i in range(N)]
    aggregate = np.asarray(agg.aggregate(grads))
    ex = RaggedExecutor(agg, D, row_capacity=N, max_cohorts=1)
    (view,) = ex.aggregate([_cohort(grads)], ["t0"])
    host_plane = ForensicsPlane("host")
    kernel_plane = ForensicsPlane("kernel")
    ev_host = host_plane.observe_round(
        0, matrix, valid, clients, aggregate, aggregator=agg
    )
    prep = kernel_plane.prepare(
        0, matrix, valid, clients, aggregate,
        aggregator=agg, precomputed=view.precomputed(),
    )
    ev_kernel = kernel_plane.apply(prep)
    assert ev_kernel.score_kind == ev_host.score_kind
    for rh, rk in zip(ev_host.records, ev_kernel.records, strict=True):
        assert rk.selected == rh.selected
        assert rk.flags == rh.flags
        assert rk.trust == rh.trust


# ---------------------------------------------------------------------------
# cross-tenant batching
# ---------------------------------------------------------------------------


def _runtime_pair(make_agg):
    """Drive two same-group tenants' cohorts through the batcher in one
    pending window; returns ``(views, snapshot, grads_a, grads_b)``."""

    async def run():
        cfgs = [
            TenantConfig(
                name=n, aggregator=make_agg(), dim=D, cohort_cap=16,
            )
            for n in ("a", "b")
        ]
        runtime = RaggedRuntime(cfgs)
        assert runtime.executor_for("a") is runtime.executor_for("b")
        await runtime.start(asyncio.Lock())
        g_a = _grads(n=5, seed=41)
        g_b = _grads(n=9, seed=43)
        res = await asyncio.gather(
            runtime.aggregate_async("a", _cohort(g_a)),
            runtime.aggregate_async("b", _cohort(g_b)),
        )
        snap = runtime.snapshot()
        await runtime.close()
        return res, snap, g_a, g_b

    return asyncio.run(run())


def test_batcher_coalesces_two_tenants_into_one_dispatch():
    """Two tenants sharing a COALESCING group (Multi-Krum: one shared
    Gram scores the batch) whose cohorts are pending together ride ONE
    device call — and each gets its exact aggregate back."""
    (va, vb), snap, g_a, g_b = _runtime_pair(lambda: MultiKrum(f=1, q=2))
    agg = MultiKrum(f=1, q=2)
    np.testing.assert_array_equal(va.vector, np.asarray(agg.aggregate(g_a)))
    np.testing.assert_array_equal(vb.vector, np.asarray(agg.aggregate(g_b)))
    assert snap["dispatches"] == 1, snap
    assert snap["max_batch"] == 2, snap
    assert snap["cohorts_dispatched"] == 2


def test_sort_family_serves_per_cohort_with_one_program():
    """The non-coalescing policy pin: a sort-based aggregator's group
    serves one cohort per device call on the XLA fallback (nothing is
    shared across the batch there, and sorting the union is
    superlinear) — but still through ONE compiled program."""
    (va, vb), snap, g_a, g_b = _runtime_pair(
        lambda: CoordinateWiseTrimmedMean(f=1)
    )
    agg = CoordinateWiseTrimmedMean(f=1)
    np.testing.assert_array_equal(va.vector, np.asarray(agg.aggregate(g_a)))
    np.testing.assert_array_equal(vb.vector, np.asarray(agg.aggregate(g_b)))
    assert snap["dispatches"] == 2, snap
    assert snap["max_batch"] == 1, snap
    assert snap["compile_entries"] == 1, snap


def test_async_frontend_end_to_end_through_ragged():
    """The async scheduler path: two ragged tenants serve rounds end to
    end; accounting shows the ragged door carried every round."""

    async def run():
        fe = ServingFrontend(
            [
                TenantConfig(
                    name=n, aggregator=CoordinateWiseTrimmedMean(f=1),
                    dim=32, window_s=0.01, cohort_cap=8, min_cohort=3,
                )
                for n in ("a", "b")
            ]
        )
        await fe.start()
        rng = np.random.default_rng(3)
        for r in range(3):
            for name in ("a", "b"):
                for i in range(5):
                    ok, reason = fe.submit(
                        name, f"c{i}", fe.round_of(name),
                        rng.normal(size=32).astype(np.float32),
                    )
                    assert ok, reason
            await fe.drain("a")
            await fe.drain("b")
        stats = fe.stats()
        await fe.close()
        return stats

    stats = asyncio.run(run())
    assert stats["a"]["rounds"] >= 3 and stats["b"]["rounds"] >= 3
    assert stats["a"]["failed_rounds"] == 0
    assert stats["b"]["failed_rounds"] == 0
    snap = stats["a"]["frontend"]["ragged"]
    assert snap["dispatches"] >= 1
    assert snap["cohorts_dispatched"] >= 6
