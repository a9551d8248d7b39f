"""Seeded fuzz sweep: every fused Pallas kernel vs its XLA oracle.

Randomized (but deterministic) shapes, hyper-parameters, dtypes, and
non-finite injection patterns — the structured unit tests pin known edge
cases; this sweep hunts the unknown ones. Interpret mode on CPU, same
code paths as the chip (tests/conftest.py pins the platform).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.heavy  # opt-in lane: see pyproject addopts

from byzpy_tpu.ops import robust
from byzpy_tpu.ops.pallas_kernels import (
    nnm_stream_pallas,
    selection_mean_stream_pallas,
    sorted_reduce_stream_pallas,
)

N_CASES = 12


def _random_case(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 33))
    d = int(rng.integers(130, 900))
    x = rng.normal(size=(n, d)).astype(np.float32) * 10.0 ** float(rng.integers(-2, 3))
    # sprinkle non-finite rows/entries in ~half the cases
    if rng.random() < 0.5:
        for _ in range(int(rng.integers(1, 3))):
            r = int(rng.integers(0, n))
            val = rng.choice([np.inf, -np.inf, np.nan])
            if rng.random() < 0.5:
                x[r] = val  # whole row
            else:
                x[r, :: int(rng.integers(2, 7))] = val
    return n, d, x


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_selection_mean_krum(seed):
    n, d, x = _random_case(1000 + seed)
    rng = np.random.default_rng(seed)
    f = int(rng.integers(0, max(1, (n - 1) // 2)))
    q = int(rng.integers(1, n - f + 1))
    xa = jnp.asarray(x)
    got = selection_mean_stream_pallas(
        xa[None], f=f, q=q, mode="krum", tile=128, interpret=True
    )[0]
    want = robust.ranked_mean(xa, robust.krum_scores(xa, f=f), q)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5, equal_nan=True
    )


def _sum_over_sublanes(window):
    """The f32 sum of ``window``'s rows in the order the kernel adds
    them, which is the TPU's for a sum over the sublanes of (8, 128)
    tiles (``pallas_kernels._sublane_order_sum``; PERF.md section 6, PR
    29), spelled as the hardware does it: the rows sit at the first
    sublanes of zeroed eight-row vregs, the vregs are added one after
    another, then the eight partial sums by rotate-and-add (4, 2, 1);
    one row is itself. A top-to-bottom ``jnp.mean`` is an ulp away on a
    third of the columns, and further where the window cancels."""
    m, width = window.shape
    if m == 1:
        return window[0]
    block = np.zeros((-(-m // 8) * 8, width), np.float32)
    block[:m] = window
    with np.errstate(invalid="ignore"):  # inf - inf is the kernel's NaN too
        acc = block[:8].copy()
        for at in range(8, len(block), 8):
            acc = acc + block[at:at + 8]
        for shift in (4, 2, 1):
            acc = acc + np.roll(acc, -shift, axis=0)
    return acc[0]


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_sorted_reduce(seed):
    n, d, x = _random_case(2000 + seed)
    xa = jnp.asarray(x)
    got = sorted_reduce_stream_pallas(
        xa[None], mode="median", tile=128, interpret=True
    )[0]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.median(xa, axis=0))
    )
    f = int(np.random.default_rng(seed).integers(0, (n - 1) // 2 + 1))
    if 2 * f < n:
        got = sorted_reduce_stream_pallas(
            xa[None], mode="trimmed", f=f, tile=128, interpret=True
        )[0]
        s = np.asarray(jnp.sort(xa, axis=0))
        want = _sum_over_sublanes(s[f : n - f]) / np.float32(n - 2 * f)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6,
            equal_nan=True,
        )


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_nnm(seed):
    n, d, x = _random_case(3000 + seed)
    rng = np.random.default_rng(seed)
    f = int(rng.integers(0, n))
    xa = jnp.asarray(x)
    got = np.asarray(nnm_stream_pallas(xa[None], f=f, tile=128, interpret=True)[0])
    # oracle: the (fixed) XLA path — identical non-finite semantics
    import os

    prev = os.environ.get("BYZPY_TPU_PALLAS")
    os.environ["BYZPY_TPU_PALLAS"] = "0"
    try:
        from byzpy_tpu.ops import preagg

        want = np.asarray(preagg.nnm(xa, f=f))
    finally:
        if prev is None:
            os.environ.pop("BYZPY_TPU_PALLAS", None)
        else:
            os.environ["BYZPY_TPU_PALLAS"] = prev
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5, equal_nan=True)


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_bf16_selection(seed):
    n, d, x = _random_case(4000 + seed)
    rng = np.random.default_rng(seed)
    f = int(rng.integers(0, max(1, (n - 1) // 2)))
    q = int(rng.integers(1, n - f + 1))
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    got = selection_mean_stream_pallas(
        xb[None], f=f, q=q, mode="krum", tile=128, interpret=True
    )[0]
    want = robust.ranked_mean(xb, robust.krum_scores(xb, f=f), q)
    assert got.dtype == jnp.bfloat16
    g32 = np.asarray(got, np.float32)
    w32 = np.asarray(want, np.float32)
    both_nan = np.isnan(g32) & np.isnan(w32)
    scale = float(np.nanmax(np.abs(w32[~both_nan]))) if (~both_nan).any() else 1.0
    # bf16 scores can flip near-tie selections between the two paths;
    # any legitimate q-subset mean stays within the honest spread
    assert np.allclose(
        g32[~both_nan], w32[~both_nan], rtol=0.15, atol=0.15 * max(scale, 1e-6)
    ) or not np.isfinite(scale)


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_weighted_center_step(seed):
    from byzpy_tpu.ops.pallas_kernels import weighted_center_step_pallas

    n, d, x = _random_case(5000 + seed)
    xa = jnp.asarray(x)
    z = jnp.median(xa, axis=0)
    got = weighted_center_step_pallas(xa, z, mode="weiszfeld", tile=128,
                                      interpret=True)
    diff = xa - z[None, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
    w = 1.0 / jnp.maximum(dist, 1e-12)
    want = jnp.sum(w[:, None] * xa, axis=0) / jnp.sum(w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4, equal_nan=True
    )
    tau = float(np.random.default_rng(seed).uniform(0.5, 3.0))
    got = weighted_center_step_pallas(xa, z, mode="clip", c_tau=tau, tile=128,
                                      interpret=True)
    scale = jnp.minimum(1.0, tau / jnp.maximum(dist, 1e-12))
    want = z + jnp.mean(diff * scale[:, None], axis=0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4, equal_nan=True
    )


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_mda_matches_bruteforce(seed):
    """MDA's branch-and-bound + greedy-peel incumbent vs exhaustive
    enumeration on small instances (diameter ties broken identically:
    first subset in combination order)."""
    import itertools

    from byzpy_tpu.aggregators import MinimumDiameterAveraging

    rng = np.random.default_rng(7000 + seed)
    n = int(rng.integers(6, 11))
    f = int(rng.integers(1, (n - 1) // 2 + 1))
    m = n - f
    x = rng.normal(size=(n, 12)).astype(np.float32)
    grads = [jnp.asarray(r) for r in x]
    got = np.asarray(MinimumDiameterAveraging(f=f).aggregate(grads))
    # oracle uses the implementation's own metric (f32 Gram-trick
    # distances): a direct-difference f64 metric can crown a different
    # winner on near-ties, which is a float-representation disagreement,
    # not an algorithmic one
    gram = x @ x.T
    nrm = np.diagonal(gram)
    d2 = np.maximum(nrm[:, None] + nrm[None, :] - 2.0 * gram, 0.0)
    combos = list(itertools.combinations(range(n), m))
    diams = np.array([d2[np.ix_(np.array(c), np.array(c))].max() for c in combos])
    best_diam = diams.min()
    # the branch-and-bound may return ANY minimum-diameter subset (ties
    # are not broken by enumeration order); accept every tied winner
    winners = [
        x[list(c)].mean(0)
        for c, dm in zip(combos, diams, strict=True)
        if dm <= best_diam * (1 + 1e-6) + 1e-9
    ]
    assert any(
        np.allclose(got, w, rtol=1e-4, atol=1e-5) for w in winners
    ), (best_diam, len(winners))


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_random_dag_schedulers_agree(seed):
    """Property: ParallelScheduler and the sequential NodeScheduler give
    identical results on random DAGs of arithmetic ops."""
    import asyncio

    from byzpy_tpu.engine.graph.graph import (
        ComputationGraph,
        GraphInput,
        GraphNode,
    )
    from byzpy_tpu.engine.graph.ops import CallableOp
    from byzpy_tpu.engine.graph.parallel_scheduler import ParallelScheduler
    from byzpy_tpu.engine.graph.scheduler import NodeScheduler

    rng = np.random.default_rng(8000 + seed)
    n_nodes = int(rng.integers(3, 9))
    nodes = []
    names = []
    for i in range(n_nodes):
        # each node consumes the graph input and up to 2 earlier nodes
        deps = {"x": GraphInput("x")}
        if names:
            for j, nm in enumerate(
                rng.choice(names, size=min(len(names), int(rng.integers(0, 3))),
                           replace=False)
            ):
                deps[f"d{j}"] = str(nm)
        coefs = rng.normal(size=len(deps))

        def fn(_coefs=coefs, **kw):
            vals = [kw[k] for k in sorted(kw)]
            return sum(float(c) * v for c, v in zip(_coefs, vals, strict=True))

        name = f"n{i}"
        nodes.append(GraphNode(name=name, op=CallableOp(fn), inputs=deps))
        names.append(name)
    graph = ComputationGraph(nodes)
    inputs = {"x": jnp.asarray(rng.normal(size=(16,)).astype(np.float32))}
    seq = asyncio.run(NodeScheduler(graph).run(inputs))
    par = asyncio.run(ParallelScheduler(ComputationGraph(nodes)).run(inputs))
    for k in seq:
        np.testing.assert_allclose(
            np.asarray(seq[k]), np.asarray(par[k]), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_caf_downweights_outliers(seed):
    """Property: with f large outliers, CAF's output stays near the
    honest mean (closer than the naive mean is) and finite."""
    rng = np.random.default_rng(9000 + seed)
    n = int(rng.integers(10, 20))
    f = max(1, n // 5)
    d = int(rng.integers(16, 64))
    honest = rng.normal(size=(n - f, d)).astype(np.float32)
    outliers = (rng.normal(size=(f, d)) * 100 + 500).astype(np.float32)
    x = np.concatenate([honest, outliers])
    out = np.asarray(robust.caf(jnp.asarray(x), f=f))
    assert np.isfinite(out).all()
    honest_mean = honest.mean(0)
    naive_mean = x.mean(0)
    assert np.linalg.norm(out - honest_mean) < 0.5 * np.linalg.norm(
        naive_mean - honest_mean
    )


@pytest.mark.parametrize("seed", range(N_CASES))
def test_fuzz_meamed_window_vs_gather_oracle(seed, monkeypatch):
    """The single-phase window kernel AND the XLA window path vs the
    gather-rule oracle (shared with test_pallas_kernels), under random
    shapes/f and non-finite injection — whole-inf rows can drive the
    median itself to ±inf, the regime the round-5 review found broken.
    Non-finite outputs must match exactly (kind AND sign)."""
    from test_pallas_kernels import _meamed_oracle

    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    n, d, x = _random_case(7000 + seed)
    rng = np.random.default_rng(seed)
    f = int(rng.integers(0, n))
    want = _meamed_oracle(x, f)
    xa = jnp.asarray(x)
    got_kernel = np.asarray(
        meamed_stream_pallas(xa[None], f=f, tile=128, interpret=True)[0]
    )
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
    got_xla = np.asarray(robust.mean_of_medians(xa, f=f))
    for got, label in ((got_kernel, "kernel"), (got_xla, "xla")):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5, equal_nan=True,
            err_msg=f"{label} n={n} f={f} seed={seed}",
        )
