"""Pallas kernels vs jnp oracles (interpret mode on the CPU mesh).

The kernels must match the XLA implementations bit-for-bit in f32: the
sorting network is exact (min/max network), the Gram kernel accumulates in
f32 like the einsum path.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.ops import robust
from byzpy_tpu.ops.pallas_kernels import (
    gram_pallas,
    median_pallas,
    pairwise_sq_dists_pallas,
    selection_mean_pallas,
    selection_mean_stream_pallas,
    sort_columns,
    trimmed_mean_pallas,
    use_pallas_for,
)


@pytest.fixture(params=[(5, 300), (8, 512), (13, 1000), (32, 4096)])
def matrix(request):
    n, d = request.param
    key = jax.random.PRNGKey(n * 1000 + d)
    return jax.random.normal(key, (n, d), jnp.float32) * 10.0


def test_sort_columns_matches_jnp(matrix):
    out = sort_columns(matrix, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.sort(np.asarray(matrix), axis=0)
    )


def test_median_matches_jnp(matrix):
    out = median_pallas(matrix, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.median(np.asarray(matrix), axis=0), rtol=1e-6
    )


def test_trimmed_mean_matches_oracle(matrix):
    n = matrix.shape[0]
    f = (n - 1) // 2
    out = trimmed_mean_pallas(matrix, f=f, interpret=True)
    s = np.sort(np.asarray(matrix), axis=0)
    np.testing.assert_allclose(
        np.asarray(out), s[f : n - f].mean(axis=0), rtol=1e-6
    )
    with pytest.raises(ValueError):
        trimmed_mean_pallas(matrix, f=n, interpret=True)


def test_gram_and_distances_match(matrix):
    gram = gram_pallas(matrix, tile=256, interpret=True)
    # tiled accumulation reorders float adds vs the one-shot matmul; f32
    # rel error grows ~sqrt(d)*eps, and cancellation makes small
    # off-diagonals relatively noisy (measured 1.5e-3 rel at d=4096 on
    # entries ~1e-8 of the diagonal) — the atol is tiny vs typical
    # magnitudes (1e3-4e5) and absorbs exactly that
    np.testing.assert_allclose(
        np.asarray(gram),
        np.asarray(matrix) @ np.asarray(matrix).T,
        rtol=1e-3,
        atol=1e-2,
    )
    d2 = pairwise_sq_dists_pallas(matrix, tile=256, interpret=True)
    np.testing.assert_allclose(
        np.asarray(d2), np.asarray(robust.pairwise_sq_dists(matrix)), rtol=1e-4,
        atol=1e-3,
    )


def test_gram_bf16_accumulates_f32():
    x = (jax.random.normal(jax.random.PRNGKey(0), (8, 1024)) * 3).astype(jnp.bfloat16)
    gram = gram_pallas(x, tile=256, interpret=True)
    assert gram.dtype == jnp.float32
    oracle = np.asarray(x, np.float32) @ np.asarray(x, np.float32).T
    np.testing.assert_allclose(np.asarray(gram), oracle, rtol=2e-2)


def test_dispatch_policy_env_override(monkeypatch):
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
    assert not use_pallas_for(8, 1 << 20)
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    assert use_pallas_for(8, 100)
    assert not use_pallas_for(512, 1 << 20)  # network capped at small n
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "auto")
    # CPU backend in tests -> auto says no
    assert not use_pallas_for(8, 1 << 20)


def _inject_nonfinite(x, seed):
    """Sprinkle +inf / -inf / NaN over ~10% of entries each."""
    rng = np.random.default_rng(seed)
    a = np.asarray(x).copy()
    for val in (np.inf, -np.inf, np.nan):
        mask = rng.random(a.shape) < 0.1
        a[mask] = val
    return jnp.asarray(a)


@pytest.mark.parametrize("special", ["inf", "-inf", "nan", "mixed", "all-nan-col"])
def test_sort_columns_nonfinite_matches_jnp(special):
    """jnp.sort total order (-inf < finite < +inf < NaN) survives the network;
    regression for the finfo.max padding bug that ranked +inf after padding
    and let NaN poison the compare-exchanges."""
    x = jax.random.normal(jax.random.PRNGKey(7), (9, 700), jnp.float32) * 5.0
    a = np.asarray(x).copy()
    if special == "inf":
        a[2, ::3] = np.inf
    elif special == "-inf":
        a[4, ::5] = -np.inf
    elif special == "nan":
        a[1, ::4] = np.nan
    elif special == "mixed":
        a = np.asarray(_inject_nonfinite(x, seed=11))
    else:  # a full column of NaN
        a[:, 42] = np.nan
    out = sort_columns(jnp.asarray(a), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.sort(jnp.asarray(a), axis=0))
    )


def test_sort_columns_negative_zero_and_extremes():
    """-0.0/+0.0 compare equal; finfo.max/min sort strictly inside inf."""
    fmax = np.float32(np.finfo(np.float32).max)
    col = np.array(
        [[np.inf], [-np.inf], [fmax], [-fmax], [0.0], [-0.0], [1.0]], np.float32
    )
    a = np.tile(col, (1, 300))
    out = np.asarray(sort_columns(jnp.asarray(a), interpret=True))
    np.testing.assert_array_equal(out, np.sort(a, axis=0))
    assert out[-1, 0] == np.inf and out[-2, 0] == fmax


def test_median_trimmed_mean_with_inf_match_xla():
    """The repo's own InfAttack shape: one +inf row among honest rows must
    leave the median/trimmed-mean finite and equal to the XLA path."""
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 1024), jnp.float32)
    a = np.asarray(x).copy()
    a[3, :] = np.inf
    xa = jnp.asarray(a)
    med = np.asarray(median_pallas(xa, interpret=True))
    np.testing.assert_array_equal(med, np.asarray(jnp.median(xa, axis=0)))
    assert np.isfinite(med).all()
    s = jnp.sort(xa, axis=0)
    np.testing.assert_array_equal(
        np.asarray(trimmed_mean_pallas(xa, f=1, interpret=True)),
        np.asarray(jnp.mean(s[1:-1], axis=0)),
    )


def test_median_int_input_promotes_like_jnp():
    x = jnp.asarray(np.array([[1, 4], [2, 3], [3, 2], [4, 1]], np.int32))
    out = median_pallas(x, interpret=True)
    ref = jnp.median(x, axis=0)
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_median_f16_parity_including_overflow():
    """jnp.median midpoints in the input dtype — for f16 at half-max
    magnitude that overflows to inf, and parity means we overflow the same
    way (verified against the oracle, not an idealized contract)."""
    x = jnp.full((4, 300), 40000.0, jnp.float16)
    out = median_pallas(x, interpret=True)
    ref = jnp.median(x, axis=0)
    assert out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))


def test_median_nan_propagates_like_jnp():
    """jnp.median returns NaN for any column containing NaN; the Pallas
    median must agree (caught on-chip: sort-based middle pick is finite)."""
    x = jax.random.normal(jax.random.PRNGKey(21), (8, 512), jnp.float32)
    a = np.asarray(x).copy()
    a[5, ::7] = np.nan
    xa = jnp.asarray(a)
    np.testing.assert_array_equal(
        np.asarray(median_pallas(xa, interpret=True)),
        np.asarray(jnp.median(xa, axis=0)),
    )


def test_sort_columns_bf16_roundtrip():
    x = (jax.random.normal(jax.random.PRNGKey(5), (6, 500)) * 3).astype(jnp.bfloat16)
    a = np.asarray(x, np.float32).copy()
    a[0, ::7] = np.inf
    xa = jnp.asarray(a).astype(jnp.bfloat16)
    out = sort_columns(xa, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(jnp.sort(xa, axis=0), np.float32)
    )


def test_inf_attack_into_median_large_dim(monkeypatch):
    """Integration: InfAttack output flowing into CoordinateWiseMedian at
    d >= 256k routed through the Pallas path — the
    framework's own attack must not break its own median."""
    from byzpy_tpu.aggregators.coordinate_wise.median import CoordinateWiseMedian
    from byzpy_tpu.attacks.inf import InfAttack

    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")  # force Pallas (interpret on CPU)
    d = 262_144
    honest = [
        jax.random.normal(jax.random.PRNGKey(i), (d,), jnp.float32) for i in range(5)
    ]
    byz = InfAttack().apply(honest_grads=honest)
    assert not np.isfinite(np.asarray(byz)).any()
    stacked = jnp.stack(honest + [byz])
    got = np.asarray(CoordinateWiseMedian().aggregate(list(honest) + [byz]))
    want = np.asarray(jnp.median(stacked, axis=0))
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(got).all()


def test_robust_ops_use_pallas_when_forced(monkeypatch):
    """Forcing the flag routes the public ops through the kernels (still in
    interpret mode on CPU) and results stay correct."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    x = jax.random.normal(jax.random.PRNGKey(3), (9, 2048), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(robust.coordinate_median(x)),
        np.median(np.asarray(x), axis=0),
        rtol=1e-6,
        atol=1e-7,
    )
    s = np.sort(np.asarray(x), axis=0)
    # atol: near-zero coordinates see ulp-scale add-reorder noise from
    # the kernel's tiled mean (measured 3.7e-8 abs)
    np.testing.assert_allclose(
        np.asarray(robust.trimmed_mean(x, f=2)), s[2:-2].mean(axis=0),
        rtol=1e-6, atol=1e-7,
    )
    d2 = np.asarray(robust.pairwise_sq_dists(x))
    diff = np.asarray(x)[:, None, :] - np.asarray(x)[None, :, :]
    np.testing.assert_allclose(d2, (diff ** 2).sum(-1), rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Fused selection-mean kernel (Multi-Krum / CGE / MoNNA in one launch)
# ---------------------------------------------------------------------------


def _xla_multi_krum(x, f, q):
    scores = robust.krum_scores(x, f=f)
    return robust.ranked_mean(x, scores, q)


@pytest.mark.parametrize(
    "n,d,f,q",
    [
        pytest.param(64, 512, 8, 12, marks=pytest.mark.heavy),  # ~20s interpret run
        (17, 300, 3, 5),
        (16, 257, 2, 1),
        (8, 128, 1, 6),
    ]
)
def test_selection_mean_krum_parity(n, d, f, q):
    x = jax.random.normal(jax.random.PRNGKey(n + d), (n, d), jnp.float32)
    got = selection_mean_pallas(x, f=f, q=q, mode="krum", tile=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_xla_multi_krum(x, f, q)), rtol=1e-5, atol=1e-6
    )


def test_selection_mean_cge_monna_parity():
    x = jax.random.normal(jax.random.PRNGKey(7), (21, 400), jnp.float32)
    got = selection_mean_pallas(x, f=0, q=16, mode="cge", tile=128, interpret=True)
    want = robust.ranked_mean(x, jnp.sum(x * x, axis=1), 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    got = selection_mean_pallas(
        x, f=0, q=16, mode="monna", reference_index=3, tile=128, interpret=True
    )
    diff = x - x[3][None, :]
    want = robust.ranked_mean(x, jnp.sum(diff * diff, axis=1), 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_selection_mean_nonfinite_rows_excluded():
    """A NaN row ranks last (never selected at sane q); an inf row gets an
    inf/NaN score and is likewise excluded — matching ranked_mean's
    two-level (isnan, score) key exactly."""
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 200), jnp.float32)
    x = x.at[3].set(jnp.inf).at[7].set(jnp.nan)
    got = selection_mean_pallas(x, f=2, q=4, mode="krum", tile=128, interpret=True)
    want = _xla_multi_krum(x, f=2, q=4)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6, equal_nan=True
    )
    assert np.isfinite(np.asarray(got)).all()


def test_selection_mean_all_nan_scores_propagate():
    """If every row is NaN the selection must return NaN, not zeros from
    the masked contraction."""
    x = jnp.full((8, 128), jnp.nan, jnp.float32)
    got = selection_mean_pallas(x, f=1, q=2, mode="krum", tile=128, interpret=True)
    want = _xla_multi_krum(x, f=1, q=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_selection_mean_bf16_accumulates_f32():
    x = (jax.random.normal(jax.random.PRNGKey(5), (32, 384)) * 3).astype(jnp.bfloat16)
    got = selection_mean_pallas(x, f=4, q=6, tile=128, interpret=True)
    want = _xla_multi_krum(x, f=4, q=6)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=1e-2
    )


def test_selection_mean_vmap_batches():
    xs = jax.random.normal(jax.random.PRNGKey(9), (3, 16, 256), jnp.float32)
    got = jax.vmap(
        lambda a: selection_mean_pallas(a, f=2, q=5, tile=128, interpret=True)
    )(xs)
    want = jax.vmap(lambda a: _xla_multi_krum(a, 2, 5))(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_selection_mean_stream_matches_per_round():
    xs = jax.random.normal(jax.random.PRNGKey(11), (4, 17, 300), jnp.float32)
    xs = xs.at[0, 3].set(jnp.nan).at[1, 5].set(jnp.inf)
    got = selection_mean_stream_pallas(xs, f=3, q=5, tile=128, interpret=True)
    want = jnp.stack([_xla_multi_krum(xs[k], 3, 5) for k in range(4)])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6, equal_nan=True
    )
    got = selection_mean_stream_pallas(
        xs, f=0, q=14, mode="monna", reference_index=1, tile=128, interpret=True
    )
    want = jnp.stack(
        [robust.monna(xs[k], f=3, reference_index=1) for k in range(4)]
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5, equal_nan=True
    )


def test_selection_mean_validates_args():
    x = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(ValueError):
        selection_mean_pallas(x, f=7, q=1, mode="krum", interpret=True)
    with pytest.raises(ValueError):
        selection_mean_pallas(x, f=1, q=0, mode="cge", interpret=True)
    with pytest.raises(ValueError):
        selection_mean_pallas(x, f=1, q=2, mode="nope", interpret=True)
    with pytest.raises(ValueError):
        selection_mean_pallas(x, f=1, q=2, reference_index=9, interpret=True)


def test_robust_selection_ops_dispatch_when_forced(monkeypatch):
    """BYZPY_TPU_PALLAS=1 routes multi_krum/cge/monna and the stream
    variant through the fused kernel (interpret mode on CPU) with
    unchanged results. Oracles are computed from the un-jitted internals
    and the shape is unique to this test: the public ops are ``jax.jit``
    functions whose trace cache does not key on the env flag, so a same
    -shape call traced earlier in the process would bypass the dispatch."""
    x = jax.random.normal(jax.random.PRNGKey(13), (19, 1792), jnp.float32)
    xs = jnp.stack([x, x * 0.5 + 1.0])
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    np.testing.assert_allclose(
        np.asarray(robust.multi_krum(x, f=2, q=4)),
        np.asarray(_xla_multi_krum(x, 2, 4)), rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(robust.cge(x, f=3)),
        np.asarray(robust.ranked_mean(x, jnp.sum(x * x, axis=1), 16)),
        rtol=1e-5, atol=1e-6,
    )
    diff = x - x[2][None, :]
    np.testing.assert_allclose(
        np.asarray(robust.monna(x, f=3, reference_index=2)),
        np.asarray(robust.ranked_mean(x, jnp.sum(diff * diff, axis=1), 16)),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(robust.multi_krum_stream(xs, f=2, q=4)),
        np.asarray(jnp.stack([_xla_multi_krum(xs[k], 2, 4) for k in range(2)])),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# Fused NNM kernel
# ---------------------------------------------------------------------------


def _nnm_oracle(x, f):
    """Reference gather semantics (byzpy/pre_aggregators/nnm.py:50-95):
    stable argsort of Gram-trick distances, mean of the k selected rows."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    k = n - f
    gram = x @ x.T
    nrm = np.diagonal(gram)
    d2 = np.maximum(nrm[:, None] + nrm[None, :] - 2 * gram, 0.0)
    idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.stack([x[idx[i]].mean(0) for i in range(n)])


@pytest.mark.parametrize("n,d,f", [(16, 256, 4), (13, 300, 3), (8, 128, 0)])
def test_nnm_pallas_matches_oracle(n, d, f):
    from byzpy_tpu.ops.pallas_kernels import nnm_pallas

    x = jax.random.normal(jax.random.PRNGKey(n + d + f), (n, d), jnp.float32)
    got = np.asarray(nnm_pallas(x, f=f, tile=128, interpret=True))
    np.testing.assert_allclose(got, _nnm_oracle(x, f), rtol=1e-4, atol=1e-5)


def test_nnm_pallas_matches_xla_path():
    from byzpy_tpu.ops import preagg
    from byzpy_tpu.ops.pallas_kernels import nnm_pallas

    x = jax.random.normal(jax.random.PRNGKey(2), (21, 384), jnp.float32)
    got = np.asarray(nnm_pallas(x, f=5, tile=128, interpret=True))
    want = np.asarray(preagg.nnm(x, f=5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_nnm_nonfinite_row_taints_only_selectors():
    """A NaN gradient must NOT poison every mixed row (the old mask @ x
    path did): rows that never select it stay exactly at the gather
    oracle; the NaN row's own mix (which always self-selects) is NaN.
    Pinned for BOTH the XLA path and the kernel."""
    from byzpy_tpu.ops import preagg
    from byzpy_tpu.ops.pallas_kernels import nnm_pallas

    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(3), (10, 64), jnp.float32)
    ).copy()
    x[4] = np.nan
    # gather-oracle with the NaN row ranked last (its distances are NaN):
    # each other row's k=7 nearest come from the 9 finite rows
    keep = [i for i in range(10) if i != 4]
    xs_f = x[keep].astype(np.float64)
    d2 = ((xs_f[:, None, :] - xs_f[None, :, :]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :7]
    want = {keep[i]: xs_f[order[i]].mean(0) for i in range(9)}
    for impl in (
        lambda a: preagg.nnm(jnp.asarray(a), f=3),
        lambda a: nnm_pallas(jnp.asarray(a), f=3, tile=64, interpret=True),
    ):
        got = np.asarray(impl(x))
        assert np.isnan(got[4]).all()  # self-selection taints row 4
        for i in keep:  # NaN row ranks last: nobody else selects it
            assert not np.isnan(got[i]).any()
            np.testing.assert_allclose(got[i], want[i], rtol=1e-3, atol=1e-4)


def test_nnm_inf_row_becomes_nan_for_selectors():
    """Documented divergence from gather semantics: selecting an inf
    neighbor yields NaN (not +-inf). Force selection with f=0."""
    from byzpy_tpu.ops import preagg
    from byzpy_tpu.ops.pallas_kernels import nnm_pallas

    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(4), (6, 32), jnp.float32)
    ).copy()
    x[1] = np.inf
    for impl in (
        lambda a: preagg.nnm(jnp.asarray(a), f=0),
        lambda a: nnm_pallas(jnp.asarray(a), f=0, tile=32, interpret=True),
    ):
        got = np.asarray(impl(x))
        assert np.isnan(got).all()  # every row selects all rows at f=0


def test_nnm_stream_and_bf16():
    from byzpy_tpu.ops import preagg
    from byzpy_tpu.ops.pallas_kernels import nnm_stream_pallas

    xs = jax.random.normal(jax.random.PRNGKey(5), (3, 12, 256), jnp.float32)
    got = np.asarray(nnm_stream_pallas(xs, f=3, tile=128, interpret=True))
    want = np.stack([_nnm_oracle(xs[i], 3) for i in range(3)])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    xb = (xs[0] * 2).astype(jnp.bfloat16)
    got = nnm_stream_pallas(xb[None], f=3, tile=128, interpret=True)[0]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _nnm_oracle(np.asarray(xb, np.float32), 3),
        rtol=3e-2, atol=3e-2,
    )


def test_nnm_dispatch_when_forced(monkeypatch):
    from byzpy_tpu.ops import preagg

    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    x = jax.random.normal(jax.random.PRNGKey(6), (11, 1664), jnp.float32)
    got = np.asarray(preagg.nnm(x, f=2))
    np.testing.assert_allclose(got, _nnm_oracle(x, 2), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Fused sorted-reduce kernel (median / trimmed mean, no sort write-back)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(8, 256), (13, 300), (9, 700)])
def test_sorted_reduce_median_matches_jnp(n, d):
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    x = jax.random.normal(jax.random.PRNGKey(n * d), (n, d), jnp.float32) * 5
    got = sorted_reduce_stream_pallas(x[None], mode="median", tile=128,
                                      interpret=True)[0]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.median(x, axis=0))
    )


def test_sorted_reduce_median_nan_and_inf_parity():
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    a = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (10, 384), jnp.float32)
    ).copy()
    a[3, ::5] = np.inf
    a[7, ::9] = np.nan
    a[:, 42] = np.nan
    x = jnp.asarray(a)
    got = sorted_reduce_stream_pallas(x[None], mode="median", tile=128,
                                      interpret=True)[0]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.median(x, axis=0))
    )


def test_sorted_reduce_trimmed_matches_oracle():
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    x = jax.random.normal(jax.random.PRNGKey(2), (12, 500), jnp.float32)
    got = sorted_reduce_stream_pallas(x[None], mode="trimmed", f=3, tile=128,
                                      interpret=True)[0]
    s = np.sort(np.asarray(x), axis=0)
    np.testing.assert_allclose(
        np.asarray(got), s[3:-3].mean(axis=0), rtol=1e-5, atol=1e-6
    )
    with pytest.raises(ValueError):
        sorted_reduce_stream_pallas(x[None], mode="trimmed", f=6, interpret=True)
    with pytest.raises(ValueError):
        sorted_reduce_stream_pallas(x[None], mode="nope", interpret=True)


def test_sorted_reduce_bf16_median_bit_parity():
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    x = (jax.random.normal(jax.random.PRNGKey(3), (8, 256)) * 3).astype(
        jnp.bfloat16
    )
    got = sorted_reduce_stream_pallas(x[None], mode="median", tile=128,
                                      interpret=True)[0]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jnp.median(x, axis=0), np.float32),
    )


def test_sorted_reduce_stream_per_round_parity():
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    xs = jax.random.normal(jax.random.PRNGKey(4), (3, 9, 260), jnp.float32)
    got = sorted_reduce_stream_pallas(xs, mode="median", tile=128,
                                      interpret=True)
    want = jnp.stack([jnp.median(xs[i], axis=0) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_coordinate_median_dispatches_to_fused_reduce(monkeypatch):
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    x = jax.random.normal(jax.random.PRNGKey(5), (10, 1920), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(robust.coordinate_median(x)),
        np.asarray(jnp.median(x, axis=0)),
    )
    s = np.sort(np.asarray(x), axis=0)
    np.testing.assert_allclose(
        np.asarray(robust.trimmed_mean(x, f=2)), s[2:-2].mean(axis=0),
        rtol=1e-5, atol=1e-6,
    )
    xs = jnp.stack([x, x * 0.5])
    np.testing.assert_array_equal(
        np.asarray(robust.coordinate_median_stream(xs)),
        np.asarray(jnp.median(xs, axis=1)),
    )


def _sum_over_sublanes(vals):
    """``jnp.sum(vals, axis=0)`` as Mosaic takes it where the rows of
    ``vals`` are sublanes of (8, 128) tiles: the window brought to the
    first sublane, the sublanes past its end read as zero, the eight-row
    vregs added one after another, then the sublane rotate-and-add (by 4,
    2, 1); one row is itself. Measured on the v5e against the kernel
    below, the sign of a sum of nothing but -0.0 included (PERF.md
    section 6, PR 29); the interpreter's own ``jnp.sum`` adds top to
    bottom, an ulp away on a third of the columns."""
    m, width = vals.shape
    if m == 1:
        return vals[0]
    rows = -(-m // 8) * 8
    block = vals if rows == m else jnp.zeros((rows, width), vals.dtype).at[:m].set(vals)
    acc = block[:8]
    for at in range(8, rows, 8):
        acc = acc + block[at:at + 8]
    for shift in (4, 2, 1):
        acc = acc + jnp.roll(acc, -shift, axis=0)
    return acc[0]


def _sublane_row_sorted_reduce(xs, *, mode, f=0, tile=128, sum_rows=_sum_over_sublanes):
    """The sorted-reduce kernel as it was while a worker's row was a
    SUBLANE of the block (``(1, n_pad, tile)`` blocks, rows padded to
    eight with max-key rows, the trimmed sum a reduction over sublanes,
    here spelled in the order the TPU gives it): kept, interpreted, as
    the oracle of the folded body that replaced it in the library."""
    from jax import lax
    from jax.experimental import pallas as pl

    from byzpy_tpu.ops import pallas_kernels as pk

    K, n, d = xs.shape
    n_pad = max(8, -(-n // 8) * 8)
    d_pad = -(-d // tile) * tile

    def kernel(x_ref, o_ref):
        blk = x_ref[0].astype(jnp.float32)
        keys = pk._float_sort_keys(blk)
        row_i = lax.broadcasted_iota(jnp.int32, keys.shape, 0)
        keys = jnp.where(row_i >= n, jnp.iinfo(jnp.int32).max, keys)
        srt = pk._batcher_sort_rows(keys, n_pad)
        if mode == "median":
            lo, hi = (n - 1) // 2, n // 2
            vlo = pk._keys_to_float(srt[lo], jnp.float32).astype(o_ref.dtype)
            vhi = pk._keys_to_float(srt[hi], jnp.float32).astype(o_ref.dtype)
            out = (vlo + vhi) * jnp.asarray(0.5, o_ref.dtype)
            has_nan = srt[n - 1] > pk._INF_KEY
            out = jnp.where(has_nan, jnp.asarray(jnp.nan, o_ref.dtype), out)
        else:
            vals = pk._keys_to_float(srt[f:n - f], jnp.float32)
            out = (sum_rows(vals) / (n - 2 * f)).astype(o_ref.dtype)
        o_ref[0] = out[None, :]

    xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, d_pad // tile),
        in_specs=[pl.BlockSpec((1, n_pad, tile), lambda k, c: (k, 0, c))],
        out_specs=pl.BlockSpec((1, 1, tile), lambda k, c: (k, 0, c)),
        interpret=True,
    )(xp)
    return out[:, 0, :d]


def _rows_with_every_hard_case(n, d, dtype):
    """Seeded rows with NaN, both infinities, -0.0 beside +0.0, -0.0
    alone and tied columns, some columns holding several of them."""
    a = np.asarray(jax.random.normal(jax.random.PRNGKey(n * 7919 + d), (2, n, d))).copy()
    a = a.astype(np.float32) * 4
    a[0, 1 % n, ::5] = np.inf
    a[0, 2 % n, ::7] = -np.inf
    a[0, 0, ::9] = np.nan
    a[1, :, 3] = np.nan  # a column of nothing else
    a[0, :, 4] = 1.5  # every worker tied
    a[0, : n // 2, 6] = -0.0
    a[0, n // 2:, 6] = 0.0
    a[1, :, 8] = np.where(np.arange(n) % 2, -0.0, 0.0)
    a[1, ::2, 10:20] = a[1, 1 % n, 10:20]  # ties among some workers
    a[1, :, 11] = np.inf
    # nothing but -0.0 in the window: the zeros a sublane reduction adds
    # past the end of a window that is not whole vregs turn its -0.0 into +0.0
    a[0, :, 12] = -0.0
    f = (n - 1) // 3
    a[1, :, 13] = np.where(np.arange(n) < f, -2.0, np.where(np.arange(n) >= n - f, 2.0, -0.0))
    return jnp.asarray(a).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [384, 300], ids=["aligned", "unaligned"])
@pytest.mark.parametrize("n", [3, 6, 8, 9, 16])
@pytest.mark.parametrize("mode", ["median", "trimmed"])
def test_folded_sorted_reduce_equals_the_sublane_row_body_bitwise(mode, n, d, dtype):
    """Same keys, same network, same order of the trimmed sum: the bits
    of the sublane-row kernel, NaN, infinities, signed zeros and ties
    included; and the values of a ``jnp.sort`` reference."""
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    xs = _rows_with_every_hard_case(n, d, dtype)
    f = (n - 1) // 3 if mode == "trimmed" else 0
    got = sorted_reduce_stream_pallas(xs, mode=mode, f=f, tile=128, interpret=True)
    want = _sublane_row_sorted_reduce(xs, mode=mode, f=f)
    assert got.dtype == want.dtype == dtype and got.shape == (2, d)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)).view(np.uint32),
        np.asarray(want.astype(jnp.float32)).view(np.uint32),
    )
    if mode == "median":
        ref = jnp.median(xs, axis=1)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(ref, np.float32))
    else:
        s = jnp.sort(xs.astype(jnp.float32), axis=1)
        with np.errstate(invalid="ignore"):
            ref = np.asarray(jnp.mean(s[:, f:n - f], axis=1).astype(dtype), np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), ref, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
            atol=1e-6)


# (n, f) -> the sign bit of the parent's trimmed mean of a window of
# nothing but -0.0, read on the v5e (PR 29, second session, 46 cases; a
# sample): -0.0 where the window is whole vregs (a multiple of eight rows,
# wherever it starts) or one row, +0.0 otherwise
_NEGATIVE_ZERO_WINDOWS = [(3, 0, 0), (3, 1, 1), (6, 2, 0), (7, 3, 1), (8, 0, 1), (8, 2, 0),
                          (9, 2, 0), (10, 1, 1), (12, 2, 1), (16, 3, 0), (16, 4, 1), (24, 6, 0)]


@pytest.mark.parametrize("n, f, negative", _NEGATIVE_ZERO_WINDOWS)
def test_trimmed_mean_of_nothing_but_negative_zero_has_the_tpus_sign(n, f, negative):
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    rows = np.where(np.arange(n) < f, -2.0, np.where(np.arange(n) >= n - f, 2.0, -0.0))
    xs = jnp.asarray(np.tile(rows[::-1, None].astype(np.float32), (1, 256)))[None]
    got = np.asarray(sorted_reduce_stream_pallas(xs, mode="trimmed", f=f, tile=128,
                                                 interpret=True))
    oracle = np.asarray(_sublane_row_sorted_reduce(xs, mode="trimmed", f=f))
    want = np.uint32(0x80000000 if negative else 0)
    assert set(got.view(np.uint32).ravel()) == {want} == set(oracle.view(np.uint32).ravel())


def test_trimmed_sum_is_within_an_ulp_or_two_of_a_top_to_bottom_sum():
    """The order is the TPU's sublane reduction, not the reading order:
    against the old body summed with the interpreter's ``jnp.sum`` the
    folded kernel differs in the last bits of some columns and nowhere by
    more."""
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    xs = jax.random.normal(jax.random.PRNGKey(29), (1, 16, 1024), jnp.float32) * 4
    got = np.asarray(sorted_reduce_stream_pallas(xs, mode="trimmed", f=3, tile=128,
                                                 interpret=True))
    top_down = np.asarray(_sublane_row_sorted_reduce(
        xs, mode="trimmed", f=3, sum_rows=lambda vals: jnp.sum(vals, axis=0)))
    assert np.any(got != top_down)
    np.testing.assert_allclose(got, top_down, rtol=0, atol=4 * np.spacing(np.float32(4.0)))


def test_sorted_reduce_wider_blocks_and_a_tile_that_is_no_lane_multiple():
    """A block of several sublane rows a worker (``tile / 128`` of them)
    gives the one-row block's bits; a tile that cannot be folded is
    refused before anything is traced."""
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    xs = _rows_with_every_hard_case(8, 4096, jnp.float32)
    want = sorted_reduce_stream_pallas(xs, mode="trimmed", f=2, tile=128, interpret=True)
    for tile in (1024, 2048, 4096):
        got = sorted_reduce_stream_pallas(xs, mode="trimmed", f=2, tile=tile, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
    with pytest.raises(ValueError, match="multiple of 128"):
        sorted_reduce_stream_pallas(xs, mode="median", tile=100, interpret=True)


@pytest.mark.parametrize("dtype, whole", [(jnp.float32, 1024), (jnp.bfloat16, 2048)],
                         ids=["f32", "bf16"])
def test_sorted_reduce_resolves_only_blocks_of_whole_native_tiles(monkeypatch, dtype, whole):
    """A block holds ``tile / 128`` sublane rows of each worker and Mosaic
    takes whole (8, 128) tiles of f32, (16, 128) of 16-bit rows: a
    narrower tile from the heuristic (an odd ``d``, many workers) is
    rounded up before the call where Mosaic compiles it; one the caller
    gives for Mosaic raises, unless it covers the array."""
    from byzpy_tpu.ops import pallas_kernels as pk

    seen = []

    def recording_call(xs, *, mode, f, tile, interpret):
        seen.append(tile)
        return jnp.zeros((xs.shape[0], xs.shape[2]), xs.dtype)

    monkeypatch.setattr(pk, "_sorted_reduce_stream_call", recording_call)
    xs = jnp.ones((1, 8, 4096), dtype)
    with pytest.raises(ValueError, match="whole tiles"):  # where a variable once put 512
        pk.sorted_reduce_stream_pallas(xs, mode="median", tile=512, interpret=False)
    pk.sorted_reduce_stream_pallas(  # 128 | d only
        jnp.ones((1, 8, 128 * 33), dtype), mode="median", interpret=False)
    pk.sorted_reduce_stream_pallas(
        jnp.ones((1, 128, 1024 * 3), dtype), mode="median", interpret=False)
    assert seen == [whole] * 2
    pk.sorted_reduce_stream_pallas(xs, mode="median", interpret=False)  # a wide tile stays
    pk.sorted_reduce_stream_pallas(  # and the interpreter takes what was resolved
        jnp.ones((1, 8, 128 * 33), dtype), mode="median", interpret=True)
    assert seen[-2:] == [4096, 128]
    # a caller's tile, for Mosaic: whole native tiles, or the whole array
    with pytest.raises(ValueError, match="whole tiles"):
        pk.sorted_reduce_stream_pallas(xs, mode="median", tile=whole // 2, interpret=False)
    pk.sorted_reduce_stream_pallas(xs, mode="median", tile=whole, interpret=False)
    pk.sorted_reduce_stream_pallas(xs[:, :, :512], mode="median", tile=512, interpret=False)
    pk.sorted_reduce_stream_pallas(xs, mode="median", tile=128, interpret=True)
    assert seen[-3:] == [whole, 512, 128]


# -- the attack formed in the kernel's body (PR 43) ---------------------------

def _formed_attacks():
    """Every attack ``ops/coordinatewise.py`` declares formable in the kernel,
    as the rounds hand it over: ``(name, attack, h for b, exact)``. A sign
    flip of the honest rows themselves sends a row a honest worker, so
    ``h == b``; a copy or a sign moves no bit, a mean is a sum in some order."""
    from byzpy_tpu.ops import attack_ops, coordinatewise

    made = {
        attack_ops.sign_flip: [
            ("signflip-mean", coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean"),
             lambda b: 6, False),
            ("signflip-rows", coordinatewise.RoundAttack(attack_ops.sign_flip), lambda b: b, True)],
        attack_ops.empire: [
            ("empire", coordinatewise.RoundAttack(attack_ops.empire, kwargs={"scale": -1.5}),
             lambda b: 6, False)],
        attack_ops.mimic: [
            ("mimic", coordinatewise.RoundAttack(attack_ops.mimic, kwargs={"epsilon": 1}),
             lambda b: 6, True)],
    }
    assert set(made) == set(coordinatewise.KERNEL_FORMED_ATTACKS)
    return [case for fn in sorted(made, key=lambda fn: fn.__name__) for case in made[fn]]


_ATTACKED_CASES = [
    pytest.param(mode, f, attack, h_of(b), b, exact, id=f"{mode}{f or ''}-{name}-b{b}")
    for mode, f in (("trimmed", 1), ("trimmed", 2), ("median", 0))
    for name, attack, h_of, exact in _formed_attacks()
    for b in (1, 2)
    if mode == "median" or 2 * f < h_of(b) + b
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("width, d", [(1024, 1024), (1000, 900)], ids=["tiles", "padded"])
@pytest.mark.parametrize("mode, f, attack, h, b, exact", _ATTACKED_CASES)
def test_rows_formed_in_the_kernel_equal_rows_written_to_the_matrix(
        mode, f, attack, h, b, exact, width, d, dtype):
    """The call with a prologue on the ``(h, width)`` honest rows against the
    round's own ``_byzantine_rows`` written beside them and the kernel on the
    ``(n, width)`` matrix. Where the attack is a copy or a sign the two agree
    bit for bit. Where it takes the mean of the h rows, the two add them in
    different orders: a sum of h values of magnitude at most ``a`` is off by
    at most ``(h - 1) eps a`` either way, the formed row by ``|scale|`` times
    that over h, and a sorted window's mean or midpoint moves by no more than
    its one moved entry; 16-bit rows round that row, and the result, once more
    (an ulp of bfloat16 each). A width that is not whole tiles is padded by the
    wrapper; the columns past ``d``, zero in every honest row, come out zero."""
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas
    from byzpy_tpu.parallel.ps import _byzantine_rows

    honest = 4 * jax.random.normal(jax.random.PRNGKey(43 * h + b), (h, width), jnp.float32)
    honest = jnp.where(jnp.arange(width) < d, honest, 0).astype(dtype)
    byz = _byzantine_rows(attack, honest, None, b, d)
    matrix = jnp.concatenate([honest, jnp.broadcast_to(byz, (b, width))])
    want = sorted_reduce_stream_pallas(matrix[None], mode=mode, f=f, tile=512, interpret=True)
    got = sorted_reduce_stream_pallas(
        honest[None], mode=mode, f=f, tile=512, interpret=True, attack=attack, b=b)
    assert got.shape == want.shape == (1, width) and got.dtype == want.dtype == dtype
    got, want = (np.asarray(a[0].astype(jnp.float32)) for a in (got, want))
    assert not got[d:].any() and not want[d:].any()
    if exact:
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    else:
        a = float(jnp.max(jnp.abs(honest.astype(jnp.float32))))
        scale = abs(attack.kwargs.get("scale", -1.0))
        atol = scale * a * ((h - 1) * np.finfo(np.float32).eps / h
                            + (2 * float(jnp.finfo(dtype).eps) if dtype != jnp.float32 else 0))
        np.testing.assert_allclose(got, want, rtol=0, atol=atol)
        assert np.mean(got == want) > 0.5  # and most columns agree to the bit


def test_an_attack_and_its_rows_go_together_and_a_wrong_count_is_refused():
    from byzpy_tpu.ops import attack_ops, coordinatewise
    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    honest = jnp.ones((1, 6, 256), jnp.float32)
    flip = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
    with pytest.raises(ValueError, match="go together"):
        sorted_reduce_stream_pallas(honest, mode="median", interpret=True, b=2)
    with pytest.raises(ValueError, match="go together"):
        sorted_reduce_stream_pallas(honest, mode="median", interpret=True, attack=flip)
    with pytest.raises(ValueError, match="0 <= 2f < n"):  # n counts the formed rows
        sorted_reduce_stream_pallas(honest, mode="trimmed", f=4, interpret=True, attack=flip, b=2)
    with pytest.raises(ValueError, match="one row, or one for each"):  # six rows for two workers
        sorted_reduce_stream_pallas(honest, mode="median", interpret=True,
                                    attack=coordinatewise.RoundAttack(attack_ops.sign_flip), b=2)


# sha256[:16] of the jaxpr of the call WITHOUT a prologue, taken on the commit
# before the kernel could form rows (6612e71) with `_plain_jaxpr_digest`: the
# wrapper, the jitted call, the block specs and the kernel's body, line for line
_PARENT_PLAIN_JAXPRS = {
    ("trimmed", 1, "float32", 1024): "9712d77464858849",
    ("trimmed", 2, "float32", 1000): "c6dfcdfead440c65",
    ("trimmed", 2, "bfloat16", 1024): "25d08499071462fe",
    ("median", 0, "float32", 1024): "98082fa9f0a376e8",
    ("median", 0, "float32", 1000): "6eba5459ac52a036",
    ("median", 0, "bfloat16", 1000): "209003bee3155ba4",
}


def _plain_jaxpr_digest(mode, f, dtype, d):
    import hashlib
    import re

    from byzpy_tpu.ops.pallas_kernels import sorted_reduce_stream_pallas

    text = str(jax.make_jaxpr(lambda a: sorted_reduce_stream_pallas(
        a, mode=mode, f=f, tile=512 if d == 1024 else 128, interpret=True))(
            jnp.zeros((1, 8, d), dtype)))
    text = re.sub(r" at [^\s]*pallas_kernels\.py:\d+", "", text)  # where the kernel stands
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("mode, f, dtype, d", sorted(_PARENT_PLAIN_JAXPRS))
def test_without_a_prologue_the_kernel_traces_to_what_it_traced_to(mode, f, dtype, d):
    assert _plain_jaxpr_digest(mode, f, dtype, d) == _PARENT_PLAIN_JAXPRS[mode, f, dtype, d]


# ---------------------------------------------------------------------------
# Fused MeaMed kernel
# ---------------------------------------------------------------------------


def _meamed_oracle(x, f):
    """Gather-semantics oracle (ref mean_of_medians: keep the n-f values
    closest to the median per coordinate, stable ties by node order)."""
    x = np.asarray(x, np.float64)
    n, d = x.shape
    k = n - f
    med = np.median(x, axis=0)  # NaN if the column contains NaN
    dev = np.abs(x - med[None, :])
    out = np.empty(d)
    for j in range(d):
        if np.isnan(med[j]):
            out[j] = np.nan
            continue
        order = np.argsort(dev[:, j], kind="stable")[:k]
        if np.isnan(dev[order, j]).any():
            out[j] = np.nan
            continue
        out[j] = x[order, j].mean()
    return out


@pytest.mark.parametrize("n,d", [(8, 256), (13, 300), (10, 700)])
def test_meamed_pallas_matches_oracle(n, d):
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    f = (n - 1) // 3
    x = jax.random.normal(jax.random.PRNGKey(n * d), (n, d), jnp.float32) * 4
    got = meamed_stream_pallas(x[None], f=f, tile=128, interpret=True)[0]
    np.testing.assert_allclose(
        np.asarray(got), _meamed_oracle(x, f), rtol=1e-5, atol=1e-6
    )


def test_meamed_pallas_matches_xla_path_with_nonfinite():
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    a = np.asarray(
        jax.random.normal(jax.random.PRNGKey(3), (12, 384), jnp.float32)
    ).copy()
    a[2] = np.inf
    a[5, ::7] = np.nan
    x = jnp.asarray(a)
    got = meamed_stream_pallas(x[None], f=3, tile=128, interpret=True)[0]
    import os

    prev = os.environ.get("BYZPY_TPU_PALLAS")
    os.environ["BYZPY_TPU_PALLAS"] = "0"
    try:
        want = robust.mean_of_medians(x, f=3)
    finally:
        # leave the variable as it was found: a later test on this worker
        # (tests/test_chip_smoke.py) asserts that nothing leaked one
        if prev is None:
            del os.environ["BYZPY_TPU_PALLAS"]
        else:
            os.environ["BYZPY_TPU_PALLAS"] = prev
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6, equal_nan=True
    )


def test_meamed_pallas_stable_ties_match_node_order_rule():
    """Quantized values force exact ties in |x - med|, including the
    adversarial med-r / med+r pairs (equal deviation, DIFFERENT values):
    the single-phase window kernel must reproduce the stable node-order
    tie rule exactly, not just pick any k-closest set."""
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    rng = np.random.default_rng(11)
    for trial in range(10):
        n = int(rng.integers(5, 14))
        f = int(rng.integers(0, n))
        x = (np.round(rng.normal(size=(n, 256)) * 2) / 2).astype(np.float32)
        got = meamed_stream_pallas(
            jnp.asarray(x)[None], f=f, tile=128, interpret=True
        )[0]
        np.testing.assert_allclose(
            np.asarray(got), _meamed_oracle(x, f), rtol=1e-5, atol=1e-6,
            err_msg=f"trial={trial} n={n} f={f}",
        )


def test_meamed_median_near_float_max_no_overflow():
    """Odd-n median must be the middle element itself and even-n must
    average as 0.5a + 0.5b: forming a+b first overflows f32 for
    near-max values where the true median is representable (review
    finding, round 5). k=1 isolates the median path from the
    (independent, pre-existing) selection-sum overflow."""
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    x = jnp.asarray(np.full((3, 4), 3e38, np.float32))
    np.testing.assert_allclose(
        np.asarray(robust.mean_of_medians(x, f=2)), 3e38, rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(
            meamed_stream_pallas(x[None], f=2, tile=128, interpret=True)[0]
        ),
        3e38, rtol=1e-6,
    )
    x2 = jnp.asarray(
        np.array([[2e38], [3e38], [3.2e38], [3.3e38]], np.float32)
    )
    out = np.asarray(robust.mean_of_medians(x2, f=3))
    assert np.isfinite(out).all(), out
    got = np.asarray(
        meamed_stream_pallas(x2[None], f=3, tile=128, interpret=True)[0]
    )
    np.testing.assert_allclose(got, out, rtol=1e-6)


def test_meamed_stream_and_dispatch(monkeypatch):
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    xs = jax.random.normal(jax.random.PRNGKey(4), (3, 9, 260), jnp.float32)
    got = meamed_stream_pallas(xs, f=2, tile=128, interpret=True)
    want = np.stack([_meamed_oracle(xs[i], 2) for i in range(3)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)

    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    x = jax.random.normal(jax.random.PRNGKey(5), (11, 2176), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(robust.mean_of_medians(x, f=3)),
        _meamed_oracle(x, 3), rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# Fused weighted-center step (Weiszfeld / centered clipping)
# ---------------------------------------------------------------------------


def test_weighted_center_weiszfeld_step_matches_xla():
    from byzpy_tpu.ops.pallas_kernels import weighted_center_step_pallas

    x = jax.random.normal(jax.random.PRNGKey(0), (13, 300), jnp.float32)
    z = jnp.median(x, axis=0)
    got = weighted_center_step_pallas(x, z, mode="weiszfeld", tile=128,
                                      interpret=True)
    diff = x - z[None, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
    w = 1.0 / jnp.maximum(dist, 1e-12)
    want = jnp.sum(w[:, None] * x, axis=0) / jnp.sum(w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_weighted_center_clip_step_matches_xla():
    from byzpy_tpu.ops.pallas_kernels import weighted_center_step_pallas

    x = jax.random.normal(jax.random.PRNGKey(1), (10, 260), jnp.float32) * 3
    v = jnp.mean(x, axis=0)
    got = weighted_center_step_pallas(x, v, mode="clip", c_tau=1.5, tile=128,
                                      interpret=True)
    diff = x - v[None, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=1))
    scale = jnp.minimum(1.0, 1.5 / jnp.maximum(dist, 1e-12))
    want = v + jnp.mean(diff * scale[:, None], axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_geometric_median_and_clipping_dispatch_when_forced(monkeypatch):
    """Full iterative aggregators through the fused step (forced dispatch,
    interpret mode) must converge to the XLA-path results."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
    x = jax.random.normal(jax.random.PRNGKey(2), (11, 2304), jnp.float32)
    want_gm = robust.geometric_median(x, max_iter=64)
    want_cc = robust.centered_clipping(x, c_tau=2.0, M=6)
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    # fresh shape so the jit cache can't serve the XLA-path trace
    x2 = jnp.concatenate([x, x[:1]], axis=0)
    want_gm2 = None
    got_gm = robust.geometric_median(x2, max_iter=64)
    got_cc = robust.centered_clipping(x2, c_tau=2.0, M=6)
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
    # oracle at the same fresh shape via raw numpy Weiszfeld
    xa = np.asarray(x2, np.float64)
    z = np.median(xa, axis=0)
    for _ in range(64):
        dist = np.sqrt(((xa - z) ** 2).sum(1))
        w = 1.0 / np.maximum(dist, 1e-12)
        z_new = (w[:, None] * xa).sum(0) / w.sum()
        if np.sqrt(((z_new - z) ** 2).sum()) <= 1e-6:
            z = z_new
            break
        z = z_new
    np.testing.assert_allclose(np.asarray(got_gm), z, rtol=1e-4, atol=1e-4)
    v = xa.mean(0)
    for _ in range(6):
        dist = np.sqrt(((xa - v) ** 2).sum(1))
        s = np.minimum(1.0, 2.0 / np.maximum(dist, 1e-12))
        v = v + ((xa - v) * s[:, None]).mean(0)
    np.testing.assert_allclose(np.asarray(got_cc), v, rtol=1e-4, atol=1e-4)


def test_sort_tile_budget_respects_scoped_vmem():
    """Regression: the sort-based kernels' working set is ~8-9x the input
    block (f32 up-cast + int32 keys + Batcher stage temporaries), and
    Mosaic's scoped-VMEM limit is 16 MiB — a 64x16384 tile measured a
    34.35 MiB scoped stack on v5e (compile-time OOM that interpret mode
    never sees). The budget must keep 10 f32 copies of the block under
    ~14 MiB for every (d, n_pad) the dispatch gates admit."""
    from byzpy_tpu.ops.pallas_kernels import (
        MAX_NETWORK_ROWS, _auto_sort_tile, _round_up, _SUBLANES,
    )

    for n in (8, 16, 17, 24, 64, 100, MAX_NETWORK_ROWS):
        n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
        for d in (65_536, 262_144, 1_048_576, 2_097_152):
            tile = _auto_sort_tile(d, n_pad)
            assert 10 * n_pad * tile * 4 <= 14 * 1024 * 1024, (n, d, tile)
            assert d % tile == 0
    # MeaMed also charges its (1, d) f32 median scratch to the budget
    tile = _auto_sort_tile(1_048_576, 64, extra_bytes=4 * 1_048_576)
    assert 10 * 64 * tile * 4 + 4 * 1_048_576 <= 14 * 1024 * 1024


def test_phase_parked_kernels_interpret_parity():
    """The ``c * p`` phase-parked output maps (no HBM output traffic
    during the Gram/median sweep) must leave no unwritten garbage blocks
    at any (K, C) combination, including C == 1 where phase 0 and phase 1
    share a single block index."""
    from byzpy_tpu.ops import pallas_kernels as pk
    from byzpy_tpu.ops import preagg

    for d in (256, 1024):  # C = 2 and 8 at tile=128; plus C=1 via tile=d
        for tile in (128, d):
            xs = jax.random.normal(jax.random.PRNGKey(3), (3, 10, d))
            got = pk.nnm_stream_pallas(xs, f=3, tile=tile, interpret=True)
            want = jax.vmap(lambda x: preagg.nnm(x, f=3))(xs)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
            )
            got = pk.selection_mean_stream_pallas(
                xs, f=3, q=4, tile=tile, interpret=True
            )
            want = jax.vmap(
                lambda x: robust.ranked_mean(x, robust.krum_scores(x, f=3), 4)
            )(xs)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
            )


class TestFusedNnmSelection:
    """nnm_selection_mean_stream_pallas == nnm -> selection two-step."""

    @staticmethod
    def _oracle(x, f_nnm, f, q):
        from byzpy_tpu.ops import preagg

        mixed = preagg.nnm(x, f=f_nnm)
        return robust.ranked_mean(mixed, robust.krum_scores(mixed, f=f), q)

    def test_matches_two_step_composition(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        for seed, (n, d, f_nnm, f, q) in enumerate(
            [(10, 512, 3, 2, 4), (16, 1024, 4, 3, 5), (9, 384, 2, 2, 3)]
        ):
            x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
            got = nnm_selection_mean_stream_pallas(
                x[None], f_nnm=f_nnm, f=f, q=q, interpret=True
            )[0]
            want = self._oracle(x, f_nnm, f, q)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
            )

    def test_stream_matches_vmapped_oracle(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        xs = jax.random.normal(jax.random.PRNGKey(7), (4, 12, 640))
        got = nnm_selection_mean_stream_pallas(
            xs, f_nnm=3, f=2, q=4, interpret=True
        )
        want = jnp.stack([self._oracle(xs[k], 3, 2, 4) for k in range(4)])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_ops_wrappers_dispatch_and_match(self, monkeypatch):
        # oracles come from the UN-JITTED two-step composition — the
        # public ops are jax.jit functions whose trace cache does not key
        # on the env flag, so flipping BYZPY_TPU_PALLAS between calls of
        # the SAME wrapper would compare the kernel against itself
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
        x = jax.random.normal(jax.random.PRNGKey(3), (12, 2048))
        got = robust.nnm_multi_krum(x, f_nnm=3, f=2, q=4)
        want = self._oracle(x, 3, 2, 4)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        xs = jnp.stack([x, x * 0.5 + 1.0])
        got = robust.nnm_multi_krum_stream(xs, f_nnm=3, f=2, q=4)
        want = jnp.stack([self._oracle(xs[k], 3, 2, 4) for k in range(2)])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        # and the gated-off path agrees with the same oracle at a FRESH
        # shape (no cache reuse)
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
        x2 = jax.random.normal(jax.random.PRNGKey(5), (11, 1536))
        got = robust.nnm_multi_krum(x2, f_nnm=3, f=2, q=4)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._oracle(x2, 3, 2, 4)),
            rtol=2e-5, atol=2e-5,
        )

    def test_bf16_close_to_f32_composition(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        x32 = jax.random.normal(jax.random.PRNGKey(13), (10, 1024))
        x16 = x32.astype(jnp.bfloat16)
        got = nnm_selection_mean_stream_pallas(
            x16[None], f_nnm=3, f=2, q=4, interpret=True
        )[0]
        assert got.dtype == jnp.bfloat16
        # scored from the f32 derived Gram: close to the f32 analytic
        # composition within bf16 rounding of the inputs (see the kernel
        # docstring for the documented divergence from the dtype-rounded
        # two-step on near-tie selections)
        want = self._oracle(x32, 3, 2, 4)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want), rtol=5e-2,
            atol=5e-2,
        )

    def test_nonfinite_rows_follow_two_step_rule(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        n, d = 12, 512
        x = np.asarray(
            jax.random.normal(jax.random.PRNGKey(11), (n, d))
        ).copy()
        x[2] = np.inf  # tainted source
        x = jnp.asarray(x)
        got = nnm_selection_mean_stream_pallas(
            x[None], f_nnm=3, f=2, q=4, interpret=True
        )[0]
        want = self._oracle(x, 3, 2, 4)
        if bool(jnp.isnan(want).any()):
            assert bool(jnp.isnan(got).any())
        else:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
            )

    def test_all_sources_tainted_outputs_nan(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        x = jnp.full((8, 256), jnp.inf)
        got = nnm_selection_mean_stream_pallas(
            x[None], f_nnm=2, f=1, q=2, interpret=True
        )[0]
        assert bool(jnp.isnan(got).all())

    def test_validation(self):
        from byzpy_tpu.ops.pallas_kernels import (
            nnm_selection_mean_stream_pallas,
        )

        xs = jnp.zeros((1, 8, 256))
        with pytest.raises(ValueError, match="f_nnm"):
            nnm_selection_mean_stream_pallas(xs, f_nnm=8, f=1, q=2)
        with pytest.raises(ValueError, match="krum"):
            nnm_selection_mean_stream_pallas(xs, f_nnm=2, f=7, q=2)
        with pytest.raises(ValueError, match="unknown mode"):
            nnm_selection_mean_stream_pallas(
                xs, f_nnm=2, f=1, q=2, mode="bogus"
            )


class TestFusedClipSelection:
    """clip_selection_mean_stream_pallas == clip_rows -> selection."""

    @staticmethod
    def _oracle(x, tau, f, q):
        from byzpy_tpu.ops.preagg import clip_rows

        clipped = clip_rows(x, threshold=tau)
        return robust.ranked_mean(clipped, robust.krum_scores(clipped, f=f), q)

    def test_matches_two_step_composition(self):
        from byzpy_tpu.ops.pallas_kernels import (
            clip_selection_mean_stream_pallas,
        )

        for seed, (n, d, tau, f, q) in enumerate(
            [(10, 512, 8.0, 2, 4), (16, 1024, 20.0, 3, 5), (9, 384, 1.5, 2, 3)]
        ):
            # mixed magnitudes so some rows clip and some do not
            x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
            x = x.at[::3].multiply(10.0)
            got = clip_selection_mean_stream_pallas(
                x[None], tau=tau, f=f, q=q, interpret=True
            )[0]
            want = self._oracle(x, tau, f, q)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_stream_and_ops_wrappers(self, monkeypatch):
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
        xs = jax.random.normal(jax.random.PRNGKey(7), (3, 12, 640))
        xs = xs.at[:, ::2].multiply(7.0)
        got = robust.clipped_multi_krum_stream(xs, tau=5.0, f=2, q=4)
        want = jnp.stack([self._oracle(xs[k], 5.0, 2, 4) for k in range(3)])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
        got1 = robust.clipped_multi_krum(xs[0], tau=5.0, f=2, q=4)
        np.testing.assert_allclose(
            np.asarray(got1), np.asarray(want[0]), rtol=2e-4, atol=2e-4
        )
        # gated-off path at a fresh shape agrees with the same oracle
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
        x2 = jax.random.normal(jax.random.PRNGKey(9), (11, 768)) * 4.0
        np.testing.assert_allclose(
            np.asarray(robust.clipped_multi_krum(x2, tau=5.0, f=2, q=4)),
            np.asarray(self._oracle(x2, 5.0, 2, 4)),
            rtol=2e-4, atol=2e-4,
        )

    def test_nonfinite_norm_rows_rank_last(self):
        from byzpy_tpu.ops.pallas_kernels import (
            clip_selection_mean_stream_pallas,
        )

        n, d = 12, 512
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (n, d))).copy()
        x[4] = np.inf   # inf norm -> factor 0 -> NaN Gm row
        x[7, 0] = np.nan  # NaN norm -> NaN factor
        x = jnp.asarray(x)
        got = clip_selection_mean_stream_pallas(
            x[None], tau=3.0, f=2, q=4, interpret=True
        )[0]
        want = self._oracle(x, 3.0, 2, 4)
        if bool(jnp.isnan(want).any()):
            assert bool(jnp.isnan(got).any())
        else:
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_validation(self):
        from byzpy_tpu.ops.pallas_kernels import (
            clip_selection_mean_stream_pallas,
        )

        xs = jnp.zeros((1, 8, 256))
        with pytest.raises(ValueError, match="tau"):
            clip_selection_mean_stream_pallas(xs, tau=0.0, f=1, q=2)
        with pytest.raises(ValueError, match="krum"):
            clip_selection_mean_stream_pallas(xs, tau=1.0, f=7, q=2)


def test_clipped_multi_krum_validates_tau_on_both_paths(monkeypatch):
    x = jnp.ones((8, 256))
    for flag in ("0", "1"):
        monkeypatch.setenv("BYZPY_TPU_PALLAS", flag)
        with pytest.raises(ValueError, match="tau"):
            robust.clipped_multi_krum(x, tau=-1.0, f=1, q=2)
        with pytest.raises(ValueError, match="tau"):
            robust.clipped_multi_krum_stream(x[None], tau=0.0, f=1, q=2)


def test_clip_fused_finite_norm_overflow_documented_divergence():
    """Pin the documented deviation: a FINITE row whose squared norm
    overflows f32 is excluded by the fused kernel (inf norm is
    indistinguishable from inf data in the Gram), while the materialized
    path clips it to the all-zero vector. Both outputs must be finite
    and robust; they need not be equal."""
    from byzpy_tpu.ops.pallas_kernels import clip_selection_mean_stream_pallas
    from byzpy_tpu.ops.preagg import clip_rows

    n, d = 10, 512
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (n, d))).copy()
    x[3] = 1e18  # finite, but sum of squares overflows f32
    x = jnp.asarray(x)
    got = clip_selection_mean_stream_pallas(
        x[None], tau=3.0, f=2, q=4, interpret=True
    )[0]
    clipped = clip_rows(x, threshold=3.0)
    want = robust.ranked_mean(clipped, robust.krum_scores(clipped, f=2), 4)
    assert bool(jnp.isfinite(got).all())
    assert bool(jnp.isfinite(want).all())
    # the kernel's aggregate stays in the honest cluster's scale
    assert float(jnp.max(jnp.abs(got))) < 10.0


class TestFusedArcSelection:
    """arc_selection_mean_stream_pallas == arc_clip -> selection."""

    @staticmethod
    def _oracle(x, f_arc, f, q):
        from byzpy_tpu.ops.preagg import arc_clip

        clipped = arc_clip(x, f=f_arc)
        return robust.ranked_mean(clipped, robust.krum_scores(clipped, f=f), q)

    def test_matches_two_step_composition(self):
        from byzpy_tpu.ops.pallas_kernels import (
            arc_selection_mean_stream_pallas,
        )

        for seed, (n, d, f_arc, f, q) in enumerate(
            [(10, 512, 2, 2, 4), (16, 1024, 4, 3, 5), (9, 384, 0, 2, 3),
             (12, 640, 5, 2, 4)]
        ):
            x = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
            x = x.at[::3].multiply(9.0)  # spread norms so ARC clips some
            got = arc_selection_mean_stream_pallas(
                x[None], f_arc=f_arc, f=f, q=q, interpret=True
            )[0]
            want = self._oracle(x, f_arc, f, q)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_ops_wrappers(self, monkeypatch):
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
        xs = jax.random.normal(jax.random.PRNGKey(3), (3, 12, 640))
        xs = xs.at[:, ::2].multiply(6.0)
        got = robust.arc_multi_krum_stream(xs, f_arc=3, f=2, q=4)
        want = jnp.stack([self._oracle(xs[k], 3, 2, 4) for k in range(3)])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
        x2 = jax.random.normal(jax.random.PRNGKey(5), (11, 768)) * 3.0
        np.testing.assert_allclose(
            np.asarray(robust.arc_multi_krum(x2, f_arc=3, f=2, q=4)),
            np.asarray(self._oracle(x2, 3, 2, 4)),
            rtol=2e-4, atol=2e-4,
        )

    def test_tie_norms_match_sort_semantics(self):
        from byzpy_tpu.ops.pallas_kernels import (
            arc_selection_mean_stream_pallas,
        )

        # identical norms everywhere: the threshold is that norm, nothing
        # clips, and the fused path must agree with the oracle exactly
        x = jax.random.normal(jax.random.PRNGKey(8), (8, 256))
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True) * 5.0
        got = arc_selection_mean_stream_pallas(
            x[None], f_arc=3, f=2, q=3, interpret=True
        )[0]
        want = self._oracle(x, 3, 2, 3)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_arc_multi_krum_validates_f_arc_on_both_paths(monkeypatch):
    x = jnp.ones((8, 256))
    for flag in ("0", "1"):
        monkeypatch.setenv("BYZPY_TPU_PALLAS", flag)
        with pytest.raises(ValueError, match="f_arc"):
            robust.arc_multi_krum(x, f_arc=-1, f=1, q=2)
        with pytest.raises(ValueError, match="f_arc"):
            robust.arc_multi_krum_stream(x[None], f_arc=9, f=1, q=2)


def test_meamed_majority_inf_column_selects_finite_rows():
    """A majority-inf column drives the median itself to inf; the window
    arithmetic (inf - inf = NaN) must not poison the cut — the k
    finite-deviation rows are selected, matching the gather oracle
    (review finding, round 5)."""
    from byzpy_tpu.ops.pallas_kernels import meamed_stream_pallas

    x = np.zeros((5, 256), np.float32)
    x[0], x[1] = 0.0, 1.0
    x[2:] = np.inf
    want = np.full(256, 0.5, np.float32)  # mean of the two finite rows
    got_xla = np.asarray(robust.mean_of_medians(jnp.asarray(x), f=3))
    np.testing.assert_allclose(got_xla, want, rtol=1e-6)
    got_k = np.asarray(
        meamed_stream_pallas(jnp.asarray(x)[None], f=3, tile=128,
                             interpret=True)[0]
    )
    np.testing.assert_allclose(got_k, want, rtol=1e-6)
    # fewer than k finite-or-inf deviations (NaN med) still yields NaN
    x2 = x.copy()
    x2[0, :] = np.nan
    out2 = np.asarray(robust.mean_of_medians(jnp.asarray(x2), f=3))
    assert np.isnan(out2).all()


def test_meamed_integer_input_promotes_like_median():
    """Integer gradients must promote to float (jnp.median semantics) —
    a 0.5 literal in an int dtype silently truncated the midpoint to
    zero (review finding, round 5)."""
    x = jnp.asarray(np.array([[100], [110], [120], [2]], np.int32))
    out = np.asarray(robust.mean_of_medians(x, f=1))
    # med = 110, deviations [10, 0, 10, 108]; keep 3 closest -> 110
    np.testing.assert_allclose(out, [110.0], rtol=1e-6)
