"""Parity pins for the roofline-driven hot-path surgery (ISSUE 2):

* ``sort_rows`` (the int32-key XLA sort serving every coordinate-wise
  fallback) matches ``jnp.sort``'s value ordering including non-finite
  values (bit-level divergence on signed zeros only, as documented);
* the conditional-mask selection fallback (``_selection_mean_xla``)
  matches the reference ``ranked_mean`` path for finite AND adversarial
  inputs across dtypes;
* the fused from-Gram Pallas pass matches the unfused
  ``multi_krum_from_gram`` (documented tolerance — score sums reduce in
  a different order), including through the streaming fold.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from byzpy_tpu.aggregators import MultiKrum
from byzpy_tpu.ops import pallas_kernels as pk
from byzpy_tpu.ops import robust


def _rand(n, d, dtype=jnp.float32, seed=0, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# sort_rows == jnp.sort, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_sort_rows_matches_jnp_sort(dtype):
    x = _rand(13, 999, dtype, seed=1, scale=10.0)
    np.testing.assert_array_equal(
        np.asarray(robust.sort_rows(x)), np.asarray(jnp.sort(x, axis=0))
    )


def test_sort_rows_nonfinite_and_signed_zero_order():
    x = np.random.default_rng(0).normal(size=(11, 64)).astype(np.float32)
    x[0, :8] = np.nan
    x[1, :8] = np.inf
    x[2, :8] = -np.inf
    x[3, :16] = 0.0
    x[4, :16] = -0.0
    xj = jnp.asarray(x)
    got = np.asarray(robust.sort_rows(xj))
    want = np.asarray(jnp.sort(xj, axis=0))
    # value equality (assert_array_equal would distinguish -0.0/+0.0)
    np.testing.assert_allclose(got, want, rtol=0, atol=0, equal_nan=True)
    # signed zeros: VALUES match (0.0 == -0.0); the key path orders
    # -0.0 strictly before +0.0 where the stable jnp.sort preserves
    # input order — the same documented bit-level-only divergence as
    # sort_columns. Pin the key path's order per column.
    for c in range(16):
        zero_rows = np.flatnonzero(got[:, c] == 0.0)
        assert zero_rows.size == 2
        assert np.signbit(got[zero_rows[0], c])
        assert not np.signbit(got[zero_rows[1], c])


def test_sort_rows_int_dtype_passthrough():
    x = jnp.asarray(np.random.default_rng(1).integers(-50, 50, (9, 33)),
                    jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(robust.sort_rows(x)), np.asarray(jnp.sort(x, axis=0))
    )


def test_coordinate_median_matches_jnp_median_fallback():
    for seed, poison in ((0, False), (1, True)):
        x = np.array(_rand(10, 257, seed=seed, scale=100.0))
        if poison:
            x[3, 5] = np.nan
            x[:, 6] = np.inf
        xj = jnp.asarray(x)
        np.testing.assert_array_equal(
            np.asarray(robust.coordinate_median(xj)),
            np.asarray(jnp.median(xj, axis=0)),
        )


# ---------------------------------------------------------------------------
# Conditional-mask selection == reference ranked_mean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multi_krum_fallback_matches_reference(dtype):
    x = _rand(23, 700, dtype, seed=2)
    got = robust.multi_krum(x, f=4, q=6)
    want = robust.ranked_mean(x, robust.krum_scores(x, f=4), 6)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("poison", ["nan", "inf", "overflow"])
def test_selection_fallbacks_route_adversarial_rows_to_masked_path(poison):
    x = np.array(_rand(17, 300, seed=3))
    val = {"nan": np.nan, "inf": np.inf, "overflow": 1e30}[poison]
    x[5] = val
    xj = jnp.asarray(x)
    got = np.asarray(robust.multi_krum(xj, f=3, q=4))
    want = np.asarray(robust.ranked_mean(xj, robust.krum_scores(xj, f=3), 4))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.isfinite(got).all()  # the bad row was never selected
    for fn, ref_scores in (
        (lambda a: robust.cge(a, f=3), lambda a: jnp.sum(a * a, axis=1)),
        (lambda a: robust.monna(a, f=3),
         lambda a: jnp.sum((a - a[0][None, :]) ** 2, axis=1)),
    ):
        got = np.asarray(fn(xj))
        want = np.asarray(robust.ranked_mean(xj, ref_scores(xj), 17 - 3))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   equal_nan=True)


# ---------------------------------------------------------------------------
# Fused from-Gram pass vs the unfused finalize, incl. the streaming fold
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_from_gram_kernel_matches_unfused(dtype):
    x = _rand(16, 384, dtype, seed=4)
    gram = robust.gram_matrix(x)
    got = pk.selection_mean_from_gram_pallas(
        x, gram, f=2, q=5, mode="krum", interpret=True
    )
    want = robust.multi_krum_from_gram(x, gram, f=2, q=5)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6,
    )
    # ... and both equal the from-scratch multi_krum on the same matrix
    direct = robust.multi_krum(x, f=2, q=5)
    np.testing.assert_allclose(
        np.asarray(want, np.float32), np.asarray(direct, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6,
    )


def test_from_gram_kernel_nan_scores_rank_last():
    x = np.array(_rand(12, 256, seed=5))
    x[2] = np.nan
    xj = jnp.asarray(x)
    gram = robust.gram_matrix(xj)
    got = np.asarray(pk.selection_mean_from_gram_pallas(
        xj, gram, f=2, q=4, mode="krum", interpret=True
    ))
    want = np.asarray(robust.multi_krum_from_gram(xj, gram, f=2, q=4))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.isfinite(got).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streaming_fold_matches_barrier_across_dtypes(dtype):
    """The donated-buffer Gram fold reproduces the barrier aggregate for
    any arrival order (documented float tolerance: the per-arrival
    matvec accumulates in arrival order)."""
    n, d = 11, 193
    rng = np.random.default_rng(7)
    grads = [
        jnp.asarray(rng.normal(size=d), jnp.float32).astype(dtype)
        for _ in range(n)
    ]
    agg = MultiKrum(f=2, q=3)
    ref = np.asarray(agg.aggregate(list(grads)), np.float32)
    for order in ([*range(n)], [*reversed(range(n))], [5, 0, 9, 2, 7, 1, 10, 4, 8, 3, 6]):
        state = agg.fold_init(n)
        for i in order:
            agg.fold(state, i, grads[i])
        out = np.asarray(agg.fold_finalize(state), np.float32)
        np.testing.assert_allclose(
            out, ref, rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
            atol=1e-6,
        )


def test_streaming_fold_partial_round():
    """Elastic partial rounds gather the arrived subset in canonical
    order — same result as the barrier over the arrived gradients."""
    n, d = 9, 120
    rng = np.random.default_rng(8)
    grads = [jnp.asarray(rng.normal(size=d), jnp.float32) for _ in range(n)]
    agg = MultiKrum(f=1, q=3)
    arrived = [7, 1, 4, 2, 8, 0]
    state = agg.fold_init(n)
    for i in arrived:
        agg.fold(state, i, grads[i])
    out = np.asarray(agg.fold_finalize(state))
    ref = np.asarray(agg.aggregate([grads[i] for i in sorted(arrived)]))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_fold_guards_slot_errors():
    agg = MultiKrum(f=1, q=2)
    state = agg.fold_init(4)
    g = jnp.ones((8,), jnp.float32)
    agg.fold(state, 1, g)
    with pytest.raises(ValueError, match="folded twice"):
        agg.fold(state, 1, g)
    with pytest.raises(IndexError):
        agg.fold(state, 4, g)
    with pytest.raises(ValueError, match="same length"):
        agg.fold(state, 2, jnp.ones((9,), jnp.float32))


