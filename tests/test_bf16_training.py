"""bf16 gradients as a first-class robust-training mode.

The 150k grads/sec headline is a bf16 kernel number; these tests pin the
TRAINING-path semantics around it: per-node gradients cast to bfloat16
before attack + robust aggregation, f32 master params/optimizer, and a
trajectory that stays close to the f32 one (robustness survives the
cast).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import ShardedDataset, mnist_mlp, synthetic_classification
from byzpy_tpu.ops import attack_ops, robust
from byzpy_tpu.parallel import PSStepConfig, jit_ps_train_step

N, B = 8, 16


@pytest.fixture(scope="module")
def setup():
    bundle = mnist_mlp(hidden=16)
    x, y = synthetic_classification(n_samples=N * B, seed=11)
    ds = ShardedDataset(x, y, n_nodes=N)
    xs, ys = ds.stacked_shards()
    return bundle, xs, ys


def _flat(params):
    return np.concatenate(
        [np.ravel(leaf) for leaf in jax.tree_util.tree_leaves(params)]
    )


def test_bf16_grad_step_keeps_f32_master_params(setup):
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N, n_byzantine=2)
    step, opt0 = jit_ps_train_step(
        bundle, lambda m: robust.trimmed_mean(m, f=2), cfg,
        attack=lambda honest, key: attack_ops.empire(honest),
        grad_dtype=jnp.bfloat16, donate=False,
    )
    params, opt, metrics = step(
        bundle.params, opt0, xs, ys, jax.random.PRNGKey(0)
    )
    # master params and the applied update stay f32 end to end
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.float32, leaf.dtype
    assert np.isfinite(float(metrics["agg_grad_norm"]))
    assert not np.allclose(_flat(params), _flat(bundle.params))


def test_bf16_trajectory_tracks_f32_under_attack(setup):
    """5 rounds of trimmed-mean under empire: the bf16-gradient
    trajectory lands near the f32 one (bf16 has ~3 decimal digits; the
    robust statistics are medians/means over 64 values, so the update
    error stays at the rounding scale, not the attack scale)."""
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N, n_byzantine=2)

    def run(grad_dtype):
        step, opt0 = jit_ps_train_step(
            bundle, lambda m: robust.trimmed_mean(m, f=2), cfg,
            attack=lambda honest, key: attack_ops.empire(honest),
            grad_dtype=grad_dtype, donate=False,
        )
        params, opt = bundle.params, opt0
        for r in range(5):
            params, opt, _ = step(params, opt, xs, ys, jax.random.PRNGKey(r))
        return _flat(params)

    f32 = run(None)
    bf16 = run(jnp.bfloat16)
    # relative trajectory deviation bounded by bf16 rounding accumulation
    denom = np.maximum(np.abs(f32), 1e-3)
    assert np.max(np.abs(bf16 - f32) / denom) < 0.15, (
        np.max(np.abs(bf16 - f32) / denom)
    )


def test_robust_ops_bf16_in_bf16_out_f32_accumulation():
    """Aggregators keep bf16 payloads bf16 (half the HBM traffic) while
    reducing in f32: the bf16 result must match the f32 oracle to bf16
    resolution, far tighter than bf16-accumulation error would allow at
    n=64."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 4096), jnp.float32)
    xb = x.astype(jnp.bfloat16)
    for fn in (
        lambda m: robust.trimmed_mean(m, f=8),
        robust.coordinate_median,
        lambda m: robust.multi_krum(m, f=8, q=12),
    ):
        out_b = fn(xb)
        assert out_b.dtype == jnp.bfloat16, out_b.dtype
        oracle = fn(x)
        np.testing.assert_allclose(
            np.asarray(out_b, np.float32), np.asarray(oracle),
            rtol=2e-2, atol=2e-2,
        )


def test_study_config_plumbs_grad_dtype():
    from byzpy_tpu.models.data import load_digits_dataset
    from byzpy_tpu.models.nets import digits_mlp
    from byzpy_tpu.utils.robust_study import StudyConfig, run_cell

    cfg = StudyConfig(rounds=2, eval_every=1, grad_dtype="bfloat16")
    cell = run_cell(
        lambda: digits_mlp(seed=0),
        load_digits_dataset(seed=0),
        "trimmed_mean", "sign_flip", cfg,
    )
    assert 0.0 <= cell.final_accuracy <= 1.0
    assert np.isfinite(cell.final_accuracy)
