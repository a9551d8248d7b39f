"""The fused round writes its gradient matrix once.

``build_ps_train_step`` ravels each worker's row at the width the
aggregate's consumer reads in place (``pallas_kernels.aligned_width``),
sets the byzantine rows into the stack instead of rebuilding it, and
cuts the aggregate's zero tail before the update. On the CPU the width is
``d`` and only the in-place row write differs from a round that
concatenates: bit-identical. With the width forced wider, the XLA route
sees the zero-tailed matrix every backend's wide path would.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.ops import attack_ops, pallas_kernels, preagg, robust
from byzpy_tpu.parallel.ps import (
    PSStepConfig,
    build_ps_train_step,
    jit_ps_train_step,
)
from byzpy_tpu.utils.trees import ravel_pytree_fn, tree_size

N, B, STEPS = 8, 2, 3
CFG = PSStepConfig(n_nodes=N, n_byzantine=B, learning_rate=0.05, momentum=0.9)


def _mean(x):
    return jnp.mean(x, axis=0)


AGGREGATORS = {
    "trimmed_mean": partial(robust.trimmed_mean, f=2),
    "multi_krum": partial(robust.multi_krum, f=2, q=4),
    "median": robust.coordinate_median,
    "mean": _mean,
}
COORDINATE_WISE = {"trimmed_mean", "median", "mean"}


def _sign_flip(honest, key):
    return attack_ops.sign_flip(jnp.mean(honest, axis=0))


def _empire(honest, key):
    return attack_ops.empire(honest)


def _noise(honest, key):
    """Additive Gaussian noise, non-zero in every column it is asked for
    (so in a pad tail too). Drawn at one fixed length and cut, so that the
    first d columns do not depend on the matrix's width."""
    width = honest.shape[1]
    noise = attack_ops.gaussian(key, (1 << 14,), sigma=0.1)[:width]
    return jnp.mean(honest, axis=0) + noise


ATTACKS = {"sign_flip": _sign_flip, "empire": _empire, "noise": _noise}


@pytest.fixture(scope="module")
def bundle():
    return mnist_mlp(0, hidden=16)


@pytest.fixture(scope="module")
def batches():
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    xs = jax.random.normal(kx, (STEPS, N, 4, 28, 28, 1), jnp.float32)
    ys = jax.random.randint(ky, (STEPS, N, 4), 0, 10)
    keys = jax.random.split(jax.random.PRNGKey(11), STEPS)
    return xs, ys, keys


def _concatenating_step(bundle, aggregate, attack):
    """The round as it was before the matrix was written once: rows
    ravelled at width d, the matrix rebuilt by ``concatenate``."""
    opt = optax.sgd(CFG.learning_rate, momentum=CFG.momentum)
    ravel, unravel = ravel_pytree_fn(bundle.params)
    h = CFG.n_honest

    def per_node_grad(params, x, y):
        loss, g = jax.value_and_grad(bundle.loss_fn)(params, x, y)
        return loss, ravel(g)

    def step(params, opt_state, xs, ys, key):
        losses, grads = jax.vmap(per_node_grad, in_axes=(None, 0, 0))(params, xs, ys)
        honest = grads[:h]
        byz = jnp.broadcast_to(attack(honest, key), (B, honest.shape[1]))
        agg = aggregate(jnp.concatenate([honest, byz.astype(honest.dtype)], axis=0))
        updates, opt_state = opt.update(unravel(agg), opt_state, params)
        metrics = {"honest_loss": jnp.mean(losses[:h]),
                   "agg_grad_norm": jnp.sqrt(jnp.sum(jnp.square(agg)))}
        return optax.apply_updates(params, updates), opt_state, metrics

    return jax.jit(step), opt.init(bundle.params)


def _drive(step, params, opt_state, batches, state=None):
    """Flat parameters and aggregate norms after ``STEPS`` steps; the
    last optimizer state is appended to ``state`` where one is given."""
    xs, ys, keys = batches
    norms = []
    for i in range(STEPS):
        params, opt_state, metrics = step(params, opt_state, xs[i], ys[i], keys[i])
        norms.append(np.asarray(metrics["agg_grad_norm"]))
    if state is not None:
        state.append(opt_state)
    flat = np.concatenate([np.asarray(v).ravel() for v in jax.tree_util.tree_leaves(params)])
    return flat, np.asarray(norms)


def _matrix_the_aggregate_sees(bundle, attack, batches):
    """One round run eagerly (no jit), so that the aggregate is handed a
    real array: the matrix of the first step."""
    seen = []

    def recording_mean(x):
        seen.append(np.asarray(x))
        return jnp.mean(x, axis=0)

    step, opt_state = build_ps_train_step(bundle, recording_mean, CFG, attack=attack)
    xs, ys, keys = batches
    step(bundle.params, opt_state, xs[0], ys[0], keys[0])
    (matrix,) = seen
    return matrix


def _round_to_128(n, d):
    return -(-d // 128) * 128


# -- (i) on the CPU the round equals the concatenating round, bit for bit ----


@pytest.mark.parametrize("attack", sorted(ATTACKS))
@pytest.mark.parametrize("agg", sorted(AGGREGATORS))
def test_three_steps_equal_the_concatenating_round_bitwise(bundle, batches, agg, attack):
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS[agg], CFG, attack=ATTACKS[attack], donate=False)
    ref_step, ref_opt = _concatenating_step(bundle, AGGREGATORS[agg], ATTACKS[attack])
    got, got_norms = _drive(step, bundle.params, opt_state, batches)
    want, want_norms = _drive(ref_step, bundle.params, ref_opt, batches)
    if agg == "mean":
        # XLA folds a plain row mean into whatever builds its operand, and
        # adds the eight rows in another order over a concatenate than over
        # a buffer: the last bit, nothing the round decides
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got_norms, want_norms, rtol=1e-6)
    else:
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_norms, want_norms)


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_the_matrix_the_aggregate_sees_equals_the_concatenated_one_bitwise(
        bundle, batches, attack):
    """Whatever the aggregator: eagerly, row for row and bit for bit."""
    xs, ys, keys = batches
    matrix = _matrix_the_aggregate_sees(bundle, ATTACKS[attack], batches)
    ravel, _ = ravel_pytree_fn(bundle.params)
    grads = jax.vmap(lambda x, y: ravel(jax.grad(bundle.loss_fn)(bundle.params, x, y)))(
        xs[0], ys[0])
    honest = grads[: CFG.n_honest]
    byz = jnp.broadcast_to(ATTACKS[attack](honest, keys[0]), (B, honest.shape[1]))
    np.testing.assert_array_equal(matrix, np.asarray(jnp.concatenate([honest, byz])))


# -- (ii) the wide path, on the XLA route ------------------------------------


@pytest.mark.parametrize("attack", sorted(ATTACKS))
@pytest.mark.parametrize("agg", sorted(AGGREGATORS))
def test_wide_rows_give_the_unpadded_rounds_parameters(monkeypatch, bundle, batches, agg, attack):
    narrow_step, narrow_opt = jit_ps_train_step(
        bundle, AGGREGATORS[agg], CFG, attack=ATTACKS[attack], donate=False)
    want, want_norms = _drive(narrow_step, bundle.params, narrow_opt, batches)
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_128)
    wide_step, wide_opt = jit_ps_train_step(
        bundle, AGGREGATORS[agg], CFG, attack=ATTACKS[attack], donate=False)
    got, got_norms = _drive(wide_step, bundle.params, wide_opt, batches)
    if agg in COORDINATE_WISE:
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_norms, want_norms)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(got_norms, want_norms, rtol=1e-6)


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_pad_tail_is_zero_in_every_row_the_aggregate_sees(monkeypatch, bundle, batches, attack):
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_128)
    d = tree_size(bundle.params)
    matrix = _matrix_the_aggregate_sees(bundle, ATTACKS[attack], batches)
    keys = batches[2]
    assert matrix.shape == (N, _round_to_128(N, d)) and matrix.shape[1] > d
    assert np.count_nonzero(matrix[:, d:]) == 0
    assert np.count_nonzero(matrix[CFG.n_honest:, :d]) > 0  # the attack's rows are there
    if attack == "noise":  # and the attack itself did write into the tail
        honest = jnp.asarray(matrix[: CFG.n_honest])
        assert np.count_nonzero(np.asarray(_noise(honest, keys[0]))[d:]) > 0


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _matrix_rebuilds(step, args, shape):
    """``[(primitive, scope)]`` of the step's ``concatenate`` and ``pad``
    equations whose result is a whole ``shape`` matrix."""
    found = []
    for eqn in _equations(jax.make_jaxpr(step)(*args).jaxpr):
        if eqn.primitive.name in ("concatenate", "pad") and any(
                getattr(v.aval, "shape", None) == shape for v in eqn.outvars):
            found.append((eqn.primitive.name, str(eqn.source_info.name_stack)))
    return found


@pytest.mark.parametrize("wide", [False, True])
def test_the_matrix_is_built_by_the_ravel_alone(monkeypatch, bundle, batches, wide):
    """Each honest worker's row is made by one ``concatenate``, the
    ravel's (leaves and zero tail together), inside the one-device loop
    in ``round.fwdbwd``; the loop stacks the rows; the one op that makes
    the n-row matrix of the stack is the ``pad`` the byzantine rows are
    selected into (one fused pass on the chip); nothing concatenates or
    pads rows or matrix again."""
    if wide:
        monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_128)
    d = tree_size(bundle.params)
    width = _round_to_128(N, d) if wide else d
    step, opt_state = build_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], CFG, attack=ATTACKS["noise"])
    xs, ys, keys = batches
    args = (bundle.params, opt_state, xs[0], ys[0], keys[0])
    rows = _matrix_rebuilds(step, args, (width,))
    assert [name for name, _ in rows] == ["concatenate"]
    assert _matrix_rebuilds(step, args, (CFG.n_honest, width)) == []
    rebuilds = _matrix_rebuilds(step, args, (N, width))
    assert [name for name, _ in rebuilds] == ["pad"]
    assert "round.build_matrix" in rebuilds[0][1]
    # the reference round above does rebuild it: the probe sees a second one
    ref_step, ref_opt = _concatenating_step(bundle, AGGREGATORS["trimmed_mean"], ATTACKS["noise"])
    ref = _matrix_rebuilds(ref_step, (bundle.params, ref_opt, xs[0], ys[0], keys[0]), (N, d))
    assert [name for name, _ in ref] == ["concatenate", "concatenate"]


def test_sharded_update_on_one_device_carries_its_state_at_the_same_width(
        monkeypatch, bundle, batches):
    """One padded width serves the matrix and the sharded update's flat
    state: forced on without a mesh, the state is as wide as the rows."""
    narrow_step, narrow_opt = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], CFG, attack=_sign_flip, donate=False,
        sharded_update="on")
    want, _ = _drive(narrow_step, bundle.params, narrow_opt, batches)
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_128)
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], CFG, attack=_sign_flip, donate=False,
        sharded_update="on")
    d = tree_size(bundle.params)
    assert opt_state[0].shape == (_round_to_128(N, d),)
    last = []
    got, _ = _drive(step, bundle.params, opt_state, batches, state=last)
    # the flat update is compiled for another length: the last bit may differ
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    flat, inner = last[0]
    assert np.count_nonzero(np.asarray(flat)[d:]) == 0
    for leaf in jax.tree_util.tree_leaves(inner):
        if leaf.shape == flat.shape:
            assert np.count_nonzero(np.asarray(leaf)[d:]) == 0


# -- (iii) the width the dispatch layer publishes ------------------------------

RESNET18_D = 11_173_962


def test_aligned_width_is_d_where_the_kernels_will_not_serve(monkeypatch):
    monkeypatch.delenv("BYZPY_TPU_PALLAS", raising=False)
    assert pallas_kernels.aligned_width(8, RESNET18_D) == RESNET18_D  # CPU
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "0")
    assert pallas_kernels.aligned_width(8, RESNET18_D) == RESNET18_D
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    too_many_rows = pallas_kernels.MAX_NETWORK_ROWS + 1
    assert pallas_kernels.aligned_width(too_many_rows, RESNET18_D) == RESNET18_D


@pytest.mark.parametrize("d", [1 << 20, 3 * 16384])
def test_aligned_width_keeps_an_aligned_d(monkeypatch, d):
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    assert pallas_kernels.aligned_width(64, d) == d


@pytest.mark.parametrize("n", [8, 16, 64, 128])
def test_aligned_width_gives_both_tile_heuristics_an_exact_divisor(monkeypatch, n):
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    width = pallas_kernels.aligned_width(n, RESNET18_D)
    assert RESNET18_D <= width < RESNET18_D + 16384 and width % 16384 == 0
    n_pad = max(8, -(-n // 8) * 8)
    for tile in (pallas_kernels._auto_sort_tile(width, n_pad),
                 pallas_kernels._auto_selection_tile(width, n_pad)):
        assert width % tile == 0
    # at d itself neither finds one, which is what the padded copy was for
    assert RESNET18_D % pallas_kernels._auto_sort_tile(RESNET18_D, n_pad) != 0
    assert RESNET18_D % pallas_kernels._auto_selection_tile(RESNET18_D, n_pad) != 0


def test_forced_kernels_read_the_wide_matrix_without_a_padded_copy(monkeypatch):
    """The two stream wrappers the benchmark's cells reach, handed a
    matrix at the published width: their jaxprs hold no ``pad`` and no
    zero buffer to scatter the matrix into (interpreted kernels, traced
    only)."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    n, d = 8, 40_000
    width = pallas_kernels.aligned_width(n, d)
    assert width == 49_152
    for aggregate in (AGGREGATORS["trimmed_mean"], AGGREGATORS["multi_krum"]):
        copies = {}
        for cols in (d, width):
            jaxpr = jax.make_jaxpr(aggregate)(jax.ShapeDtypeStruct((n, cols), jnp.float32))
            copies[cols] = [
                eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)
                if eqn.primitive.name in ("pad", "scatter", "dynamic_update_slice", "concatenate")
                and any(len(v.aval.shape) >= 2 and v.aval.shape[-2] >= n
                        and v.aval.shape[-1] >= cols for v in eqn.outvars)
            ]
        assert copies[width] == [] and copies[d] != []


# -- every shipped aggregator and pre-aggregator maps zero columns to zero ----

D_SMALL, K_PAD = 1000, 24

AGG_CONTRACT = {
    "mean": _mean,
    "coordinate_median": robust.coordinate_median,
    "trimmed_mean": partial(robust.trimmed_mean, f=2),
    "mean_of_medians": partial(robust.mean_of_medians, f=2),
    "multi_krum": partial(robust.multi_krum, f=2, q=4),
    "krum": partial(robust.krum, f=2),
    "nnm_multi_krum": partial(robust.nnm_multi_krum, f_nnm=2, f=2, q=4),
    "clipped_multi_krum": partial(robust.clipped_multi_krum, tau=5.0, f=2, q=4),
    "arc_multi_krum": partial(robust.arc_multi_krum, f_arc=2, f=2, q=4),
    "geometric_median": robust.geometric_median,
    "centered_clipping": partial(robust.centered_clipping, c_tau=5.0),
    "cge": partial(robust.cge, f=2),
    "monna": partial(robust.monna, f=2),
    "caf": partial(robust.caf, f=2),
}
# scale rows by (or iterate on) norms summed over the columns: the zeros
# add nothing, but XLA may block a longer reduction differently, so the
# last bit of a factor may differ
NORM_SCALED = {"geometric_median", "centered_clipping", "clipped_multi_krum",
               "clip_rows", "arc_clip"}
# docs/performance.md, "The zero-column contract": CAF's power iteration
# starts from a seeded vector of the matrix's width, so a padded matrix
# starts it elsewhere
START_DEPENDS_ON_WIDTH = {"caf"}

PREAGG_CONTRACT = {
    "clip_rows": partial(preagg.clip_rows, threshold=5.0),
    "bucket_means": lambda x: preagg.bucket_means(
        x, jax.random.permutation(jax.random.PRNGKey(3), x.shape[0]), bucket_size=3),
    "nnm": partial(preagg.nnm, f=2),
    "arc_clip": partial(preagg.arc_clip, f=2),
}


@pytest.fixture(scope="module")
def attacked_matrix():
    x = jax.random.normal(jax.random.PRNGKey(5), (N, D_SMALL), jnp.float32)
    return x.at[-B:].set(-3.0 * jnp.mean(x[:-B], axis=0))


@pytest.mark.parametrize("name", sorted(AGG_CONTRACT))
def test_aggregator_maps_zero_columns_to_zero_and_keeps_the_rest(attacked_matrix, name):
    agg = AGG_CONTRACT[name]
    want = np.asarray(agg(attacked_matrix))
    got = np.asarray(agg(jnp.pad(attacked_matrix, ((0, 0), (0, K_PAD)))))
    assert got.shape == (D_SMALL + K_PAD,)
    assert np.count_nonzero(got[D_SMALL:]) == 0
    if name in START_DEPENDS_ON_WIDTH:
        assert np.linalg.norm(got[:D_SMALL] - want) <= 0.5 * np.linalg.norm(want)
    elif name in NORM_SCALED:
        np.testing.assert_allclose(got[:D_SMALL], want, rtol=1e-5, atol=1e-7)
    else:
        np.testing.assert_array_equal(got[:D_SMALL], want)


@pytest.mark.parametrize("name", sorted(PREAGG_CONTRACT))
def test_pre_aggregator_maps_zero_columns_to_zero_and_keeps_the_rest(attacked_matrix, name):
    pre = PREAGG_CONTRACT[name]
    want = np.asarray(pre(attacked_matrix))
    got = np.asarray(pre(jnp.pad(attacked_matrix, ((0, 0), (0, K_PAD)))))
    assert got.shape == (want.shape[0], D_SMALL + K_PAD)
    assert np.count_nonzero(got[:, D_SMALL:]) == 0
    if name in NORM_SCALED:
        np.testing.assert_allclose(got[:, :D_SMALL], want, rtol=1e-6, atol=1e-7)
    else:
        np.testing.assert_array_equal(got[:, :D_SMALL], want)
