"""The fused round writes its gradient matrix once.

``build_ps_train_step`` ravels each worker's row at the width the
aggregate's consumer reads in place (``pallas_kernels.aligned_width``),
sets the byzantine rows into the stack instead of rebuilding it, and
cuts the aggregate's zero tail before the update. On the CPU the width is
``d`` and only the in-place row write differs from a round that
concatenates: bit-identical. With the width forced wider, the XLA route
sees the zero-tailed matrix every backend's wide path would.

Where rows are folded (``(width / 128, 128)``: the TPU's wide path, forced
here by the width or by the interpreted kernels) the order of a row's
columns is the round's own (``utils.trees.row_layout``): leaves that are
whole tiles first, each written into the loop's stack on its own. The
tests that pin the matrix to ``ravel_pytree``'s order are restated there
against the layout's order, case for case.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import re
import subprocess
import sys
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.bundle import ModelBundle, Segment
from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.ops import attack_ops, pallas_kernels, preagg, robust
from byzpy_tpu.parallel.ps import (
    PSStepConfig,
    build_ps_train_step,
    jit_ps_train_step,
)
from byzpy_tpu.parallel import ps as ps_module
from byzpy_tpu.parallel.mesh import node_mesh
from byzpy_tpu.utils.trees import RowLayout, ravel_pytree_fn, row_layout, tree_size

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
    noise = attack_ops.gaussian(key, (1 << 15,), sigma=0.1)[:width]
    return jnp.mean(honest, axis=0) + noise


ATTACKS = {"sign_flip": _sign_flip, "empire": _empire, "noise": _noise}


@pytest.fixture(scope="module")
def bundle():
    return mnist_mlp(0, hidden=16)


def _tiles_apply(params, x):
    y = x.reshape(x.shape[0], -1)
    for k in range(4):
        y = jnp.tanh(y @ params[f"w{k}"] + params[f"b{k}"])
    return y @ params["w4"] + params["b4"]


TILES_SHAPES = {
    "w0": (784, 16), "b0": (16,),   # ragged: 12544 is no multiple of 1024
    "w1": (16, 256), "b1": (256,),  # a tile leaf kept in the order of its (8, 128) tiles
    "w2": (256, 32), "b2": (32,),   # a tile leaf narrower than a tile: row-major
    "w3": (32, 128), "b3": (128,),  # a tile leaf one tile wide: row-major is its tiles
    "w4": (128, 10), "b4": (10,),
}
TILE_LEAVES = 3


@pytest.fixture(scope="module")
def tiles_bundle():
    """A hand-made MLP on the same batches with three tile leaves (one of
    each kind) between ragged ones: d = 30,650, folded rows 31,744 wide
    (32,768 under the forced kernels)."""
    keys = jax.random.split(jax.random.PRNGKey(3), len(TILES_SHAPES))
    params = {name: jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
              for k, (name, shape) in zip(keys, TILES_SHAPES.items())}
    return ModelBundle(_tiles_apply, params)


def _round_to_1024(n, d):
    return -(-d // 1024) * 1024


def _layout_order(bundle, folded=True):
    """``(ravel, unravel)`` of rows ``d`` wide in the layout's order."""
    layout = row_layout(bundle.params, tree_size(bundle.params), folded=folded)
    return layout.ravel, layout.unravel


@pytest.fixture(scope="module")
def batches():
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    xs = jax.random.normal(kx, (STEPS, N, 4, 28, 28, 1), jnp.float32)
    ys = jax.random.randint(ky, (STEPS, N, 4), 0, 10)
    keys = jax.random.split(jax.random.PRNGKey(11), STEPS)
    return xs, ys, keys


def _concatenating_step(bundle, aggregate, attack, order=None):
    """The round as it was before the matrix was written once: rows
    ravelled at width d (in ``ravel_pytree``'s order, or in the
    ``order = (ravel, unravel)`` given), the matrix rebuilt by
    ``concatenate``."""
    opt = optax.sgd(CFG.learning_rate, momentum=CFG.momentum)
    ravel, unravel = order or ravel_pytree_fn(bundle.params)
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


def _matrix_the_aggregate_sees(bundle, attack, batches, **step_kwargs):
    """One round run eagerly (no jit), so that the aggregate is handed a
    real array: the matrix of the first step."""
    seen = []

    def recording_mean(x):
        seen.append(np.asarray(x))
        return jnp.mean(x, axis=0)

    step, opt_state = build_ps_train_step(bundle, recording_mean, CFG, attack=attack,
                                          **step_kwargs)
    xs, ys, keys = batches
    step(bundle.params, opt_state, xs[0], ys[0], keys[0])
    (matrix,) = seen
    return matrix


def _concatenated_matrix(bundle, attack, batches, ravel, width):
    """The first step's matrix as a round that concatenates makes it: rows
    ravelled by ``ravel`` and zero-padded to ``width``, the attack's rows
    (tail forced to zero) under the honest ones."""
    xs, ys, keys = batches
    d = tree_size(bundle.params)
    grads = jax.lax.map(lambda xy: ravel(jax.grad(bundle.loss_fn)(bundle.params, *xy)),
                        (xs[0], ys[0]))  # one worker after another, as the round's loop
    honest = jnp.pad(grads[: CFG.n_honest], ((0, 0), (0, width - d)))
    byz = jnp.broadcast_to(attack(honest, keys[0]), (B, width))
    byz = jnp.where(jnp.arange(width) < d, byz, 0)
    return np.asarray(jnp.concatenate([honest, byz]))


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
@pytest.mark.parametrize("agg", sorted(AGGREGATORS))
def test_three_folded_steps_equal_the_concatenating_round_in_the_layouts_order(
        monkeypatch, tiles_bundle, batches, agg, attack):
    """The same on the wide path with folded rows (the width forced to a
    multiple of 1024; XLA route): the reference concatenates rows ``d``
    wide in the layout's order. A coordinate-wise aggregate gives the same
    bits; a selection sums its distances over the columns in another order
    and over a zero tail (the last bit of a norm)."""
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_1024)
    step, opt_state = jit_ps_train_step(
        tiles_bundle, AGGREGATORS[agg], CFG, attack=ATTACKS[attack], donate=False)
    ref_step, ref_opt = _concatenating_step(
        tiles_bundle, AGGREGATORS[agg], ATTACKS[attack], order=_layout_order(tiles_bundle))
    got, got_norms = _drive(step, tiles_bundle.params, opt_state, batches)
    want, want_norms = _drive(ref_step, tiles_bundle.params, ref_opt, batches)
    if agg in ("trimmed_mean", "median"):
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_norms, want_norms)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got_norms, want_norms, rtol=1e-5)


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_the_matrix_the_aggregate_sees_equals_the_concatenated_one_bitwise(
        bundle, batches, attack):
    """Whatever the aggregator: eagerly, row for row and bit for bit."""
    matrix = _matrix_the_aggregate_sees(bundle, ATTACKS[attack], batches)
    ravel, _ = ravel_pytree_fn(bundle.params)
    want = _concatenated_matrix(bundle, ATTACKS[attack], batches, ravel, matrix.shape[1])
    np.testing.assert_array_equal(matrix, want)


@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_the_folded_matrix_equals_the_concatenated_one_in_the_layouts_order_bitwise(
        monkeypatch, tiles_bundle, batches, attack):
    """Folded rows: the columns are the parameters in the layout's order,
    the same in every row (the attack is handed them in that order and
    its rows land in it), then the exactly-zero tail."""
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_1024)
    d = tree_size(tiles_bundle.params)
    matrix = _matrix_the_aggregate_sees(tiles_bundle, ATTACKS[attack], batches)
    assert matrix.shape == (N, _round_to_1024(N, d)) and matrix.shape[1] > d
    ravel, _ = _layout_order(tiles_bundle)
    want = _concatenated_matrix(tiles_bundle, ATTACKS[attack], batches, ravel, matrix.shape[1])
    np.testing.assert_array_equal(matrix, want)
    assert np.count_nonzero(matrix[:, d:]) == 0
    # and that order is not ravel_pytree's
    other, _ = ravel_pytree_fn(tiles_bundle.params)
    assert not np.array_equal(
        want, _concatenated_matrix(tiles_bundle, ATTACKS[attack], batches, other, matrix.shape[1]))


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
@pytest.mark.parametrize("folded", [False, True], ids=["flat", "folded"])
def test_pad_tail_is_zero_in_every_row_the_aggregate_sees(
        monkeypatch, bundle, tiles_bundle, batches, attack, folded):
    """The real columns are the first d and the tail is zero, in the
    computed rows and in the attack's: flat rows in ``ravel_pytree``'s
    order, and folded rows in the layout's."""
    round_up = _round_to_1024 if folded else _round_to_128
    bundle = tiles_bundle if folded else bundle
    monkeypatch.setattr(pallas_kernels, "aligned_width", round_up)
    d = tree_size(bundle.params)
    matrix = _matrix_the_aggregate_sees(bundle, ATTACKS[attack], batches)
    keys = batches[2]
    assert matrix.shape == (N, round_up(N, d)) and matrix.shape[1] > d
    assert np.count_nonzero(matrix[:, d - 1]) == N  # the last real column is one
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
    in ``round.fwdbwd``; the loop writes it into the n-row stack it
    carries and the byzantine rows are written into the same stack;
    nothing concatenates or pads rows or matrix again."""
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
    assert _matrix_rebuilds(step, args, (N, width)) == []
    # the reference round above does rebuild it: the probe sees a second one
    ref_step, ref_opt = _concatenating_step(bundle, AGGREGATORS["trimmed_mean"], ATTACKS["noise"])
    ref = _matrix_rebuilds(ref_step, (bundle.params, ref_opt, xs[0], ys[0], keys[0]), (N, d))
    assert [name for name, _ in ref] == ["concatenate", "concatenate"]


def _stack_writes(step, args, stack_shape):
    """``[(update's shape, scope)]`` of the step's ``dynamic_update_slice``
    equations whose result is the ``stack_shape`` stack."""

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            scope = outer + "/" + str(eqn.source_info.name_stack)
            if (eqn.primitive.name == "dynamic_update_slice"
                    and eqn.outvars[0].aval.shape == stack_shape):
                yield eqn.invars[1].aval.shape, scope
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):  # a loop's body names its scopes from here
                        yield from walk(inner, scope)

    return list(walk(jax.make_jaxpr(step)(*args).jaxpr, ""))


def test_the_folded_matrix_is_built_leaf_by_leaf(monkeypatch, tiles_bundle, batches):
    """Folded rows: nothing concatenates a whole row. Inside the loop in
    ``round.fwdbwd`` each tile leaf's gradient is written into row i of
    the stack by a ``dynamic_update_slice`` of its own, and one more
    writes the other leaves, ravelled with the zero tail by the one small
    ``concatenate``."""
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_1024)
    d = tree_size(tiles_bundle.params)
    width = _round_to_1024(N, d)
    layout = row_layout(tiles_bundle.params, width, folded=True)
    assert layout.tile_leaves == TILE_LEAVES
    step, opt_state = build_ps_train_step(
        tiles_bundle, AGGREGATORS["trimmed_mean"], CFG, attack=ATTACKS["noise"])
    xs, ys, keys = batches
    args = (tiles_bundle.params, opt_state, xs[0], ys[0], keys[0])
    for shape in [(width,), (d,), (CFG.n_honest, width), (N, width)]:
        assert _matrix_rebuilds(step, args, shape) == []
    rest = width - layout.offsets[-1]
    assert [name for name, _ in _matrix_rebuilds(step, args, (rest,))] == ["concatenate"]
    writes = _stack_writes(step, args, (N, width // 128, 128))
    in_loop = [shape for shape, scope in writes if "round.fwdbwd" in scope]
    sizes = [int(np.prod(TILES_SHAPES[k])) for k in ("w1", "w2", "w3")]
    assert in_loop == [(1, size // 128, 128) for size in sizes] + [(1, rest // 128, 128)]
    assert len(in_loop) == layout.tile_leaves + 1
    assert len(writes) == len(in_loop)  # (the byzantine rows are set by a scatter)


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


def test_folded_sharded_update_carries_its_flat_state_in_the_layouts_order(
        monkeypatch, tiles_bundle, batches):
    """Folded rows and the sharded update on one device: the carried flat
    parameters and momentum are ``d_pad`` wide in the layout's order (the
    aggregate's own, so the update reads it as it comes), their tail zero,
    and the parameters returned are those of the replicated update."""
    monkeypatch.setattr(pallas_kernels, "aligned_width", _round_to_1024)
    kwargs = dict(attack=_sign_flip, donate=False)
    tree_step, tree_opt = jit_ps_train_step(
        tiles_bundle, AGGREGATORS["trimmed_mean"], CFG, **kwargs)
    want, want_norms = _drive(tree_step, tiles_bundle.params, tree_opt, batches)
    step, opt_state = jit_ps_train_step(
        tiles_bundle, AGGREGATORS["trimmed_mean"], CFG, sharded_update="on", **kwargs)
    d = tree_size(tiles_bundle.params)
    width = _round_to_1024(N, d)
    layout = row_layout(tiles_bundle.params, width, folded=True)
    np.testing.assert_array_equal(opt_state[0], layout.ravel(tiles_bundle.params))
    last = []
    got, got_norms = _drive(step, tiles_bundle.params, opt_state, batches, state=last)
    # the flat update is compiled for another length: the last bit may differ
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got_norms, want_norms, rtol=1e-5)
    flat, inner = last[0]
    assert flat.shape == (width,) and np.count_nonzero(np.asarray(flat)[d:]) == 0
    carried = layout.unravel(flat[:d])
    np.testing.assert_allclose(
        np.concatenate([np.asarray(v).ravel() for v in jax.tree_util.tree_leaves(carried)]),
        got, rtol=1e-6, atol=1e-7)


def _ravel_pytree_layout(example, width, *, folded):
    """``row_layout`` as the round had it before: ``ravel_pytree`` and
    nothing placed on its own."""
    ravel, unravel = ravel_pytree_fn(example)
    d = tree_size(example)
    assert width == d
    return RowLayout(width=width, d=d, dtype=ravel(example).dtype, offsets=(0,),
                     place=lambda tree, cast=None: [ravel(tree) if cast is None
                                                    else ravel(tree).astype(cast)],
                     ravel=lambda tree, cast=None: (ravel(tree) if cast is None
                                                    else ravel(tree).astype(cast)),
                     unravel=unravel)


@pytest.mark.parametrize("kwargs", [{}, {"sharded_update": "off"}, {"grad_dtype": jnp.bfloat16}],
                         ids=["sharded_update", "replicated_update", "grad_bf16"])
def test_the_mesh_step_is_the_ravel_pytree_round(monkeypatch, tiles_bundle, batches, kwargs):
    """On a mesh rows are ``d`` wide and not folded: the layout places no
    leaf on its own, and the step's jaxpr is, equation for equation, the
    one that ``ravel_pytree`` and its inverse give."""
    mesh = node_mesh(8)
    xs, ys, keys = batches

    def jaxpr_text():
        step, opt_state = build_ps_train_step(
            tiles_bundle, AGGREGATORS["trimmed_mean"], CFG, attack=_sign_flip, mesh=mesh,
            **kwargs)
        return str(jax.make_jaxpr(step)(tiles_bundle.params, opt_state, xs[0], ys[0], keys[0]))

    with_layout = jaxpr_text()
    monkeypatch.setattr(ps_module, "row_layout", _ravel_pytree_layout)
    assert with_layout == jaxpr_text()
    assert "concatenate" in with_layout and "dynamic_update_slice" not in with_layout


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


# -- (iv) folded rows: the stack reaches the sort family's kernel as written ----


def _clip(x):
    return preagg.clip_rows(x, threshold=5.0)


def _nnm(x):
    return preagg.nnm(x, f=2)


FOLDED_ROUNDS = {
    # name: (aggregate, pre_aggregate, whole-matrix sublane writes on the TPU)
    "trimmed_mean": ("trimmed_mean", None, 0),
    "median": ("median", None, 0),
    "multi_krum": ("multi_krum", None, 1),  # its Gram wants the workers in sublanes
    # a row-wise scaling is elementwise over the folded rows too
    "clip_then_trimmed_mean": ("trimmed_mean", _clip, 0),
    # a mixing of rows is a matmul over the workers: one relayout in front of
    # its kernel, and the kernel's own (n, d_pad) result
    "nnm_then_trimmed_mean": ("trimmed_mean", _nnm, 2),
}


@pytest.mark.parametrize("attack", ["sign_flip", "noise", "empire"])
@pytest.mark.parametrize("name", sorted(FOLDED_ROUNDS))
@pytest.mark.parametrize("leaves", ["ragged", "tiles"])
def test_folded_round_with_forced_kernels_equals_the_concatenating_round(
        monkeypatch, bundle, tiles_bundle, batches, leaves, name, attack):
    """Kernels forced (interpreted here), so the rows are 16384 (32768)
    wide and stacked as (n, 128, 128): the loop's n-row stack, the
    byzantine rows written into it and the folded kernel give the
    parameters of the round that concatenates (n, d) rows in the layout's
    order, bit for bit where the aggregate is coordinate-wise. ``ragged``:
    no leaf is whole tiles and the order is ``ravel_pytree``'s; ``tiles``:
    three leaves are placed on their own."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    agg, pre, _ = FOLDED_ROUNDS[name]
    bundle = tiles_bundle if leaves == "tiles" else bundle
    d = tree_size(bundle.params)
    assert pallas_kernels.aligned_width(N, d) == (32768 if leaves == "tiles" else 16384)
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS[agg], CFG, attack=ATTACKS[attack], pre_aggregate=pre, donate=False)
    reference = AGGREGATORS[agg] if pre is None else (lambda x: AGGREGATORS[agg](pre(x)))
    order = _layout_order(bundle)
    if leaves == "ragged":
        flat, _ = ravel_pytree_fn(bundle.params)
        np.testing.assert_array_equal(order[0](bundle.params), flat(bundle.params))
    ref_step, ref_opt = _concatenating_step(bundle, reference, ATTACKS[attack], order=order)
    got, got_norms = _drive(step, bundle.params, opt_state, batches)
    want, want_norms = _drive(ref_step, bundle.params, ref_opt, batches)
    if name in ("trimmed_mean", "median"):
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got_norms, want_norms)
    else:  # norms and Gram blocks summed over another number of columns
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got_norms, want_norms, rtol=1e-5)


@pytest.mark.parametrize("leaves", ["ragged", "tiles"])
def test_folded_stack_is_what_the_aggregate_is_handed(
        monkeypatch, bundle, tiles_bundle, batches, leaves):
    """Eagerly, kernels forced: the (n, d_pad) matrix the aggregate sees is
    the concatenated one (columns in the layout's order) with an
    exactly-zero tail, all n rows written."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    bundle = tiles_bundle if leaves == "tiles" else bundle
    d = tree_size(bundle.params)
    matrix = _matrix_the_aggregate_sees(bundle, ATTACKS["noise"], batches)
    assert matrix.shape == (N, 32768 if leaves == "tiles" else 16384)
    ravel, _ = _layout_order(bundle)
    want = _concatenated_matrix(bundle, ATTACKS["noise"], batches, ravel, matrix.shape[1])
    np.testing.assert_array_equal(matrix, want)
    assert np.count_nonzero(matrix[:, d:]) == 0

# the streamed round's update: a segmented toy whose body holds a leaf at a
# first column that is no multiple of its own row of tiles ((64, 1024) after
# (16, 256): 4096 is no multiple of 8192), a leaf under 128 lanes wide
# ((256, 64): the width of every folded row divides by 64) and ragged ones
STREAMED_SHAPES = {
    "body": {"w0": (16, 256), "w1": (64, 1024), "w2": (256, 64), "b": (64,), "w3": (1024, 3)},
    "head": {"w": (64, 10), "b": (10,)},
}
ROW_SIZED = 32_768  # half the largest leaf; the narrow leaf's own stretch is 16,384


def _streamed_toy():
    def body(p, x):
        y = jnp.tanh(x @ p["w0"])
        y = jnp.tanh(y[:, :64] @ p["w1"])
        return jnp.tanh(y[:, :256] @ p["w2"] + p["b"]) + (y @ p["w3"]).sum(-1, keepdims=True)

    def head(p, x, y):
        logits = x @ p["w"] + p["b"]
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    params = {seg: {name: jax.random.normal(next(keys), shape, jnp.float32) / np.sqrt(shape[0])
                    for name, shape in leaves.items()}
              for seg, leaves in STREAMED_SHAPES.items()}
    return ModelBundle(apply_fn=None, params=params,
                       segments=(Segment("body", body), Segment("head", head)))


# the attention kernels' compile: a sequence that is neither the query
# heads' width (4096) nor a block's, a hidden size that is no block's
ATTENTION_TOKENS, ATTENTION_HIDDEN = 2048, 384
TWO_WIDTH_TOKENS = 1024
GLM_CELL_TOKENS = 4096


def _latent_attention_lowered(module, cfg, tokens, one_chip):
    """Value and gradient of ``layers.mla_attention`` under ``vmap`` over
    one sequence of ``tokens`` positions, on ``module``'s weights of an
    expert block at ``cfg``'s sizes, lowered for ``one_chip``."""
    from byzpy_tpu.models import layers

    shapes = jax.eval_shape(lambda: module.init_params(cfg)["seg02_moe"])
    return jax.jit(jax.value_and_grad(
        lambda p, xs: jnp.sum(jax.vmap(lambda s: layers.mla_attention(p, s, cfg))(xs)),
        argnums=(0, 1))).lower(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), shapes),
        jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.float32, sharding=one_chip))


def _write_tpu_texts(out_dir):
    """The optimised text of the one-device step of every
    ``FOLDED_ROUNDS`` entry, compiled for a described (not attached) TPU
    v5e chip with its kernels compiled by Mosaic, as the chip's process
    would: ``<out_dir>/<name>.hlo.txt``. The TPU's own text is where a
    relayout shows (on the CPU every reshape is a bitcast). Run in a
    process of its own (:func:`tpu_texts`): it loads libtpu, turns the
    persistent compile cache off (a compile for a described chip cannot
    be read back without the chip) and replaces the interpreter switch."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    os.environ["BYZPY_TPU_PALLAS"] = "1"
    pallas_kernels._resolve_interpret = lambda interpret: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    # FIRST, before this process has traced anything (what a lowered text calls
    # and what it inlines depends on that): latent attention at the Xing4.0
    # cell's own sizes as LOWERED, the kernels' bodies decoded (they hold file
    # names): there the rule keeps the three-axis form
    import round_texts
    from byzpy_tpu.models import xing4

    lowered = _latent_attention_lowered(xing4, xing4.Xing4Config(), TWO_WIDTH_TOKENS, one_chip)
    with open(os.path.join(out_dir, "mla_attention_xing_cell_float32.hlo.txt"), "w",
              encoding="utf-8") as fh:
        fh.write(round_texts._decoded(lowered.as_text()))

    toy = mnist_mlp(0, hidden=16)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    for name, (agg, pre, _) in FOLDED_ROUNDS.items():
        step, opt_state = build_ps_train_step(
            toy, AGGREGATORS[agg], CFG, attack=ATTACKS["sign_flip"], pre_aggregate=pre)
        args = (described(toy.params), described(opt_state),
                jax.ShapeDtypeStruct((N, 4, 28, 28, 1), jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct((N, 4), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
        text = jax.jit(step).lower(*args).compile().as_text()
        with open(os.path.join(out_dir, name + ".hlo.txt"), "w", encoding="utf-8") as fh:
            fh.write(text)

    # the streamed round on the segmented toy, its default optimizer
    from byzpy_tpu.ops import coordinatewise

    streamed = _streamed_toy()
    step, opt_state = build_ps_train_step(
        streamed, AGGREGATORS["trimmed_mean"], CFG,
        attack=coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean"))
    text = jax.jit(step).lower(
        described(streamed.params), described(opt_state),
        jax.ShapeDtypeStruct((N, 4, 16), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((N, 4), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile().as_text()
    with open(os.path.join(out_dir, "streamed_update.hlo.txt"), "w", encoding="utf-8") as fh:
        fh.write(text)

    # a worker's batch in passes: the GroupNorm toy of test_worker_passes,
    # declared, on the described chip, whose fast memory the rule reads
    # itself (128 MiB); the budget cut so that a worker's 8 images (2 KiB
    # of activation each) go through whole, in two passes and in four
    import dataclasses

    import test_worker_passes as passes
    from byzpy_tpu.models.nets import make_bundle
    from byzpy_tpu.parallel import ps

    ps._default_device = lambda: topo.devices[0]
    images = dataclasses.replace(
        make_bundle(passes.GroupNormCNN(), (1, 8, 8, 3)), example_mean_loss=True)
    for count in PASS_COUNTS:
        ps._PASS_BUDGET = (8 // count) * 2048 / (128 << 20)
        step, opt_state = build_ps_train_step(
            images, AGGREGATORS["trimmed_mean"], CFG, attack=ATTACKS["sign_flip"])
        text = jax.jit(step).lower(
            described(images.params), described(opt_state),
            jax.ShapeDtypeStruct((N, 8, 8, 8, 3), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((N, 8), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)).compile().as_text()
        with open(os.path.join(out_dir, f"passes_{count}.hlo.txt"), "w", encoding="utf-8") as fh:
            fh.write(text)

    # the block-causal attention kernels at the Nemotron cell's heads
    # (32 / 2 x 128), value and gradient through gqa_attention under vmap
    from byzpy_tpu.models import nemotron_h

    pallas_kernels._on_tpu = lambda: True
    cfg = nemotron_h.NemotronHConfig(hidden_size=ATTENTION_HIDDEN)
    weights = {"w_q": (ATTENTION_HIDDEN, 4096), "w_k": (ATTENTION_HIDDEN, 256),
               "w_v": (ATTENTION_HIDDEN, 256), "w_o": (4096, ATTENTION_HIDDEN)}
    for dtype in ("float32", "bfloat16"):
        def sds(*shape, dtype=dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)

        text = jax.jit(jax.value_and_grad(
            lambda p, xs: jnp.sum(jax.vmap(lambda s: nemotron_h.gqa_attention(p, s, cfg))(xs)
                                  .astype(jnp.float32)), argnums=(0, 1))).lower(
            {name: sds(*shape) for name, shape in weights.items()},
            sds(1, ATTENTION_TOKENS, ATTENTION_HIDDEN)).compile().as_text()
        with open(os.path.join(out_dir, f"attention_{dtype}.hlo.txt"), "w",
                  encoding="utf-8") as fh:
            fh.write(text)

    # the same kernels in latent attention's regime (20 key/value heads of 256,
    # one query head each), value and gradient through mla_attention under vmap
    from byzpy_tpu.models import glm4_moe_lite

    mla = glm4_moe_lite.Glm4MoeLiteConfig(hidden_size=ATTENTION_HIDDEN)
    shapes = jax.eval_shape(lambda: glm4_moe_lite.init_params(mla)["seg02_moe"])
    text = jax.jit(jax.value_and_grad(
        lambda p, xs: jnp.sum(jax.vmap(lambda s: glm4_moe_lite.mla_attention(p, s, mla))(xs)),
        argnums=(0, 1))).lower(
        described(shapes), jax.ShapeDtypeStruct(
            (1, ATTENTION_TOKENS, ATTENTION_HIDDEN), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    with open(os.path.join(out_dir, "mla_attention_float32.hlo.txt"), "w",
              encoding="utf-8") as fh:
        fh.write(text)

    # and at two widths (32 key/value heads, queries and keys of 192 padded to
    # 256, values of 128, under YaRN), at the Xing4.0 cell's 1024 positions
    from byzpy_tpu.models import layers, xing4

    two = xing4.Xing4Config(hidden_size=ATTENTION_HIDDEN)
    shapes = jax.eval_shape(lambda: xing4.init_params(two)["seg02_moe"])
    text = jax.jit(jax.value_and_grad(
        lambda p, xs: jnp.sum(jax.vmap(lambda s: layers.mla_attention(p, s, two))(xs)),
        argnums=(0, 1))).lower(
        described(shapes), jax.ShapeDtypeStruct(
            (1, TWO_WIDTH_TOKENS, ATTENTION_HIDDEN), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    with open(os.path.join(out_dir, "mla_attention_192_128_float32.hlo.txt"), "w",
              encoding="utf-8") as fh:
        fh.write(text)

    # latent attention at the GLM cell's OWN sizes (hidden size and sequence as
    # `chipbench/configs/` has them), where the rule takes q, k and v born in
    # the kernels' rows
    cell = glm4_moe_lite.Glm4MoeLiteConfig()
    text = _latent_attention_lowered(glm4_moe_lite, cell, GLM_CELL_TOKENS,
                                     one_chip).compile().as_text()
    with open(os.path.join(out_dir, "mla_attention_glm_cell_float32.hlo.txt"), "w",
              encoding="utf-8") as fh:
        fh.write(text)

    # and at heads NARROWER than a lane tile (32 query / 8 key-value heads of 64,
    # per-head norms and the rotary turn in front, the LFM2 cell's heads):
    # nothing padded, two key/value heads a tile
    from byzpy_tpu.models import lfm2_moe

    narrow = lfm2_moe.Lfm2MoeConfig()
    shapes = jax.eval_shape(lambda: lfm2_moe.init_params(narrow)["seg02_attn_moe"])
    for dtype in ("float32", "bfloat16"):
        text = jax.jit(jax.value_and_grad(
            lambda p, xs: jnp.sum(jax.vmap(lambda s: lfm2_moe.gqa_attention(p, s, narrow))(xs)
                                  .astype(jnp.float32)), argnums=(0, 1))).lower(
            described(shapes), jax.ShapeDtypeStruct(
                (1, ATTENTION_TOKENS, narrow.hidden_size), jnp.dtype(dtype), sharding=one_chip)
        ).compile().as_text()
        with open(os.path.join(out_dir, f"attention_64_{dtype}.hlo.txt"), "w",
                  encoding="utf-8") as fh:
            fh.write(text)

    # and SmallThinker's attention of both kinds at its cell's own shape (28 query /
    # 4 key-value heads of 128, SEVEN heads a group, 8192 positions): a windowed
    # block (window 4096, rotary) and a global one (no positions)
    from byzpy_tpu.models import smallthinker

    seven = smallthinker.SmallThinkerConfig()
    shapes = jax.eval_shape(lambda: smallthinker.init_params(seven)["seg02_window"])
    for name, kind, dtype in (("window_float32", (True, True), "float32"),
                              ("window_bfloat16", (True, True), "bfloat16"),
                              ("global_float32", (False, False), "float32")):
        text = jax.jit(jax.value_and_grad(
            lambda p, xs: jnp.sum(jax.vmap(lambda s: smallthinker.attention(p, s, seven, kind))(xs)
                                  .astype(jnp.float32)), argnums=(0, 1))).lower(
            described(shapes), jax.ShapeDtypeStruct(
                (1, WINDOW_TOKENS, seven.hidden_size), jnp.dtype(dtype), sharding=one_chip)
        ).compile().as_text()
        with open(os.path.join(out_dir, f"attention_7_{name}.hlo.txt"), "w",
                  encoding="utf-8") as fh:
            fh.write(text)

    # the held experts' layer, value and gradient, at the three language cells'
    # tokens, picks, held experts, round and width (the experts' own width cut
    # to 128: the read-back kernel sees none of it)
    from byzpy_tpu.parallel import moe

    for name, (n_experts, top_k, held, round_rows, d) in EXPERT_CELLS.items():
        def f32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

        text = jax.jit(jax.grad(
            lambda x, w: jnp.sum(moe.held_experts_ffn(
                x, *w, first_held=0, n_experts=n_experts, top_k=top_k,
                round_rows=None if round_rows == EXPERT_TOKENS // 8 else round_rows)[0]),
            argnums=(0, 1))).lower(
            f32(EXPERT_TOKENS, d),
            (f32(d, n_experts), f32(held, d, 128), f32(held, 128, d))).compile().as_text()
        with open(os.path.join(out_dir, f"experts_{name}.hlo.txt"), "w", encoding="utf-8") as fh:
            fh.write(text)


# the language cells' expert layers: experts, picks a token, experts held, a
# round's rows (the layer's own eighth of the tokens; Qwen3-Next's four times
# the mean load), hidden size (`chipbench/configs/`), at a worker's 4096 tokens
EXPERT_TOKENS = 4096
EXPERT_CELLS = {"nemotron": (128, 6, 8, 512, 2688), "glm": (64, 4, 8, 512, 2048),
                "qwen": (512, 10, 32, 320, 2048)}


PASS_COUNTS = (1, 2, 4)  # in how many passes the toy worker's 8 images go
WINDOW_TOKENS = 8192  # the SmallThinker cell's tokens a worker: twice its window


@pytest.fixture(scope="module")
def tpu_texts(tmp_path_factory):
    """``{round's name: its text}`` from :func:`_write_tpu_texts`, run once
    in a child process with a temporary directory of its own (libtpu's
    logs land there) and ``ALLOW_MULTIPLE_LIBTPU_LOAD``, so that neither
    another test worker nor a job on this host that holds libtpu's lock
    can turn these checks off. Skipped only where no libtpu is installed;
    a child that fails where one is installed fails the tests."""
    pytest.importorskip("libtpu")
    out_dir = tmp_path_factory.mktemp("tpu_texts")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               TMPDIR=str(out_dir), TPU_STDERR_LOG_LEVEL="3",
               PYTHONPATH=os.pathsep.join(filter(None, [root, os.environ.get("PYTHONPATH")])))
    done = subprocess.run([sys.executable, os.path.abspath(__file__), str(out_dir)], env=env,
                          cwd=root, capture_output=True, text=True, timeout=900, check=False)
    if done.returncode:
        pytest.fail("compiling the toy rounds for a described v5e failed "
                    f"(exit {done.returncode}):\n{done.stderr[-3000:]}")
    texts = {}
    for name in [*FOLDED_ROUNDS, "streamed_update", "attention_float32", "attention_bfloat16",
                 "mla_attention_float32", "mla_attention_192_128_float32",
                 "mla_attention_glm_cell_float32", "mla_attention_xing_cell_float32",
                 "attention_64_float32", "attention_64_bfloat16",
                 "attention_7_window_float32", "attention_7_window_bfloat16",
                 "attention_7_global_float32",
                 *(f"experts_{cell}" for cell in EXPERT_CELLS),
                 *(f"passes_{count}" for count in PASS_COUNTS)]:
        with open(os.path.join(out_dir, name + ".hlo.txt"), encoding="utf-8") as fh:
            texts[name] = fh.read()
    return texts


def _benchmark_reader(metric):
    """One of the benchmark's own readers (``chipbench/layer_metrics``)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "chipbench", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(metric.replace(".", "_"), path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def _sublane_matrix_writes(text, d):
    """The benchmark's own counter (``sublane_matrix_writes.train``)."""
    config = {"n_nodes": N, "n_byzantine": B, "n_parameters": d}
    return _benchmark_reader("sublane_matrix_writes.train").read(
        SimpleNamespace(outcome={"compiled_text": text}, config=config))


def _entry_instructions(text):
    """``{name: (opcode, [operand names], line)}`` of the entry computation."""
    entry = text.partition("\nENTRY ")[2].partition("\n}")[0]
    found = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = .*? ([\w\-]+)\(([^)]*)\)", line)
        if m:
            found[m.group(1)] = (m.group(2), re.findall(r"%[\w.\-]+", m.group(3)), line)
    return found


@pytest.mark.parametrize("name", sorted(FOLDED_ROUNDS))
def test_on_the_tpu_only_an_aggregate_that_wants_sublane_rows_relays_the_stack(
        tpu_texts, bundle, name):
    text = tpu_texts[name]
    writes = FOLDED_ROUNDS[name][2]
    assert "tpu_custom_call" in text
    assert _sublane_matrix_writes(text, tree_size(bundle.params)) == writes


@pytest.mark.parametrize("agg", ["trimmed_mean", "median"])
def test_on_the_tpu_the_sort_kernels_operand_is_the_loops_stack(tpu_texts, agg):
    """From the kernel's custom call back to the loop: the row write of
    the byzantine rows (a fusion around a ``dynamic-update-slice``, which
    updates its operand in place) and bitcasts; no copy, no relayout."""
    entry = _entry_instructions(tpu_texts[agg])
    (kernel,) = [v for v in entry.values() if "sorted_reduce_stream" in v[2]
                 and v[0] == "custom-call"]
    assert "f32[1,8,128,128]" in kernel[2]  # the folded operand, whole (8, 128) tiles a row
    at, path = kernel[1][0], []
    while entry[at][0] != "while":
        opcode, operands, line = entry[at]
        path.append(opcode)
        if opcode == "fusion":
            assert "dynamic-update-slice" in line  # named after what it fuses
        at = operands[0]
    assert set(path) <= {"fusion", "bitcast", "get-tuple-element"} and "fusion" in path
    # and the result is the flat aggregate: no relayout between kernel and update
    assert " copy(" not in "".join(v[2] for v in entry.values() if "round.update" in v[2])


# -- the attention kernels, compiled by Mosaic ----------------------------------


def _moved_under(text, scope, at_least):
    """Instructions of the entry computation under ``scope`` that move
    at least ``at_least`` elements and compute nothing: ``(opcode, line)``
    of every ``copy``, ``slice``, ``reshape``, ``transpose``,
    ``concatenate`` and ``pad`` (a ``reshape`` that is left in a compiled
    TPU text is a relayout: a free one is a ``bitcast``), and of every
    fusion made of such ops alone."""
    moves = {"copy", "slice", "reshape", "transpose", "concatenate", "pad"}
    fused = {}
    for block in re.split(r"\n(?=%?[\w.\-]+ \()", text):
        head = re.match(r"%?([\w.\-]+) \(", block)
        if head:
            fused[head.group(1)] = set(re.findall(
                r"= \S+ ([a-z\-]+)\(", block)) - {"parameter", "bitcast", "tuple"}
    found = []
    for opcode, _, line in _entry_instructions(text).values():
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not op_name or scope not in op_name.group(1):
            continue
        if opcode == "fusion":
            inside = fused.get(re.search(r"calls=%?([\w.\-]+)", line).group(1), {"?"})
            if not inside <= moves:
                continue
        elif opcode not in moves:
            continue
        result = line.split(" = ", 1)[1].split(" " + opcode + "(", 1)[0]
        sizes = [int(np.prod([int(n) for n in dims.split(",") if n]))
                 for dims in re.findall(r"f32\[([\d,]*)\]", result)]
        if sizes and max(sizes) >= at_least:
            found.append((opcode, line.strip()[:200]))
    return found


def test_on_the_tpu_the_streamed_update_moves_no_row_sized_array(tpu_texts):
    """Under ``round.update`` of the segmented toy's step nothing stands
    that moves a row-sized array without computing: no cut of the row
    into a buffer of its own before a leaf's update, no relayout of the
    whole row to a narrow leaf's minor dimension (the parent of PR 42
    compiled three: a ``slice`` to f32[65536] for ``w1``, and a
    ``reshape`` of the body's whole row, 98,304 columns, to f32[1536, 64]
    for ``w2`` and to f32[32768, 3] for ``w3``). What is left is smaller
    than ``ROW_SIZED``: a narrow leaf's own stretch, relaid."""
    text = tpu_texts["streamed_update"]
    assert "tpu_custom_call" in text and "round.update" in text
    assert _moved_under(text, "round.update", ROW_SIZED) == []
    # and the reader sees what it is for: the aggregate kernel's own scope
    # holds the row-sized bitcasts and nothing that moves
    assert _moved_under(text, "round.aggregate", ROW_SIZED) == []


def test_on_the_tpu_the_streamed_round_has_its_kernel_form_the_byzantine_rows(tpu_texts):
    """The same step (trimmed mean under the omniscient sign flip, kernels
    serving): Mosaic compiles the kernel with the attack in its body, one
    call a segment, which the benchmark's own counter reads; each stack
    holds the h honest rows; nothing stands under ``round.build_matrix``
    and no array leads with n where the parent of PR 43 kept n rows of a
    segment."""
    text = tpu_texts["streamed_update"]
    reader = _benchmark_reader("attack_in_kernel_segments.train")
    assert reader.read(SimpleNamespace(outcome={"compiled_text": text})) == 2  # body, head
    assert "sorted_reduce_stream." not in text and "round.build_matrix" not in text
    assert re.search(r"f32\[%d,\d+,128\]" % (N - B), text)
    assert not re.search(r"f32\[(1,)?%d,\d+,128\]" % N, text)


def _attention_calls(text):
    """``{kernel's name: its custom call's line}`` under ``model.attention``."""
    calls = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%((?:causal|window)_attention_\w+?)(?:\.\d+)? = ", line)
        if m and "tpu_custom_call" in line:
            assert "model.attention" in re.search(r'op_name="([^"]*)"', line).group(1)
            assert m.group(1) not in calls
            calls[m.group(1)] = line
    return calls


@pytest.mark.parametrize("dtype, narrow", [("float32", "f32"), ("bfloat16", "bf16")])
def test_on_the_tpu_attention_is_three_mosaic_kernels(tpu_texts, dtype, narrow):
    text = tpu_texts[f"attention_{dtype}"]
    calls = _attention_calls(text)
    assert sorted(calls) == ["causal_attention_dkv", "causal_attention_dq",
                             "causal_attention_fwd"]
    t = ATTENTION_TOKENS
    # q (T, 32 x 128) and k, v (T, 2 x 128) go in as the projections leave them:
    # no key/value tensor repeated over the sixteen query heads of a group
    for line in calls.values():
        operands = line.partition("custom-call(")[2]
        assert f"{narrow}[{t},4096]" in line and f"{narrow}[{t},256]" in line, operands
    # one log-sum-exp a query row a head leaves the forward, float32
    assert f"f32[2,16,{t}]" in calls["causal_attention_fwd"].partition(" custom-call(")[0]


def test_on_the_tpu_latent_attention_is_the_same_three_kernels_one_query_head_a_group(tpu_texts):
    calls = _attention_calls(tpu_texts["mla_attention_float32"])
    assert sorted(calls) == ["causal_attention_dkv", "causal_attention_dq",
                             "causal_attention_fwd"]
    t = ATTENTION_TOKENS
    # q, k and v all go in as (T, 20 x 256): every head its own key/value head
    for line in calls.values():
        assert line.count(f"f32[{t},5120]") >= 3
    assert f"f32[20,1,{t}]" in calls["causal_attention_fwd"].partition(" custom-call(")[0]
    # the latent projections, norms and rotary turns stand in their own scope
    assert "model.mla_latent" in tpu_texts["mla_attention_float32"]
    reader = _benchmark_reader("attention_kernel_calls.train")
    assert reader.read(SimpleNamespace(
        outcome={"compiled_text": tpu_texts["mla_attention_float32"]})) == 3


def test_on_the_tpu_latent_attention_of_192_and_128_pads_its_keys_and_never_its_values(tpu_texts):
    text = tpu_texts["mla_attention_192_128_float32"]
    calls = _attention_calls(text)
    assert sorted(calls) == ["causal_attention_dkv", "causal_attention_dq",
                             "causal_attention_fwd"]
    t = TWO_WIDTH_TOKENS
    # q and k go in as (T, 32 x 256), v (and the output, and its cotangent) as
    # (T, 32 x 128); no array of 32 x 192 reaches a kernel and no value is padded
    forward = calls["causal_attention_fwd"]
    assert forward.count(f"f32[{t},8192]") >= 2 and forward.count(f"f32[{t},4096]") >= 2
    for line in calls.values():
        assert f"f32[{t},6144]" not in line
    backward = calls["causal_attention_dkv"].partition(" custom-call(")[0]
    assert f"f32[{t},8192]" in backward and f"f32[{t},4096]" in backward  # dk, dv
    assert f"f32[32,1,{t}]" in forward.partition(" custom-call(")[0]
    reader = _benchmark_reader("attention_kernel_calls.train")
    assert reader.read(SimpleNamespace(outcome={"compiled_text": text})) == 3


def _instructions(text):
    """``(opcode, shape, op_name)`` of every instruction of a compiled text
    that has an ``op_name``."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name:
            yield m.group(2), tuple(int(d) for d in m.group(1).split(",") if d), name.group(1)


@pytest.mark.parametrize("opcode", ["copy", "slice", "concatenate", "pad"])
def test_at_the_glm_cells_sizes_no_activation_is_cut_or_joined_on_three_axes(tpu_texts, opcode):
    """q, k and v are born ``(T, 20 x 256)`` in the kernels' rows: outside
    the attention core no ``copy``, ``slice``, ``concatenate`` or ``pad``
    under ``model.attention`` writes an array of three or more axes with
    the sequence's 4096 rows (a leading axis of one is ``vmap``'s), and no
    ``copy`` stands at ``model.attention/reshape``, where the caller's
    ``q.reshape(t, -1)`` stood. What is cut, padded and joined has the
    latents' 768 or 512 rows."""
    text = tpu_texts["mla_attention_glm_cell_float32"]
    outside = [(op, shape, name) for op, shape, name in _instructions(text)
               if _WHOLE_ATTENTION.search(name) and "model.attention_core" not in name]
    assert any(op == "fusion" and GLM_CELL_TOKENS in shape for op, shape, _ in outside)
    found = [(shape, name) for op, shape, name in outside
             if op == opcode and GLM_CELL_TOKENS in shape
             and len([d for d in shape if d != 1]) >= 3]
    assert not found, found
    if opcode == "copy":
        assert not [name for op, _, name in _instructions(text)
                    if op == "copy" and name.endswith("model.attention/reshape")]
    else:  # the cuts, pads and joins are there, on the weights
        assert any(op in (opcode, "fusion") and shape[0] in (768, 512) and len(shape) == 3
                   for op, shape, _ in outside)


def test_at_the_glm_cells_sizes_the_labels_and_the_three_kernels_stand(tpu_texts):
    text = tpu_texts["mla_attention_glm_cell_float32"]
    calls = _attention_calls(text)
    assert sorted(calls) == ["causal_attention_dkv", "causal_attention_dq",
                             "causal_attention_fwd"]
    for line in calls.values():
        assert line.count(f"f32[{GLM_CELL_TOKENS},5120]") >= 3
    names = _op_names(text)
    for label in ("model.mla_latent/model.rotary", "model.mla_latent", "model.attention_proj",
                  "model.attention_core"):
        assert any(label in name for name in names), label


# sha256 of the Xing4.0 cell's latent attention as LOWERED (kernel bodies
# decoded), taken on the commit before the rows' route (986fa54)
XING_CELL_LOWERED = "62eaa75b9ee444a06de98fed6b831748a2ad5c60e42547eb5826a7efd789d531"


def test_at_the_xing_cells_sizes_the_rule_keeps_the_lowered_text_it_had(tpu_texts):
    text = tpu_texts["mla_attention_xing_cell_float32"]
    assert hashlib.sha256(text.encode()).hexdigest() == XING_CELL_LOWERED


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_on_the_tpu_heads_of_64_are_the_same_three_kernels_two_heads_a_lane_tile(tpu_texts,
                                                                                 dtype):
    """LFM2's 32 query / 8 key-value heads of 64: Mosaic takes
    all three kernels; q goes in as (T, 2048) and k, v as (T, 512), as the
    projections leave them (no head padded to 128: no (T, 4096) or (T, 1024)
    operand); a grid step is the two key/value heads of one lane tile with
    their eight query heads, so the forward's log-sum-exp is (4, 8, T)."""
    text = tpu_texts[f"attention_64_{dtype}"]
    calls = _attention_calls(text)
    assert sorted(calls) == ["causal_attention_dkv", "causal_attention_dq",
                             "causal_attention_fwd"]
    t, kind = ATTENTION_TOKENS, {"float32": "f32", "bfloat16": "bf16"}[dtype]
    forward = calls["causal_attention_fwd"]
    assert forward.count(f"{kind}[{t},2048]") >= 2 and forward.count(f"{kind}[{t},512]") >= 2
    for line in calls.values():
        assert f"{kind}[{t},4096]" not in line and f"{kind}[{t},1024]" not in line
    assert f"f32[4,8,{t}]" in forward.partition(" custom-call(")[0]
    backward = calls["causal_attention_dkv"].partition(" custom-call(")[0]
    assert backward.count(f"{kind}[{t},512]") == 2  # dk, dv
    reader = _benchmark_reader("attention_kernel_calls.train")
    assert reader.read(SimpleNamespace(outcome={"compiled_text": text})) == 3


@pytest.mark.parametrize("name, kernels", [
    ("window_float32", "window"), ("window_bfloat16", "window"), ("global_float32", "causal")])
def test_on_the_tpu_seven_heads_a_group_are_three_kernels_windowed_or_causal(tpu_texts, name,
                                                                             kernels):
    """SmallThinker's 28 query / 4 key-value heads of 128 at 8192 positions:
    Mosaic takes all three kernels at seven heads a group (3584 folded rows,
    walked 896 at a time), a windowed block's under names of their own and a
    global block's under the causal names; q goes in as (T, 3584) and k, v as
    (T, 512), the forward's log-sum-exp is (4, 7, T); the benchmark's two
    counters read each kind's calls and not the other's."""
    text = tpu_texts[f"attention_7_{name}"]
    calls = _attention_calls(text)
    assert sorted(calls) == [f"{kernels}_attention_dkv", f"{kernels}_attention_dq",
                             f"{kernels}_attention_fwd"]
    t, kind = WINDOW_TOKENS, "bf16" if name.endswith("bfloat16") else "f32"
    forward = calls[f"{kernels}_attention_fwd"]
    assert f"{kind}[{t},3584]" in forward and forward.count(f"{kind}[{t},512]") >= 2
    assert f"f32[4,7,{t}]" in forward.partition(" custom-call(")[0]
    ctx = SimpleNamespace(outcome={"compiled_text": text}, config={"reference": {"arch": {
        "sliding_window_size": 4096}}})
    assert _benchmark_reader("attention_kernel_calls.train").read(ctx) == 3
    assert _benchmark_reader("window_attention_kernel_calls.train").read(ctx) == (
        3 if kernels == "window" else 0)
    # a configuration without a window has nothing for the new counter to say
    assert _benchmark_reader("window_attention_kernel_calls.train").read(SimpleNamespace(
        outcome={"compiled_text": text}, config={"reference": {"arch": {}}})) is None
    # no (T, T) or (T, window) score matrix in the program
    assert not re.search(r"\[(?:\d+,)*%d,(?:%d|4096)\]" % (t, t), text)


# the attention texts above: name -> whether the call turns by position
ATTENTION_TEXTS = {
    "attention_float32": False, "attention_bfloat16": False, "mla_attention_float32": True,
    "mla_attention_192_128_float32": True, "attention_64_float32": True,
    "attention_64_bfloat16": True, "attention_7_window_float32": True,
    "attention_7_window_bfloat16": True, "attention_7_global_float32": False}
INSIDE_ATTENTION = ("model.attention_proj", "model.rotary", "model.attention_core")
_WHOLE_ATTENTION = re.compile(r"model\.attention(?!_)")  # the label itself, not its prefix


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("name", sorted(ATTENTION_TEXTS))
def test_on_the_tpu_the_kernels_and_their_own_backward_rule_stand_in_the_core(tpu_texts, name):
    """The three calls hold ``model.attention_core`` inside
    ``model.attention``; so does what ``_causal_attention_bwd`` (a
    ``custom_vjp``'s rule, traced outside the forward's scopes, which enters
    both labels itself) puts in front of its two calls: the ``delta``
    row-sum's multiply and reduction. The products with ``w_q``, ``w_k``,
    ``w_v`` and ``w_o`` hold ``model.attention_proj`` and no kernel does;
    ``model.rotary`` is there where the call turns by position, and inside
    ``model.mla_latent`` where the turn is the latents'."""
    text = tpu_texts[name]
    calls = _attention_calls(text)
    assert len(calls) == 3
    for line in calls.values():
        path = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "model.attention_core" in path and _WHOLE_ATTENTION.search(path)
        assert "model.attention_proj" not in path and "model.rotary" not in path
    names = _op_names(text)
    # the rule's own ops: the labels entered by hand, one inside the other
    own = [path for path in names if "model.attention/model.attention_core/" in path]
    assert own and all("transpose(" in path for path in own)
    assert any(path.endswith("model.attention_core/reduce_sum") for path in own)
    assert any("_attention_dq/" in path for path in own)
    assert any("_attention_dkv/" in path for path in own)
    products = [path for path in names if path.endswith("dot_general")]
    proj = [path for path in products if "model.attention_proj" in path]
    assert len(proj) >= 4 and all(_WHOLE_ATTENTION.search(path) for path in proj)
    turned = [path for path in names if "model.rotary" in path]
    assert bool(turned) == ATTENTION_TEXTS[name]
    if name.startswith("mla_"):
        assert turned and all("model.mla_latent" in path for path in turned)
        assert [path for path in products if "model.mla_latent" in path
                and "model.attention_proj" not in path]
    # nothing of the three outside model.attention
    assert all(_WHOLE_ATTENTION.search(path) for path in names
               if any(label in path for label in INSIDE_ATTENTION))


@pytest.mark.parametrize("name", sorted(ATTENTION_TEXTS))
def test_on_the_tpu_what_attention_moves_reads_the_same_with_and_without_the_labels(tpu_texts,
                                                                                    name):
    """``attention_moved_mb.train`` on a text compiled for the TPU: a
    number (the relayouts the compiler left standing under
    ``model.attention``), the same for the text with the three labels taken
    out of every ``op_name`` (it asks what a path HOLDS), and at most what
    every top-level move of the program writes."""
    text = tpu_texts[name]
    reader = _benchmark_reader("attention_moved_mb.train")
    moved = reader.read(SimpleNamespace(outcome={"compiled_text": text}))
    assert moved is not None and moved > 0
    parent = text
    for label in INSIDE_ATTENTION:
        parent = parent.replace(label + "/", "")
    assert "model.attention_core" not in parent and "model.attention" in parent
    assert reader.read(SimpleNamespace(outcome={"compiled_text": parent})) == moved
    everywhere = reader.read(SimpleNamespace(outcome={"compiled_text": re.sub(
        r'op_name="[^"]*"', 'op_name="model.attention"', text)}))
    assert moved <= everywhere


# -- the held experts' read-back, compiled by Mosaic ------------------------------


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_on_the_tpu_the_rows_go_back_to_the_tokens_by_one_mosaic_kernel(tpu_texts, cell):
    """At a language cell's tokens, picks, held experts, round and width Mosaic
    takes the kernel; it runs twice a round (the combine's forward, the
    dispatch gather's backward) under ``model.moe_experts``; the layer's
    compiled gradient scatters no row and holds no ``(T, k, D)`` array."""
    text = tpu_texts[f"experts_{cell}"]
    _n_experts, top_k, held, round_rows, d = EXPERT_CELLS[cell]
    calls = [line for line in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%rows_to_tokens(?:\.\d+)? = ", line)]
    assert len(calls) == 2 and all("tpu_custom_call" in line for line in calls)
    for line in calls:
        assert "model.moe_experts" in re.search(r'op_name="([^"]*)"', line).group(1)
        # the table stays in HBM as the products leave it; the sum comes out (T, D)
        assert f"f32[{held * round_rows},{d}]" in line.partition("custom-call(")[2]
        assert f"f32[{EXPERT_TOKENS},{d}]" in line.partition(" custom-call(")[0]
    assert any("transpose(" in re.search(r'op_name="([^"]*)"', line).group(1) for line in calls)
    for line in text.splitlines():
        if re.search(r" scatter\(| scatter-add\(", line) or "%scatter" in line.split("=")[0]:
            assert f",{d}]" not in line.partition("=")[2].partition("(")[0], line
    for picks in {top_k, held}:
        assert f"[{EXPERT_TOKENS},{picks},{d}]" not in text
        assert f"[{EXPERT_TOKENS * picks},{d}]" not in text


def test_on_the_tpu_attention_leaves_no_score_matrix_in_the_program(tpu_texts):
    """No array of the compiled program has a query and a key dimension:
    neither the sequence twice nor a query block by the sequence."""
    text = tpu_texts["attention_float32"]
    t = ATTENTION_TOKENS
    shapes = {m for m in re.findall(r"\b(?:f32|bf16|pred|s32)\[([\d,]+)\]", text)}
    assert shapes
    for shape in shapes:
        dims = [int(d) for d in shape.split(",")]
        assert dims.count(t) <= 1, shape
        assert not (t in dims and {512, 1024} & set(dims)), shape


def test_on_the_tpu_the_benchmarks_counter_reads_the_attention_kernels(tpu_texts):
    reader = _benchmark_reader("attention_kernel_calls.train")
    assert reader.read(SimpleNamespace(
        outcome={"compiled_text": tpu_texts["attention_float32"]})) == 3
    assert reader.read(SimpleNamespace(
        outcome={"compiled_text": tpu_texts["trimmed_mean"]})) is None


@pytest.mark.parametrize("count", PASS_COUNTS)
def test_on_the_tpu_the_benchmarks_counter_reads_a_workers_passes(tpu_texts, count):
    """The rule itself chose them (a declared bundle, the described chip's
    128 MiB of fast memory, the budget cut to the toy's size), and the
    TPU's text, which writes no trip count on a loop's line, says how many."""
    text = tpu_texts[f"passes_{count}"]
    assert _benchmark_reader("worker_passes.train").read(
        SimpleNamespace(outcome={"compiled_text": text})) == count
    assert ("stream.passes" in text) == (count > 1)


# -- (v) the layout of a row ---------------------------------------------------

LAYOUT_TREES = {
    # name: (shapes in tree order, tile leaves when folded)
    "tile_leaves": ({"a": (3, 3, 16, 256), "b": (8, 128), "c": (2, 512)}, 3),
    "ragged_leaves": ({"k": (3, 3, 3, 64), "s": (64,), "w": (512, 10), "z": (7,)}, 1),
    "mixed": (TILES_SHAPES, TILE_LEAVES),
    "no_leaf": ({}, 0),
    "only_ragged": ({"s": (64,), "t": (5, 5), "u": (1000,)}, 0),
    # whole tiles, but no tile of its own to keep: rows not a multiple of 8
    "odd_rows": ({"a": (4, 256), "b": (12, 256)}, 2),
}


def _random_tree(shapes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), max(len(shapes), 1))
    return {name: jax.random.normal(k, shape, jnp.float32)
            for k, (name, shape) in zip(keys, shapes.items())}


@pytest.mark.parametrize("cast", [None, jnp.bfloat16], ids=["f32", "grad_bf16"])
@pytest.mark.parametrize("folded", [False, True], ids=["flat", "folded"])
@pytest.mark.parametrize("name", sorted(LAYOUT_TREES))
def test_the_layouts_two_maps_are_inverse(name, folded, cast):
    shapes, tile_leaves = LAYOUT_TREES[name]
    tree = _random_tree(shapes)
    d = tree_size(tree)
    width = d + 24
    layout = row_layout(tree, width, folded=folded)
    assert (layout.d, layout.width) == (d, width)
    assert layout.tile_leaves == (tile_leaves if folded else 0)
    row = layout.ravel(tree, cast)
    assert row.shape == (width,) and row.dtype == (cast or jnp.float32)
    assert np.count_nonzero(np.asarray(row[d:], np.float32)) == 0
    pieces = layout.place(tree, cast)
    assert len(pieces) == len(layout.offsets) == layout.tile_leaves + 1
    at = 0
    for first, piece in zip(layout.offsets, pieces):
        assert first == at and first % 1024 == 0
        at += piece.shape[0]
    assert at == width
    # a permutation of the leaves' values: nothing dropped, nothing doubled
    flat = np.concatenate([np.asarray(v, np.float32).ravel() for v in
                           jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                               lambda v: v.astype(cast or v.dtype), tree))] + [np.zeros(0)])
    np.testing.assert_array_equal(np.sort(np.asarray(row[:d], np.float32)), np.sort(flat))
    back = layout.unravel(row[:d])
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(tree)
    for got, want in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(tree)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want.astype(cast or want.dtype).astype(want.dtype))
    if not layout.tile_leaves:  # ravel_pytree's order, with the tail
        ravel, _ = ravel_pytree_fn(tree)
        np.testing.assert_array_equal(np.asarray(row[:d], np.float32),
                                      np.asarray(ravel(tree).astype(cast or jnp.float32),
                                                 np.float32))


def test_a_wide_leaf_is_laid_down_tile_after_tile():
    """A tile leaf (..., C) with C a multiple of 128 above 128 and whole
    tiles high keeps the order of its (8, 128) tiles: 1024 consecutive
    columns of the row are 8 rows x 128 columns of the leaf."""
    leaf = jnp.arange(16 * 384, dtype=jnp.float32).reshape(2, 8, 384)
    layout = row_layout({"w": leaf}, leaf.size, folded=True)
    row = np.asarray(layout.ravel({"w": leaf}))
    as_matrix = np.asarray(leaf).reshape(16, 384)
    for t, (r, c) in enumerate((r, c) for r in range(2) for c in range(3)):
        np.testing.assert_array_equal(row[1024 * t: 1024 * (t + 1)].reshape(8, 128),
                                      as_matrix[8 * r: 8 * r + 8, 128 * c: 128 * c + 128])
    # a leaf one tile wide, or not whole tiles high, is row-major
    for shape in [(16, 128), (4, 256), (1024,)]:
        leaf = jnp.arange(int(np.prod(shape)), dtype=jnp.float32).reshape(shape)
        row = row_layout({"w": leaf}, leaf.size, folded=True).ravel({"w": leaf})
        np.testing.assert_array_equal(row, leaf.reshape(-1))


def test_the_layout_of_resnet18_places_all_but_a_thousandth():
    """The benchmark's model, shapes only: 20 of its 62 leaves are whole
    tiles and hold 99.9 % of the parameters; the row is 11,190,272 wide."""
    from byzpy_tpu.models.nets import cifar_resnet18

    shapes = jax.eval_shape(lambda: cifar_resnet18(0).params)
    layout = row_layout(shapes, 11_190_272, folded=True)
    assert layout.d == RESNET18_D and len(jax.tree_util.tree_leaves(shapes)) == 62
    assert layout.tile_leaves == 20 and layout.offsets[-1] == 11_162_624
    assert round(layout.placed_share, 5) == 0.99899
    assert row_layout(shapes, RESNET18_D, folded=False).placed_share == 0.0


def test_a_row_too_narrow_for_the_tree_is_refused():
    with pytest.raises(ValueError, match="cannot hold"):
        row_layout({"w": jnp.zeros((4, 4))}, 15, folded=False)


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


if __name__ == "__main__":  # the child process of the ``tpu_texts`` fixture
    _write_tpu_texts(sys.argv[1])
