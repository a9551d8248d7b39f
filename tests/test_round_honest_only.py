"""On one device the fused round runs forward/backward for the honest
workers only, one after another.

Nothing reads a byzantine worker's own gradient or loss: its row of the
matrix is the attack's (or, with no attack, an honest row echoed). So
where no node axis forces n rows, ``build_ps_train_step`` runs
``per_node_grad`` over ``xs[:h]`` in a loop (``lax.map``: on the TPU a
convolutional model's per-worker gradients cost far less one worker at a
time than vmapped, ``docs/performance.md``), and ``_set_byzantine_rows`` makes
the ``(n, .)`` matrix from the ``(h, .)`` stack. The results are the same
function of the honest inputs as a round that computes all n rows at once
and overwrites b of them. On a mesh the step computes all n rows (a
byzantine worker's chip runs in lockstep with the others: skipping it frees
no time), each chip the rows of the workers it holds one after another
(section (d); ``vmap`` over all n only where the node axis does not divide n).
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.nets import mnist_cnn, mnist_mlp
from byzpy_tpu.ops import attack_ops, robust
from byzpy_tpu.parallel.mesh import node_mesh, replicated
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step, jit_ps_train_step
from byzpy_tpu.utils.trees import ravel_pytree_fn

N, IMAGES, STEPS = 8, 4, 3

AGGREGATORS = {
    "trimmed_mean": partial(robust.trimmed_mean, f=2),
    "multi_krum": partial(robust.multi_krum, f=2, q=4),
}


def _sign_flip(honest, key):
    return attack_ops.sign_flip(jnp.mean(honest, axis=0))


def _empire(honest, key):
    return attack_ops.empire(honest)


def _little(honest, key):
    # f as the attack would be told it, held where its quantile is finite
    return attack_ops.little(honest, f=min(N - honest.shape[0], 3), n_total=N)


def _gaussian(honest, key):
    """Keyed, and a row of its own for every byzantine worker."""
    b, width = N - honest.shape[0], honest.shape[1]
    return jnp.mean(honest, axis=0) + attack_ops.gaussian(key, (b, width), sigma=0.1)


ATTACKS = {"sign_flip": _sign_flip, "empire": _empire, "little": _little,
           "gaussian": _gaussian, "echo": None}


def _cfg(b):
    return PSStepConfig(n_nodes=N, n_byzantine=b, learning_rate=0.05, momentum=0.9)


@pytest.fixture(scope="module")
def bundle():
    return mnist_mlp(0, hidden=16)


@pytest.fixture(scope="module")
def batches():
    kx, ky = jax.random.split(jax.random.PRNGKey(7))
    xs = jax.random.normal(kx, (STEPS, N, IMAGES, 28, 28, 1), jnp.float32)
    ys = jax.random.randint(ky, (STEPS, N, IMAGES), 0, 10)
    keys = jax.random.split(jax.random.PRNGKey(11), STEPS)
    return xs, ys, keys


def _all_rows_round(bundle, cfg, aggregate, attack):
    """The plain round: every worker's gradient is computed, then the b
    byzantine rows are overwritten."""
    opt = optax.sgd(cfg.learning_rate, momentum=cfg.momentum)
    ravel, unravel = ravel_pytree_fn(bundle.params)
    h, b = cfg.n_honest, cfg.n_byzantine

    def per_node_grad(params, x, y):
        loss, g = jax.value_and_grad(bundle.loss_fn)(params, x, y)
        return loss, ravel(g)

    def step(params, opt_state, xs, ys, key):
        losses, grads = jax.vmap(per_node_grad, in_axes=(None, 0, 0))(params, xs, ys)
        assert grads.shape[0] == cfg.n_nodes
        honest = grads[:h]
        if attack is None:
            byz = jnp.tile(honest, (-(-b // h), 1))[:b]
        else:
            byz = jnp.broadcast_to(attack(honest, key), (b, honest.shape[1]))
        agg = aggregate(grads.at[h:].set(byz.astype(grads.dtype)))
        updates, opt_state = opt.update(unravel(agg), opt_state, params)
        metrics = {"honest_loss": jnp.mean(losses[:h]),
                   "agg_grad_norm": jnp.sqrt(jnp.sum(jnp.square(agg)))}
        return optax.apply_updates(params, updates), opt_state, metrics

    return jax.jit(step), opt.init(bundle.params)


def _flat(tree):
    return np.concatenate([np.asarray(v).ravel() for v in jax.tree_util.tree_leaves(tree)])


def _drive(step, params, opt_state, batches):
    """Flat parameters and optimizer state after ``STEPS`` steps, and the
    two metrics of every step."""
    xs, ys, keys = batches
    losses, norms = [], []
    for i in range(STEPS):
        params, opt_state, metrics = step(params, opt_state, xs[i], ys[i], keys[i])
        losses.append(np.asarray(metrics["honest_loss"]))
        norms.append(np.asarray(metrics["agg_grad_norm"]))
    return _flat(params), _flat(opt_state), np.asarray(losses), np.asarray(norms)


def _assert_same_round(got, want):
    # one worker's gradient does not depend on how many are batched beside
    # it, but XLA may block a batched contraction of six otherwise than one
    # of eight: the last bit
    for a, b in zip(got, want):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


# -- (a) the same function of the honest inputs -------------------------------


@pytest.mark.parametrize("agg", sorted(AGGREGATORS))
def test_without_byzantine_workers_three_steps_equal_the_vmapped_round(bundle, batches, agg):
    cfg = _cfg(0)
    step, opt_state = jit_ps_train_step(bundle, AGGREGATORS[agg], cfg, donate=False)
    ref_step, ref_opt = _all_rows_round(bundle, cfg, AGGREGATORS[agg], None)
    _assert_same_round(_drive(step, bundle.params, opt_state, batches),
                       _drive(ref_step, bundle.params, ref_opt, batches))


@pytest.mark.parametrize("b", [1, 2, 7])
@pytest.mark.parametrize("attack", sorted(ATTACKS))
@pytest.mark.parametrize("agg", sorted(AGGREGATORS))
def test_three_steps_equal_the_round_that_computes_all_rows(bundle, batches, agg, attack, b):
    cfg = _cfg(b)
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS[agg], cfg, attack=ATTACKS[attack], donate=False)
    ref_step, ref_opt = _all_rows_round(bundle, cfg, AGGREGATORS[agg], ATTACKS[attack])
    _assert_same_round(_drive(step, bundle.params, opt_state, batches),
                       _drive(ref_step, bundle.params, ref_opt, batches))


# -- (b) the byzantine workers' batches are not read --------------------------


@pytest.mark.parametrize("b", [1, 2, 7])
@pytest.mark.parametrize("attack", ["sign_flip", "gaussian", "echo"])
def test_byzantine_batches_are_not_read(bundle, batches, attack, b):
    cfg = _cfg(b)
    h = cfg.n_honest
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], cfg, attack=ATTACKS[attack], donate=False)
    xs, ys, keys = batches
    poisoned = (xs.at[:, h:].set(jnp.nan), ys.at[:, h:].set(-1), keys)
    want = _drive(step, bundle.params, opt_state, batches)
    got = _drive(step, bundle.params, opt_state, poisoned)
    for a, w in zip(got, want):
        assert np.all(np.isfinite(a))
        np.testing.assert_array_equal(a, w)


# -- (c) what the one-device program holds ------------------------------------


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _slices_of_the_batches(step, args):
    """Shapes of the results of every ``slice`` the step takes of an
    array shaped like ``xs`` or ``ys``."""
    shapes = {tuple(args[2].shape), tuple(args[3].shape)}
    return [tuple(eqn.outvars[0].aval.shape)
            for eqn in _equations(jax.make_jaxpr(step)(*args).jaxpr)
            if eqn.primitive.name == "slice" and tuple(eqn.invars[0].aval.shape) in shapes]


def _sample_counts(text):
    """Leading sizes ``(workers, images)`` of every tensor of the lowered
    text with at least three dimensions whose second is the batch: the
    forward/backward activations (the matrix is (rows, d))."""
    return {(int(w), int(i)) for w, i in re.findall(r"tensor<(\d+)x(\d+)x\d+[x\d]*xf32>", text)
            if int(i) == IMAGES}


@pytest.mark.parametrize("b", [1, 2, 7])
def test_forward_backward_holds_the_honest_workers_samples_only(bundle, batches, b):
    cfg = _cfg(b)
    h = cfg.n_honest
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], cfg, attack=_sign_flip, donate=False)
    xs, ys, keys = batches
    args = (bundle.params, opt_state, xs[0], ys[0], keys[0])
    assert sorted(_slices_of_the_batches(step, args)) == sorted(
        [(h, IMAGES, 28, 28, 1), (h, IMAGES)])
    text = step.lower(*args).as_text()
    body = "\n".join(line for line in text.splitlines()
                     if "func.func" not in line and "stablehlo.slice" not in line)
    counts = _sample_counts(body)
    assert (h, IMAGES) in counts  # the sliced batches, handed to the loop
    assert (N, IMAGES) not in counts  # xs itself: the signature and the slice only
    assert (N, IMAGES) in _sample_counts(text)
    # forward/backward itself holds one worker's images at a time
    assert f"tensor<{IMAGES}x784xf32>" in text and "stablehlo.while" in text
    assert f"tensor<{h}x{IMAGES}x784xf32>" not in text


def test_without_byzantine_workers_the_step_takes_no_slice(bundle, batches):
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], _cfg(0), donate=False)
    xs, ys, keys = batches
    args = (bundle.params, opt_state, xs[0], ys[0], keys[0])
    assert _slices_of_the_batches(step, args) == []
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    # nor is the stack padded to more rows: it is the matrix
    assert not any(eqn.primitive.name == "pad" for eqn in _equations(jaxpr))
    assert (N, IMAGES) in _sample_counts(step.lower(*args).as_text())


def test_the_aggregate_is_handed_n_rows_made_from_an_h_row_stack(bundle, batches):
    """Eagerly, so that the aggregate is handed a real array: n rows,
    the honest workers' first, then the attack's; the attack was handed
    the h honest rows."""
    seen = {}

    def recording_mean(x):
        seen["matrix"] = np.asarray(x)
        return jnp.mean(x, axis=0)

    def recording_attack(honest, key):
        seen["honest"] = np.asarray(honest)
        return _sign_flip(honest, key)

    cfg = _cfg(2)
    step, opt_state = build_ps_train_step(bundle, recording_mean, cfg, attack=recording_attack)
    xs, ys, keys = batches
    step(bundle.params, opt_state, xs[0], ys[0], keys[0])
    ravel, _ = ravel_pytree_fn(bundle.params)
    honest = jax.vmap(lambda x, y: ravel(jax.grad(bundle.loss_fn)(bundle.params, x, y)))(
        xs[0, :6], ys[0, :6])
    assert seen["honest"].shape == (6, honest.shape[1])
    assert seen["matrix"].shape == (N, honest.shape[1])
    np.testing.assert_allclose(seen["matrix"][:6], np.asarray(honest), rtol=2e-6, atol=1e-8)
    np.testing.assert_array_equal(seen["matrix"][:6], seen["honest"])
    np.testing.assert_array_equal(seen["matrix"][6], seen["matrix"][7])
    np.testing.assert_allclose(seen["matrix"][6], -np.asarray(jnp.mean(honest, axis=0)),
                               rtol=2e-6, atol=1e-8)


# -- (d) on a mesh the step computes all n workers' rows, a chip's own in a loop --


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    return node_mesh(4, devices=jax.devices()[:4])


def _fwdbwd_loops(compiled_text):
    """Trip counts (the CPU backend writes them on the line) of the loops
    the compiled step runs under ``round.fwdbwd``."""
    return [int(n) for line in compiled_text.splitlines()
            if " while(" in line and "round.fwdbwd" in line
            for n in re.findall(r'"known_trip_count":\{"n":"(\d+)"', line)]


@pytest.mark.parametrize("sharded_update", ["off", "on"])
def test_on_a_mesh_every_workers_row_is_computed(bundle, batches, mesh, sharded_update):
    cfg = _cfg(2)
    chips = mesh.shape["nodes"]
    step, opt_state = jit_ps_train_step(
        bundle, AGGREGATORS["trimmed_mean"], cfg, attack=_sign_flip, mesh=mesh,
        donate=False, sharded_update=sharded_update)
    params = jax.device_put(bundle.params, replicated(mesh))
    xs, ys, keys = batches
    args = (params, opt_state, xs[0], ys[0], keys[0])
    # all n rows: the batches are handed over whole, none is cut to the honest
    assert _slices_of_the_batches(step, args) == []
    lowered = step.lower(*args)
    text = lowered.as_text()
    counts = _sample_counts(text)
    assert (N, IMAGES) in counts and (cfg.n_honest, IMAGES) not in counts
    d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(bundle.params))
    assert f"tensor<{N}x{d}xf32>" in text  # the matrix the loops' blocks make up
    # a chip's block of the node axis, and a loop over it whose body holds ONE
    # worker's samples: no merged batch, of all n or of a chip's n / k
    assert (N // chips, IMAGES) in counts and "stablehlo.while" in text
    assert f"tensor<{IMAGES}x784xf32>" in text
    for merged in (N, N // chips):
        assert f"tensor<{merged}x{IMAGES}x784xf32>" not in text
    assert _fwdbwd_loops(lowered.compile().as_text()) == [N // chips]
    ref_step, ref_opt = _all_rows_round(bundle, cfg, AGGREGATORS["trimmed_mean"], _sign_flip)
    got = _drive(step, params, opt_state, batches)
    want = _drive(ref_step, bundle.params, ref_opt, batches)
    # the sharded update carries its state flat and padded: compare what
    # both rounds have, the parameters and the metrics
    for a, w in zip((got[0], got[2], got[3]), (want[0], want[2], want[3])):
        np.testing.assert_allclose(a, w, rtol=1e-5, atol=1e-7)


def _grouped_convolutions(compiled_text):
    return [line for line in compiled_text.splitlines() if " convolution(" in line
            and any(int(g) > 1 for g in re.findall(r"(?:feature|batch)_group_count=(\d+)", line))]


@pytest.mark.parametrize("n, loops", [(8, [2]), (4, []), (6, [])],
                         ids=["two_a_chip", "one_a_chip", "axis_does_not_divide"])
def test_a_chips_convolutions_are_one_workers(mesh, n, loops):
    """Under ``vmap`` a convolution's per-worker weight gradient is ONE
    convolution grouped over the worker axis, and the forward's batch the
    workers' merged; the loop's body holds plain convolutions of one worker's
    samples (a loop of one trip is no loop once compiled). The choice is read
    off the mesh and ``n`` alone: where the node axis does not divide ``n``
    the step is the ``vmap`` over all n."""
    cnn = mnist_cnn(0)
    looped = n % mesh.shape["nodes"] == 0
    cfg = PSStepConfig(n_nodes=n, n_byzantine=1)
    step, opt_state = jit_ps_train_step(
        cnn, partial(robust.trimmed_mean, f=1), cfg, attack=_sign_flip, mesh=mesh, donate=False)
    args = (jax.device_put(cnn.params, replicated(mesh)), opt_state,
            jnp.zeros((n, IMAGES, 28, 28, 1), jnp.float32), jnp.zeros((n, IMAGES), jnp.int32),
            jax.random.PRNGKey(0))
    text = step.lower(*args).compile().as_text()
    convolutions = [line for line in text.splitlines() if " convolution(" in line]
    assert convolutions
    assert _fwdbwd_loops(text) == loops
    assert bool(_grouped_convolutions(text)) == (not looped)
    if looped:
        # forward and input-gradient convolutions carry the samples in their
        # first dimension: IMAGES of them, whatever the chip holds
        assert {int(b) for line in convolutions
                for b in re.findall(r"= f32\[(\d+),\d+,\d+,\d+\]\S* convolution\(", line)
                if "b01f_01" in line and "->b01f" in line} == {IMAGES}
