"""A worker's batch in passes (``parallel/ps.py``: ``_worker_loss_and_grad``,
``_worker_passes``): the passes' mean is the whole batch's loss and gradient
for a model that keeps its examples apart, through the shared function and
through two whole steps of both ``(n, d)`` rounds; the rule as a pure
function of batch, bytes an example, budget, declaration and device; a
program that does not split is the program it was; the benchmark's counter
reads the passes off a compiled text; rows kept in bfloat16 are cast after a
float32 sum. The tests put their ``p`` in by patching the rule's function:
no round takes an argument for it."""

import dataclasses
import types
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models.bundle import ModelBundle
from byzpy_tpu.models.nets import (ResNet18, cifar_resnet18, make_bundle, mnist_cnn,
                                   mnist_mlp)
from byzpy_tpu.ops import robust
from byzpy_tpu.parallel import ps
from byzpy_tpu.parallel.mesh import grid_mesh, node_axis, node_mesh, replicated, sharding

N, B, IMAGES = 8, 2, 8
CFG = ps.PSStepConfig(n_nodes=N, n_byzantine=B, learning_rate=0.05, momentum=0.9)
MIB = 1 << 20


class GroupNormCNN(nn.Module):
    """Convolution, GroupNorm (the statistics of one example's channel
    groups), pooling within an image, a dense head: no layer mixes the
    examples of a batch."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.GroupNorm(num_groups=2)(nn.Conv(8, (3, 3), padding="SAME")(x)))
        return nn.Dense(10)(jnp.mean(x, axis=(1, 2)))


@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(
        make_bundle(GroupNormCNN(), (1, 8, 8, 3)), example_mean_loss=True)


def batch_mixing_toy():
    """A toy whose layer takes the batch's mean, as BatchNorm in training
    does: the gradient of a batch is NOT the mean of its parts'."""
    return ModelBundle(
        apply_fn=lambda p, x: (x - jnp.mean(x, axis=0)).reshape(x.shape[0], -1)[:, :16] @ p["w"],
        params={"w": jnp.linspace(-1.0, 1.0, 160, dtype=jnp.float32).reshape(16, 10)})


@pytest.fixture(scope="module")
def batches():
    kx, ky = jax.random.split(jax.random.PRNGKey(48))
    return (jax.random.normal(kx, (2, N, IMAGES, 8, 8, 3), jnp.float32),
            jax.random.randint(ky, (2, N, IMAGES), 0, 10))


def _sign_flip(honest, key):
    return -jnp.mean(honest, axis=0)


def _forced(monkeypatch, passes, seen=None):
    def rule(bundle, params, x, y, device):
        if seen is not None:
            seen.append((x.shape, device))
        return passes

    monkeypatch.setattr(ps, "_worker_passes", rule)


def _two_steps(bundle, batches, mesh=None, **kwargs):
    """(params, optimizer state, the two steps' metrics) after two whole
    steps of the round ``build_ps_train_step`` picks."""
    step, opt_state = ps.build_ps_train_step(
        bundle, partial(robust.trimmed_mean, f=B), CFG, attack=_sign_flip, mesh=mesh, **kwargs)
    step = jax.jit(step)
    params, (xs, ys) = bundle.params, batches
    if mesh is not None:
        params = jax.device_put(params, replicated(mesh))
        held_whole = N % mesh.shape[node_axis(mesh)] == 0
        at = sharding(mesh, None, node_axis(mesh)) if held_whole else replicated(mesh)
        xs, ys = (jax.device_put(a, at) for a in (xs, ys))
    metrics = []
    for k in range(2):
        params, opt_state, m = step(params, opt_state, xs[k], ys[k], jax.random.PRNGKey(k))
        metrics.append({name: float(v) for name, v in m.items()})
    return params, opt_state, metrics


def _assert_trees_close(got, want, rtol=2e-5, atol=2e-6):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol)


# -- (a) the passes' mean is the batch's ----------------------------------------


@pytest.mark.parametrize("passes", [2, 4])
def test_the_passes_mean_is_the_batchs_loss_and_gradient(toy, batches, passes):
    grad_of = jax.value_and_grad(toy.loss_fn)
    x, y = batches[0][0, 0], batches[1][0, 0]
    loss, grads = jax.jit(ps._worker_loss_and_grad(grad_of, 1))(toy.params, x, y)
    got_loss, got = jax.jit(ps._worker_loss_and_grad(grad_of, passes))(toy.params, x, y)
    assert got_loss.dtype == loss.dtype
    np.testing.assert_allclose(float(got_loss), float(loss), rtol=1e-6)
    _assert_trees_close(got, grads, rtol=1e-5, atol=1e-7)


def test_one_pass_is_the_function_itself(toy):
    # not a wrapper around it: the model is traced at the depth it was
    grad_of = jax.value_and_grad(toy.loss_fn)
    assert ps._worker_loss_and_grad(grad_of, 1) is grad_of
    assert ps._worker_loss_and_grad(grad_of, 2) is not grad_of


@pytest.mark.parametrize("passes", [2, 4])
def test_two_one_device_steps_in_passes_equal_two_whole_steps(monkeypatch, toy, batches, passes):
    want = _two_steps(toy, batches)
    _forced(monkeypatch, passes)
    got = _two_steps(toy, batches)
    _assert_trees_close(got[:2], want[:2])  # parameters, momentum
    for m, w in zip(got[2], want[2]):
        assert m == pytest.approx(w, rel=1e-5)  # honest_loss, agg_grad_norm


@pytest.mark.parametrize("passes", [2, 4])
def test_two_mesh_steps_in_passes_equal_two_whole_steps(monkeypatch, toy, batches, passes):
    mesh = node_mesh(4, devices=jax.devices()[:4])
    want = _two_steps(toy, batches, mesh)
    seen = []
    _forced(monkeypatch, passes, seen)
    got = _two_steps(toy, batches, mesh)
    assert seen and all(shape == (IMAGES, 8, 8, 3) and device == mesh.devices.flat[0]
                        for shape, device in seen)
    _assert_trees_close(got[:2], want[:2])
    for m, w in zip(got[2], want[2]):
        assert m == pytest.approx(w, rel=1e-5)


def test_on_a_grid_the_rule_sees_a_workers_whole_batch(monkeypatch, toy, batches):
    """What ``_mesh_train_step``'s docstring says of a further mesh axis: the
    rule is handed the worker's WHOLE batch (the partitioner shards each
    pass's examples over that axis), and the passes are still the batch."""
    mesh = grid_mesh(2, 2, devices=jax.devices()[:4])
    want = _two_steps(toy, batches, mesh)
    seen = []
    _forced(monkeypatch, 2, seen)
    got = _two_steps(toy, batches, mesh)
    assert {shape for shape, _ in seen} == {(IMAGES, 8, 8, 3)}
    _assert_trees_close(got[:2], want[:2])


def test_the_vmap_branch_is_never_split(monkeypatch, toy, batches):
    # 8 workers on three chips: no chip holds whole workers, the rule is not asked
    seen = []
    _forced(monkeypatch, 2, seen)
    _two_steps(toy, batches, node_mesh(3, devices=jax.devices()[:3]))
    assert seen == []


def test_a_batch_mixing_model_is_not_the_mean_of_its_parts(batches):
    """Why the declaration is asked for: forced on a model that takes the
    batch's mean, the passes give another gradient."""
    mixing = batch_mixing_toy()
    grad_of = jax.value_and_grad(mixing.loss_fn)
    x, y = batches[0][0, 0], batches[1][0, 0]
    whole = ps._worker_loss_and_grad(grad_of, 1)(mixing.params, x, y)[1]["w"]
    split = ps._worker_loss_and_grad(grad_of, 2)(mixing.params, x, y)[1]["w"]
    assert float(jnp.max(jnp.abs(whole - split))) > 1e-3 * float(jnp.max(jnp.abs(whole)))


# -- (b) the rule ---------------------------------------------------------------

RESNET_IMAGE = 32 * 32 * 64 * 4  # a stage-1 activation of ResNet-18, float32
V5E = 128 * MIB


@pytest.mark.parametrize("batch, per_example, budget, passes", [
    (128, RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 1),  # cells 1 and 3: whole
    (256, RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 2),
    (512, RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 4),  # the four-chip cell
    (1000, RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 8),  # 125 a pass: 192 would fit, 200 not
    (96, RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 1),
    (512, RESNET_IMAGE, 16 * MIB, 8),
    (512, RESNET_IMAGE, V5E, 1),
    (512, 4 * RESNET_IMAGE, int(V5E * ps._PASS_BUDGET), 16),
    (7, 100, 100, 7),       # a prime: whole or one at a time
    (6, 100, 250, 3),       # two a pass
    (1, RESNET_IMAGE, 1, 1),
    (8, 100, 50, 1),        # not even one example fits: no divisor does, no split
    (512, 0, 0, 1),
])
def test_passes_for(batch, per_example, budget, passes):
    assert ps._passes_for(batch, per_example, budget) == passes
    assert batch % passes == 0


def test_the_budget_lets_128_resnet_images_through_and_splits_256():
    assert 128 * RESNET_IMAGE <= V5E * ps._PASS_BUDGET < 256 * RESNET_IMAGE


def _shapes(batch, image=(8, 8, 3)):
    return (jax.ShapeDtypeStruct((batch, *image), jnp.float32),
            jax.ShapeDtypeStruct((batch,), jnp.int32))


def test_the_largest_activation_is_one_examples(toy):
    # the convolution's f32[1, 8, 8, 8] result (and GroupNorm's of that size)
    assert ps._largest_activation_bytes(toy.loss_fn, toy.params, *_shapes(64)) == 8 * 8 * 8 * 4
    resnet = jax.eval_shape(lambda: cifar_resnet18(0).params)
    assert ps._largest_activation_bytes(
        cifar_resnet18(0).loss_fn, resnet, *_shapes(512, (32, 32, 3))) == RESNET_IMAGE


@pytest.mark.parametrize("fast, passes", [(64 * 2048, 8), (512 * 2048, 1), (8 * 2048, 64)])
def test_a_declared_bundle_on_a_tpu_splits_by_its_fast_memory(monkeypatch, toy, fast, passes):
    tpu = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(ps, "_fast_memory_bytes", lambda device: fast)
    monkeypatch.setattr(ps, "_PASS_BUDGET", 0.125)
    assert ps._worker_passes(toy, toy.params, *_shapes(64), tpu) == passes


@pytest.mark.parametrize("bundle", ["undeclared", "batch_mixing", "own_loss"])
def test_an_undeclared_bundle_is_never_split_whatever_its_size(monkeypatch, toy, bundle):
    monkeypatch.setattr(ps, "_fast_memory_bytes", lambda device: 1)
    bundle = {
        "undeclared": dataclasses.replace(toy, example_mean_loss=False),
        "batch_mixing": batch_mixing_toy(),
        "own_loss": make_bundle(GroupNormCNN(), (1, 8, 8, 3), loss_fn=toy.loss_fn),
    }[bundle]
    assert not bundle.example_mean_loss
    tpu = types.SimpleNamespace(platform="tpu")
    assert ps._worker_passes(bundle, bundle.params, *_shapes(4096), tpu) == 1


def test_a_device_that_is_no_tpu_never_splits(monkeypatch, toy):
    cpu = jax.devices()[0]
    assert cpu.platform == "cpu" and ps._fast_memory_bytes(cpu) is None
    assert ps._default_device() == cpu
    monkeypatch.setattr(ps, "_PASS_BUDGET", 1e-12)
    assert ps._worker_passes(toy, toy.params, *_shapes(4096), cpu) == 1


@pytest.mark.parametrize("name, declared", [
    ("mnist_mlp", True), ("mnist_cnn", True), ("cifar_resnet18", True),
    ("resnet_other_norm", False), ("own_module", False), ("own_loss", False),
    ("plain_bundle", False), ("segmented", False),
])
def test_who_declares(name, declared):
    import test_round_matrix_once as toys

    bundle = {
        "mnist_mlp": lambda: mnist_mlp(0, hidden=8),
        "mnist_cnn": lambda: mnist_cnn(0),
        "cifar_resnet18": lambda: cifar_resnet18(0),
        "resnet_other_norm": lambda: make_bundle(
            ResNet18(num_filters=8, norm=partial(nn.LayerNorm, use_bias=False)), (1, 8, 8, 3)),
        "own_module": lambda: make_bundle(GroupNormCNN(), (1, 8, 8, 3)),
        "own_loss": lambda: make_bundle(
            nn.Dense(10), (1, 4), loss_fn=lambda p, x, y: jnp.sum(nn.Dense(10).apply(p, x))),
        "plain_bundle": batch_mixing_toy,
        "segmented": toys._streamed_toy,
    }[name]()
    assert bundle.example_mean_loss is declared
    assert bundle.with_params(bundle.params).example_mean_loss is declared


# -- (c) a program that does not split is the program it was ---------------------


def _lowered_text(bundle, batches, **kwargs):
    step, opt_state = ps.build_ps_train_step(
        bundle, partial(robust.trimmed_mean, f=B), CFG, attack=_sign_flip, **kwargs)
    return jax.jit(step).lower(
        bundle.params, opt_state, batches[0][0], batches[1][0], jax.random.PRNGKey(0)).as_text()


@pytest.mark.parametrize("on", ["one_device", "mesh"])
def test_one_pass_leaves_the_lowered_text_alone(monkeypatch, toy, batches, on):
    """The rule asked and answering 1 (its abstract forward trace made, as on
    a TPU whose fast memory holds the batch) leaves no op in the program:
    the text of a bundle that never asks, byte for byte."""
    kwargs = {"mesh": node_mesh(4, devices=jax.devices()[:4])} if on == "mesh" else {}
    never_asks = _lowered_text(dataclasses.replace(toy, example_mean_loss=False), batches, **kwargs)
    assert _lowered_text(toy, batches, **kwargs) == never_asks  # a CPU: no TPU, no split
    traced = []
    real = ps._largest_activation_bytes
    monkeypatch.setattr(ps, "_fast_memory_bytes", lambda device: 1 << 40)
    monkeypatch.setattr(ps, "_largest_activation_bytes",
                        lambda *a: traced.append(real(*a)) or traced[-1])
    assert _lowered_text(toy, batches, **kwargs) == never_asks
    assert traced == [8 * 8 * 8 * 4]
    monkeypatch.setattr(ps, "_fast_memory_bytes", lambda device: 32768)  # four of eight fit
    split = _lowered_text(toy, batches, **kwargs)
    assert split != never_asks and "stream.passes" not in never_asks


# -- (d) the benchmark's counter -------------------------------------------------


def _reader():
    from test_round_matrix_once import _benchmark_reader

    return _benchmark_reader("worker_passes.train")


def _compiled_text(bundle, batches, **kwargs):
    step, opt_state = ps.build_ps_train_step(
        bundle, partial(robust.trimmed_mean, f=B), CFG, attack=_sign_flip, **kwargs)
    # (the persistent cache leaves metadata out of its key: a cached step
    # would come back with its own op_names)
    from test_model_scopes import _no_compile_cache

    with _no_compile_cache():
        return jax.jit(step).lower(
            bundle.params, opt_state, batches[0][0], batches[1][0],
            jax.random.PRNGKey(0)).compile().as_text()


@pytest.mark.parametrize("on", ["one_device", "mesh"])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_counter_reads_the_passes_off_the_compiled_step(monkeypatch, toy, batches, passes, on):
    _forced(monkeypatch, passes)
    kwargs = {"mesh": node_mesh(4, devices=jax.devices()[:4])} if on == "mesh" else {}
    ctx = types.SimpleNamespace(outcome={"compiled_text": _compiled_text(toy, batches, **kwargs)})
    assert _reader().read(ctx) == passes


@pytest.mark.parametrize("text", [None, "", "HloModule jit_step\nENTRY %main () -> f32[] {\n}\n"])
def test_the_counter_gives_nothing_where_there_is_no_round_to_read(text):
    assert _reader().read(types.SimpleNamespace(outcome={"compiled_text": text})) is None


# -- (e) rows kept in bfloat16 ---------------------------------------------------


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("on", ["one_device", "mesh"])
def test_rows_kept_in_bfloat16_are_cast_after_a_float32_sum(monkeypatch, toy, batches, on):
    _forced(monkeypatch, 4)
    kwargs = {"mesh": node_mesh(4, devices=jax.devices()[:4])} if on == "mesh" else {}
    step, opt_state = ps.build_ps_train_step(
        toy, partial(robust.trimmed_mean, f=B), CFG, attack=_sign_flip,
        grad_dtype=jnp.bfloat16, **kwargs)
    jaxpr = jax.make_jaxpr(step)(
        toy.params, opt_state, batches[0][0], batches[1][0], jax.random.PRNGKey(0))
    leaves = sorted(leaf.shape for leaf in jax.tree_util.tree_leaves(toy.params))
    scans = [eqn for eqn in _equations(jaxpr.jaxpr) if eqn.primitive.name == "scan"
             and eqn.params["length"] == 4]
    assert len(scans) == 1  # one trace of the model, four trips
    carried = [v.aval for v in scans[0].outvars[:scans[0].params["num_carry"]]]
    assert sorted(a.shape for a in carried) == leaves
    assert {a.dtype for a in carried} == {jnp.dtype(jnp.float32)}
    d = sum(int(np.prod(shape)) for shape in leaves)
    rows = [v.aval for eqn in _equations(jaxpr.jaxpr) for v in eqn.outvars
            if getattr(v.aval, "dtype", None) == jnp.bfloat16 and v.aval.size >= d]
    assert rows, "no worker's row is bfloat16"
    # and the round's values: the whole batch's rows, to bfloat16's rounding
    in_passes = _two_steps(toy, batches, kwargs.get("mesh"), grad_dtype=jnp.bfloat16)
    monkeypatch.undo()
    whole = _two_steps(toy, batches, kwargs.get("mesh"), grad_dtype=jnp.bfloat16)
    _assert_trees_close(in_passes[0], whole[0], rtol=2e-2, atol=2e-3)
