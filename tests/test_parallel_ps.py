"""SPMD parameter-server step: single-device vs 8-device-mesh parity, and
end-to-end robustness (training under attack still converges)."""

import os
import re
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models import mnist_mlp, synthetic_classification, ShardedDataset
from byzpy_tpu.models.bundle import ModelBundle, Segment
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel import (
    PSStepConfig,
    build_ps_train_step,
    grid_mesh,
    jit_ps_train_step,
    node_mesh,
)
from byzpy_tpu.parallel.ps import ShardedUpdateConfig, default_optimizer
from byzpy_tpu.parallel.quantization import CommPrecision

N_NODES = 8
N_BYZ = 2


@pytest.fixture(scope="module")
def setup():
    bundle = mnist_mlp(hidden=16)
    x, y = synthetic_classification(n_samples=512, seed=7)
    ds = ShardedDataset(x, y, n_nodes=N_NODES)
    xs, ys = ds.stacked_shards()
    return bundle, xs, ys


def _attack(honest, key):
    return attack_ops.empire(honest)  # -mean(honest), broadcast over byz rows


def test_ps_step_runs_and_updates(setup):
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ, learning_rate=0.05)
    step, opt0 = jit_ps_train_step(
        bundle,
        lambda m: robust.trimmed_mean(m, f=N_BYZ),
        cfg,
        attack=_attack,
        donate=False,
    )
    params, opt, metrics = step(bundle.params, opt0, xs, ys, jax.random.PRNGKey(0))
    before = jax.tree_util.tree_leaves(bundle.params)[0]
    after = jax.tree_util.tree_leaves(params)[0]
    assert not np.allclose(np.asarray(before), np.asarray(after))
    assert np.isfinite(float(metrics["honest_loss"]))


def test_ps_step_mesh_matches_single_device(setup):
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ)
    key = jax.random.PRNGKey(1)

    step1, opt1 = build_ps_train_step(
        bundle, lambda m: robust.coordinate_median(m), cfg, attack=_attack
    )
    p1, _, m1 = jax.jit(step1)(bundle.params, opt1, xs, ys, key)

    mesh = node_mesh(N_NODES)
    step8, opt8 = build_ps_train_step(
        bundle, lambda m: robust.coordinate_median(m), cfg, attack=_attack, mesh=mesh
    )
    p8, _, m8 = jax.jit(step8)(bundle.params, opt8, xs, ys, key)

    f1 = np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(p1)])
    f8 = np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(p8)])
    np.testing.assert_allclose(f8, f1, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        float(m8["honest_loss"]), float(m1["honest_loss"]), rtol=1e-4
    )


def test_ps_training_converges_under_attack(setup):
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ, learning_rate=0.1)
    mesh = node_mesh(N_NODES)
    step, opt0 = jit_ps_train_step(
        bundle,
        lambda m: robust.multi_krum(m, f=N_BYZ, q=N_NODES - N_BYZ),
        cfg,
        attack=_attack,
        mesh=mesh,
        donate=False,
    )
    params, opt = bundle.params, opt0
    losses = []
    for i in range(15):
        params, opt, metrics = step(params, opt, xs, ys, jax.random.PRNGKey(i))
        losses.append(float(metrics["honest_loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_ps_no_byzantine_plain_mean(setup):
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=0)
    step, opt0 = jit_ps_train_step(
        bundle, lambda m: jnp.mean(m, axis=0), cfg, donate=False
    )
    params, opt, metrics = step(bundle.params, opt0, xs, ys, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["agg_grad_norm"]))


def test_ps_step_2d_grid_mesh_matches_single_device(setup):
    """A (nodes, data) 2-D mesh must give the same round as no mesh: the
    batch axis shards over the data axis and the aggregation matrix
    feature-shards over ALL axes (no idle chips), changing layout only."""
    from byzpy_tpu.parallel import grid_mesh

    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=4, n_byzantine=1)
    xs4, ys4 = xs[:4], ys[:4]
    key = jax.random.PRNGKey(2)

    step1, opt1 = build_ps_train_step(
        bundle, lambda m: robust.coordinate_median(m), cfg, attack=_attack
    )
    p1, _, m1 = jax.jit(step1)(bundle.params, opt1, xs4, ys4, key)

    mesh = grid_mesh(4, 2)  # 4 nodes x 2-way intra-node data parallelism
    step2, opt2 = build_ps_train_step(
        bundle, lambda m: robust.coordinate_median(m), cfg,
        attack=_attack, mesh=mesh,
    )
    p2, _, m2 = jax.jit(step2)(bundle.params, opt2, xs4, ys4, key)

    f1 = np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(p1)])
    f2 = np.concatenate([np.ravel(l) for l in jax.tree_util.tree_leaves(p2)])
    np.testing.assert_allclose(f2, f1, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        float(m2["honest_loss"]), float(m1["honest_loss"]), rtol=1e-4
    )


def _flat(tree):
    return np.concatenate([np.ravel(leaf) for leaf in jax.tree_util.tree_leaves(tree)])


def _three_steps(step, params, opt, xs, ys):
    """Flat parameters after three steps, and every step's metrics."""
    seen = []
    for i in range(3):
        params, opt, metrics = step(params, opt, xs, ys, jax.random.PRNGKey(i))
        seen.append({name: float(value) for name, value in metrics.items()})
    return _flat(params), seen


# case: (workers, the mesh's shape). On a mesh a chip runs the workers it holds
# one after another where the node axis divides n, and the partitioner is
# handed a ``vmap`` over all n where it does not; a grid's second axis splits
# each worker's batch under either.
MESH_SHAPES = {
    "two_a_chip": (8, (4,)),
    "one_a_chip": (8, (8,)),
    "axis_does_not_divide": (6, (4,)),
    "eight_on_three": (8, (3,)),
    "grid_4x2": (4, (4, 2)),
    "grid_2x2_two_a_chip": (4, (2, 2)),
    "grid_does_not_divide": (6, (4, 2)),
}


@pytest.mark.parametrize("case", sorted(MESH_SHAPES))
def test_three_mesh_steps_give_the_single_device_rounds_values(setup, case):
    n, shape = MESH_SHAPES[case]
    bundle, xs, ys = setup
    xs, ys = xs[:n], ys[:n]
    cfg = PSStepConfig(n_nodes=n, n_byzantine=1)
    aggregate = partial(robust.trimmed_mean, f=1)
    devices = jax.devices()[:int(np.prod(shape))]
    mesh = node_mesh(shape[0], devices=devices) if len(shape) == 1 else grid_mesh(
        *shape, devices=devices)
    one, opt1 = jit_ps_train_step(bundle, aggregate, cfg, attack=_attack, donate=False)
    many, opt_m = jit_ps_train_step(
        bundle, aggregate, cfg, attack=_attack, mesh=mesh, donate=False)
    want, want_metrics = _three_steps(one, bundle.params, opt1, xs, ys)
    got, got_metrics = _three_steps(many, bundle.params, opt_m, xs, ys)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(got_metrics, want_metrics):
        assert a == pytest.approx(b, rel=1e-4)


_S4_EF = CommPrecision(mode="s4", error_feedback=True)
FABRICS = {
    "update_sharded": dict(sharded_update="on"),
    "update_replicated": dict(sharded_update="off"),
    "transpose_s4_ef": dict(comm_precision=_S4_EF),
    "transpose_int8_update_off": dict(comm_precision="int8", sharded_update="off"),
    "transpose_and_gather_s4_ef": dict(comm_precision=_S4_EF, sharded_update=ShardedUpdateConfig(
        mode="on", param_gather_precision=_S4_EF)),
}


@pytest.mark.parametrize("fabric", sorted(FABRICS))
def test_the_loops_rows_cross_every_fabric_as_the_vmapped_rows_do(setup, fabric):
    """What follows ``round.fwdbwd`` (the compressed transpose and its
    residual, the sharded update, the gather) is handed the same ``(n, d)``
    node-sharded rows by the per-chip loop as by the ``vmap`` over all n:
    three steps of 8 workers on 4 chips against the parent's program, which
    a node axis that does not divide n still gets (8 on 3). Error feedback
    keeps an ``(n, d)`` residual on the node axis, which has to divide n:
    there the other side is a worker a chip (8 on 8), whose loop of one trip
    is no loop once compiled. (A compressed fabric rounds: a float's last bit
    can move one code, so the values are held as the mesh shapes' are.)"""
    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ)
    aggregate = partial(robust.trimmed_mean, f=N_BYZ)
    error_feedback = "ef" in fabric

    def three_steps_on(chips):
        mesh = node_mesh(chips, devices=jax.devices()[:chips])
        step, opt = jit_ps_train_step(
            bundle, aggregate, cfg, attack=_attack, mesh=mesh, donate=False, **FABRICS[fabric])
        text = step.lower(bundle.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()
        assert ("stablehlo.while" in text) == (N_NODES % chips == 0)
        return _three_steps(step, bundle.params, opt, xs, ys)

    looped, looped_metrics = three_steps_on(4)
    other, other_metrics = three_steps_on(8 if error_feedback else 3)
    np.testing.assert_allclose(looped, other, rtol=2e-4, atol=2e-5)
    assert len(looped_metrics) == len(other_metrics) == 3
    for a, b in zip(looped_metrics, other_metrics):
        assert set(a) == set(b) and a == pytest.approx(b, rel=1e-4)


class _ActorHonestNode:
    """Actor-mode honest node holding its own (replicated) params; applies
    the server gradient with the same optax chain the SPMD step uses."""

    def __init__(self, bundle, opt, x, y):
        self.bundle = bundle
        self.opt = opt
        self.params = bundle.params
        self.opt_state = opt.init(bundle.params)
        self.x, self.y = x, y
        from byzpy_tpu.utils.trees import ravel_pytree_fn

        self._ravel, self._unravel = ravel_pytree_fn(bundle.params)

    def honest_gradient_for_next_batch(self):
        g = jax.grad(self.bundle.loss_fn)(self.params, self.x, self.y)
        return [self._ravel(g)]

    def apply_server_gradient(self, g):
        import optax

        update = self._unravel(jnp.asarray(g[0]))
        updates, self.opt_state = self.opt.update(
            update, self.opt_state, self.params
        )
        self.params = optax.apply_updates(self.params, updates)


class _ActorEmpireNode(_ActorHonestNode):
    def byzantine_gradient_for_next_batch(self, honest):
        stacked = jnp.stack([jnp.asarray(h[0]) for h in honest])
        return [attack_ops.empire(stacked)]


def test_actor_ps_matches_fused_spmd_ps(setup):
    """The one seam between the two PS implementations:
    actor-mode rounds (engine/parameter_server/ps.py) and the fused SPMD
    step (parallel/ps.py) must produce the same trajectory on a fixed
    seed — same shards, same empire attack, same trimmed-mean, same
    SGD+momentum."""
    import asyncio

    from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
    from byzpy_tpu.engine.parameter_server import ParameterServer
    from byzpy_tpu.parallel.ps import default_optimizer

    bundle, xs, ys = setup
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ, learning_rate=0.05)
    rounds = 5

    # -- fused SPMD trajectory
    step, opt0 = jit_ps_train_step(
        bundle, lambda m: robust.trimmed_mean(m, f=N_BYZ), cfg,
        attack=_attack, donate=False,
    )
    params = bundle.params
    opt_state = opt0
    key = jax.random.PRNGKey(0)  # empire ignores the key; fixed for form
    for _ in range(rounds):
        params, opt_state, _ = step(params, opt_state, xs, ys, key)

    # -- actor-mode trajectory over the SAME shards
    opt = default_optimizer(cfg)
    h = cfg.n_honest
    honest_nodes = [
        _ActorHonestNode(bundle, opt, xs[i], ys[i]) for i in range(h)
    ]
    byz_nodes = [
        _ActorEmpireNode(bundle, opt, xs[h + j], ys[h + j])
        for j in range(N_BYZ)
    ]
    ps = ParameterServer(
        honest_nodes, byz_nodes,
        aggregator=CoordinateWiseTrimmedMean(f=N_BYZ),
    )
    for _ in range(rounds):
        asyncio.run(ps.round())

    f_spmd = np.concatenate(
        [np.ravel(l) for l in jax.tree_util.tree_leaves(params)]
    )
    for node in honest_nodes + byz_nodes:
        f_actor = np.concatenate(
            [np.ravel(l) for l in jax.tree_util.tree_leaves(node.params)]
        )
        np.testing.assert_allclose(f_actor, f_spmd, rtol=2e-4, atol=2e-5)


def _chain():
    """The MLP as a chain of two links, each with its own subtree."""
    def body(p, x):
        return jnp.tanh(x.reshape(x.shape[0], -1) @ p["w"])

    def head(p, hidden, y):
        return optax.softmax_cross_entropy_with_integer_labels(hidden @ p["w"], y).mean()

    kb, kh = jax.random.split(jax.random.PRNGKey(0))
    return ModelBundle(
        apply_fn=None, segments=(Segment("body", body), Segment("head", head)),
        params={"body": {"w": 0.05 * jax.random.normal(kb, (784, 16))},
                "head": {"w": 0.1 * jax.random.normal(kh, (16, 10))}})


def _like(tree):
    return jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)


# case: (a segmented bundle, a mesh, keyword arguments, the round it gets)
DISPATCH = {
    "plain_no_mesh": (False, False, {}, "one_device"),
    "plain_mesh": (False, True, {}, "mesh"),
    "segmented_no_mesh": (True, False, {}, "streamed"),
    "segmented_mesh": (True, True, {}, "mesh"),
    "flat_update_no_mesh": (False, False, {"sharded_update": "on"}, "one_device_flat"),
    "streamed_refusal": (True, False, {"aggregate": lambda m: robust.multi_krum(m, f=2, q=4)},
                         "refused"),
}


@pytest.mark.parametrize("case", sorted(DISPATCH))
def test_the_dispatcher_gives_each_call_its_round(setup, case):
    """``build_ps_train_step`` decides one thing, which round a call gets.
    What tells the three apart from outside: the structure of ``opt_state0``
    and whether the compiled step holds a collective. Every round's step is
    called ``train_step`` (the traced module ``jit_train_step`` is what the
    benchmark's drivers and the scope tests name)."""
    segmented, on_mesh, kwargs, want = DISPATCH[case]
    kwargs = dict(kwargs)
    _, xs, ys = setup
    bundle = _chain() if segmented else mnist_mlp(hidden=16)
    cfg = PSStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ)
    aggregate = kwargs.pop("aggregate", partial(robust.trimmed_mean, f=N_BYZ))
    attack = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
    mesh = node_mesh(N_NODES) if on_mesh else None
    if want == "refused":
        with pytest.raises(ValueError, match=re.escape("byzpy_tpu/ops/coordinatewise.py")):
            build_ps_train_step(bundle, aggregate, cfg, attack=attack, mesh=mesh, **kwargs)
        return
    step, opt0 = build_ps_train_step(bundle, aggregate, cfg, attack=attack, mesh=mesh, **kwargs)
    assert step.__name__ == "train_step"
    opt = default_optimizer(cfg)
    d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(bundle.params))
    if want == "one_device":
        assert _like(opt0) == _like(opt.init(bundle.params))
    elif want == "streamed":
        assert _like(opt0) == _like({key: opt.init(sub) for key, sub in bundle.params.items()})
    else:  # the flat update: on one device d wide, on the mesh padded to its grid and sharded
        flat, inner = opt0
        width = d if want == "one_device_flat" else -(-d // N_NODES) * N_NODES
        assert flat.shape == (width,) and _like(inner) == _like(opt.init(flat))
        assert len(flat.sharding.device_set) == (N_NODES if on_mesh else 1)
    text = jax.jit(step).lower(bundle.params, opt0, xs, ys, jax.random.PRNGKey(0)).compile().as_text()
    collectives = re.findall(r"= \S+ (all-to-all|all-gather|all-reduce|collective-permute)\(", text)
    assert bool(collectives) == on_mesh


if __name__ == "__main__":
    # python tests/test_parallel_ps.py <parent tree> <out dir> [group ...]: the
    # round programs of that tree and of this one, as text, side by side
    # (tests/round_texts.py; groups default to the toys, `tpu` and `mesh`)
    here = os.path.dirname(os.path.abspath(__file__))
    parent, out, *groups = sys.argv[1:]
    sides = {"parent": parent, "change": os.path.dirname(here)}
    env = dict(os.environ, ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    for group in groups or ["tpu", "mesh"]:
        for side, tree in sides.items():
            subprocess.run([sys.executable, os.path.join(here, "round_texts.py"), "write", tree,
                            os.path.join(out, side), group], env=env, check=True)
    sys.exit(subprocess.run(
        [sys.executable, os.path.join(here, "round_texts.py"), "compare",
         *(os.path.join(out, side) for side in sides)], check=False).returncode)
