"""Fused SPMD parameter server: the TPU-native fast path.

The whole Byzantine-robust round — per-node gradients, sign-flip attack on
the byzantine shard, clipping pre-aggregation, trimmed-mean aggregation,
SGD update — is ONE jitted step over a device mesh. On a pod slice each
node's forward/backward runs on its own chip and the robust aggregation
shards over ICI; here it falls back to however many devices are visible
(force 8 virtual CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu``).

No reference equivalent — the reference's round always hops through host
actors (``byzpy/engine/parameter_server/ps.py:103-144``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))  # repo root

import os
from functools import partial

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp

from byzpy_tpu.models.data import (
    ShardedDataset,
    sample_node_batches,
    synthetic_classification,
)
from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.ops import attack_ops, preagg, robust
from byzpy_tpu.parallel.mesh import node_mesh, sharding
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

ROUNDS = int(os.environ.get("PS_ROUNDS", 30))
BATCH = 64


def main():
    n_devices = len(jax.devices())
    n_nodes = max(4, n_devices)
    n_byz = max(1, n_nodes // 4)
    mesh = node_mesh(min(n_nodes, n_devices))

    bundle = mnist_mlp(seed=0)
    cfg = PSStepConfig(n_nodes=n_nodes, n_byzantine=n_byz, learning_rate=0.1)

    def attack(honest, key):
        base = jnp.mean(honest, axis=0, keepdims=True)
        return jnp.tile(attack_ops.sign_flip(base, scale=-3.0), (n_byz, 1))

    step, opt_state = build_ps_train_step(
        bundle,
        partial(robust.trimmed_mean, f=n_byz),
        cfg,
        attack=attack,
        pre_aggregate=partial(preagg.clip_rows, threshold=100.0),
        mesh=mesh,
    )
    jit_step = jax.jit(step)

    x, y = synthetic_classification(n_samples=4096, seed=0)
    data = ShardedDataset(x, y, n_nodes)
    xs_all, ys_all = data.stacked_shards()
    node_shard = sharding(mesh, "nodes") if n_nodes == mesh.devices.size else None

    params = bundle.params
    key = jax.random.PRNGKey(0)
    for r in range(ROUNDS):
        key, bkey, skey = jax.random.split(key, 3)
        xs, ys = sample_node_batches(xs_all, ys_all, bkey, BATCH)
        if node_shard is not None:
            xs, ys = jax.device_put(xs, node_shard), jax.device_put(ys, node_shard)
        params, opt_state, metrics = jit_step(params, opt_state, xs, ys, skey)
        if (r + 1) % 10 == 0:
            logits = bundle.apply_fn(params, x)
            acc = float(jnp.mean(jnp.argmax(logits, -1) == y))
            print(
                f"round {r + 1}: honest_loss {float(metrics['honest_loss']):.3f} "
                f"accuracy {acc:.3f}"
            )
    final_acc = float(
        jnp.mean(jnp.argmax(bundle.apply_fn(params, x), -1) == y)
    )
    print(f"final accuracy after {ROUNDS} rounds: {final_acc:.3f}")
    assert final_acc > 0.5, "did not learn"


if __name__ == "__main__":
    main()
