"""Decentralized (P2P) robust learning on REAL data.

The fully-decentralized counterpart of ``examples/ps/real_data_robust.py``:
every honest peer half-steps SGD on its own shard of the real
handwritten-digits dataset, gossips parameters over the topology, and
robust-aggregates what it received; byzantine peers broadcast a sign-flip
vector. The whole round — n half-steps, the broadcast matrix, per-node
trimmed-mean over in-neighborhoods — is ONE jitted SPMD program
(:func:`byzpy_tpu.parallel.gossip.build_gossip_train_step`).

Compare the two runs it prints: with plain-mean gossip the byzantine
broadcasts poison every node (accuracy collapses to ~10%); trimmed-mean
gossip learns through them.

Reference analogue: ``byzpy/examples/p2p/`` trains MNIST with torch
workers over actor topologies.

Run: ``XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu python examples/p2p/real_data_gossip.py``
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))  # repo root

from functools import partial

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

ROUNDS = int(os.environ.get("P2P_ROUNDS", 200))


def run(aggregator_fn, label):
    import jax
    import jax.numpy as jnp

    from byzpy_tpu.engine.peer_to_peer import Topology
    from byzpy_tpu.models.data import (
        ShardedDataset,
        load_digits_dataset,
        sample_node_batches,
    )
    from byzpy_tpu.models.nets import digits_mlp
    from byzpy_tpu.ops import attack_ops
    from byzpy_tpu.parallel.gossip import GossipStepConfig, build_gossip_train_step

    n_nodes, n_byz = 8, 2
    x_train, y_train, x_test, y_test = load_digits_dataset(seed=0)
    bundle = digits_mlp(seed=0)
    cfg = GossipStepConfig(n_nodes=n_nodes, n_byzantine=n_byz, learning_rate=0.1)

    def attack(honest_thetas, key):
        return jnp.tile(
            attack_ops.sign_flip(jnp.mean(honest_thetas, axis=0), scale=-3.0)[None, :],
            (n_byz, 1),
        )

    step, init = build_gossip_train_step(
        bundle, aggregator_fn, Topology.complete(n_nodes), cfg, attack=attack
    )
    jit_step = jax.jit(step)

    data = ShardedDataset(x_train, y_train, n_nodes)
    xs_all, ys_all = data.stacked_shards()
    theta = init()
    key = jax.random.PRNGKey(0)
    batch = 32
    for _ in range(ROUNDS):
        key, bkey, skey = jax.random.split(key, 3)
        xs, ys = sample_node_batches(xs_all, ys_all, bkey, batch)
        theta, _ = jit_step(theta, xs, ys, skey)

    # evaluate node 0's model (honest) on held-out data
    from byzpy_tpu.utils.trees import ravel_pytree_fn

    _, unravel = ravel_pytree_fn(bundle.params)
    params0 = unravel(theta[0])
    logits = bundle.apply_fn(params0, x_test)
    acc = float(jnp.mean(jnp.argmax(logits, -1) == y_test))
    print(f"{label}: node-0 held-out accuracy {acc:.3f}")
    return acc


def main():
    import jax.numpy as jnp

    from byzpy_tpu.ops import robust

    acc_mean = run(lambda m: jnp.mean(m, axis=0), "plain-mean gossip ")
    acc_tm = run(partial(robust.trimmed_mean, f=2), "trimmed-mean gossip")
    if ROUNDS >= 100:  # smoke runs with tiny ROUNDS can't reach the contract
        assert acc_mean < 0.5, "mean gossip should be poisoned"
        assert acc_tm > 0.8, "robust gossip should learn"
    print(
        f"\nsign-flip broadcasters: mean gossip ends at {acc_mean:.1%} "
        f"(poisoned), trimmed-mean at {acc_tm:.1%} (rescued)"
    )


if __name__ == "__main__":
    main()
