"""Peer-to-peer gossip training with a byzantine peer.

Reference semantics: ``byzpy/examples/p2p/`` — every peer half-steps on
its shard, gossips θ½ over the topology, robust-aggregates what it
received; one byzantine peer broadcasts an Empire vector.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))  # repo root

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import os

import jax
import jax.numpy as jnp
import numpy as np

from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
from byzpy_tpu.attacks import EmpireAttack
from byzpy_tpu.engine.peer_to_peer import (
    AttackP2PWorker,
    PeerToPeer,
    SGDModelWorker,
    Topology,
)
from byzpy_tpu.models.data import ShardedDataset, synthetic_classification
from byzpy_tpu.models.nets import mnist_mlp

N_NODES = int(os.environ.get("N_NODES", 5))
N_BYZ = int(os.environ.get("N_BYZ", 1))
ROUNDS = int(os.environ.get("P2P_ROUNDS", 40))
BATCH = 64


def make_worker(data, i):
    bundle = mnist_mlp(seed=0)
    sx, sy = data.node_slice(i)
    rng = np.random.default_rng(i)

    def batch_fn():
        idx = rng.integers(0, sx.shape[0], size=BATCH)
        return sx[idx], sy[idx]

    return SGDModelWorker(bundle, batch_fn)


def main():
    x, y = synthetic_classification(n_samples=4096, seed=0)
    n_honest = N_NODES - N_BYZ
    data = ShardedDataset(x, y, n_honest)
    workers = [make_worker(data, i) for i in range(n_honest)]
    byz = [AttackP2PWorker(EmpireAttack(scale=-3.0)) for _ in range(N_BYZ)]

    p2p = PeerToPeer(
        workers,
        byz,
        aggregator=CoordinateWiseTrimmedMean(f=N_BYZ),
        topology=Topology.complete(N_NODES),
        learning_rate=0.1,
    )
    p2p.run(rounds=ROUNDS)

    bundle = mnist_mlp(seed=0).with_params(workers[0].params)
    logits = bundle.apply_fn(bundle.params, x)
    acc = float(jnp.mean(jnp.argmax(logits, -1) == y))
    print(f"{ROUNDS} rounds: worker-0 accuracy {acc:.3f}")
    assert acc > 0.5, "did not learn"


if __name__ == "__main__":
    main()
