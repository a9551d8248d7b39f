"""Parameter-server training with thread-actor nodes.

Reference semantics: ``byzpy/examples/ps/thread/mnist.py`` — n honest
nodes each training an MLP on their shard, f byzantine nodes sign-flipping,
robust aggregation with coordinate-wise trimmed mean, accuracy printed
every few rounds.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))  # repo root

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import asyncio
import os

import jax
import jax.numpy as jnp

from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
from byzpy_tpu.engine.node.actors import ByzantineNodeActor, HonestNodeActor
from byzpy_tpu.engine.node.base import ByzantineNode, HonestNode
from byzpy_tpu.engine.parameter_server import ParameterServer
from byzpy_tpu.models.data import ShardedDataset, sample_batch, synthetic_classification
from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.utils.training import train_with_progress_async

N_NODES = int(os.environ.get("N_NODES", 6))
N_BYZ = int(os.environ.get("N_BYZ", 2))
ROUNDS = int(os.environ.get("PS_ROUNDS", 30))
BATCH = 64
LR = 0.1


class MnistNode(HonestNode):
    """One honest worker: its own shard, jitted grad, SGD apply."""

    def __init__(self, shard_x, shard_y, seed):
        self.bundle = mnist_mlp(seed=0)  # common init across nodes
        self.x, self.y = shard_x, shard_y
        self.key = jax.random.PRNGKey(seed)
        self._grad = jax.jit(jax.grad(self.bundle.loss_fn))

    def next_batch(self):
        self.key, sub = jax.random.split(self.key)
        return sample_batch(self.x, self.y, sub, BATCH)

    def honest_gradient(self, x, y):
        return self._grad(self.bundle.params, x, y)

    def apply_server_gradient(self, gradient):
        self.bundle = self.bundle.with_params(
            jax.tree_util.tree_map(
                lambda p, g: p - LR * g, self.bundle.params, gradient
            )
        )

    def accuracy(self, x, y):
        logits = self.bundle.apply_fn(self.bundle.params, x)
        return float(jnp.mean(jnp.argmax(logits, -1) == y))


class SignFlipNode(ByzantineNode):
    def next_batch(self):
        return None, None

    def byzantine_gradient(self, honest_gradients):
        mean = jax.tree_util.tree_map(
            lambda *gs: sum(gs) / len(gs), *honest_gradients
        )
        return jax.tree_util.tree_map(lambda g: -3.0 * g, mean)

    def apply_server_gradient(self, gradient):
        pass


async def main():
    x, y = synthetic_classification(n_samples=4096, seed=0)
    data = ShardedDataset(x, y, N_NODES)

    honest = [
        await HonestNodeActor.spawn(MnistNode, *data.node_slice(i), i, backend="thread")
        for i in range(N_NODES)
    ]
    byz = [
        await ByzantineNodeActor.spawn(SignFlipNode, backend="thread")
        for _ in range(N_BYZ)
    ]
    ps = ParameterServer(
        honest, byz, aggregator=CoordinateWiseTrimmedMean(f=N_BYZ)
    )

    async def evaluate(i):
        acc = await honest[0].accuracy(x, y)
        print(f"round {i + 1}: accuracy {acc:.3f}")
        return acc

    history = await train_with_progress_async(
        ps, ROUNDS, eval_callback=evaluate, eval_interval=10, progress=False
    )
    assert history[-1][1] > 0.5, "did not learn"
    for a in honest + byz:
        await a.close()


if __name__ == "__main__":
    asyncio.run(main())
