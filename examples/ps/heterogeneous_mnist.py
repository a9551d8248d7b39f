"""Heterogeneous parameter server: device-pinned nodes + process nodes.

Reference semantics: ``byzpy/examples/ps/heterogenous/`` — a mixed fleet
where some workers sit on accelerators and others in host processes, all
driven by one PS round loop. Here the fast nodes use the ``tpu`` actor
backend (state pinned as device arrays on a chip; falls back to ``thread``
off-TPU) and the slow cohort lives in spawned OS processes, exercising the
shm payload path. The aggregation itself is scheduled on a mixed
ActorPool whose chunk subtasks carry capability affinities.

    python examples/ps/heterogeneous_mnist.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import asyncio

import jax

if os.environ.get("BYZPY_TPU_PLATFORM"):  # see remote_tcp/node_server.py
    jax.config.update("jax_platforms", os.environ["BYZPY_TPU_PLATFORM"])

import jax.numpy as jnp

from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
from byzpy_tpu.engine.graph.pool import ActorPool, ActorPoolConfig
from byzpy_tpu.engine.node.actors import ByzantineNodeActor, HonestNodeActor
from byzpy_tpu.engine.node.base import ByzantineNode, HonestNode
from byzpy_tpu.engine.parameter_server import ParameterServer
from byzpy_tpu.models.data import ShardedDataset, sample_batch, synthetic_classification
from byzpy_tpu.models.nets import mnist_mlp

N_FAST = int(os.environ.get("N_FAST", 2))     # device-pinned nodes
N_SLOW = int(os.environ.get("N_SLOW", 2))     # process nodes
N_BYZ = int(os.environ.get("N_BYZ", 1))
ROUNDS = int(os.environ.get("PS_ROUNDS", 10))
BATCH = 64
LR = 0.1


class MnistNode(HonestNode):
    def __init__(self, shard_x, shard_y, seed):
        self.bundle = mnist_mlp(seed=0)
        self.x, self.y = jnp.asarray(shard_x), jnp.asarray(shard_y)
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
                lambda p, g: p - LR * jnp.asarray(g), self.bundle.params, gradient
            )
        )

    def accuracy(self, x, y):
        logits = self.bundle.apply_fn(self.bundle.params, jnp.asarray(x))
        return float(jnp.mean(jnp.argmax(logits, -1) == jnp.asarray(y)))


class SignFlipNode(ByzantineNode):
    def next_batch(self):
        return None, None

    def byzantine_gradient(self, honest_gradients):
        mean = jax.tree_util.tree_map(
            lambda *gs: sum(jnp.asarray(g) for g in gs) / len(gs), *honest_gradients
        )
        return jax.tree_util.tree_map(lambda g: -3.0 * g, mean)

    def apply_server_gradient(self, gradient):
        pass


def fast_backend() -> str:
    return "tpu" if jax.default_backend() == "tpu" else "thread"


async def main() -> None:
    import numpy as np

    n_honest = N_FAST + N_SLOW
    x, y = synthetic_classification(n_samples=4096, seed=0)
    data = ShardedDataset(x, y, n_honest)

    honest = []
    for i in range(n_honest):
        backend = fast_backend() if i < N_FAST else "process"
        sx, sy = data.node_slice(i)
        honest.append(
            await HonestNodeActor.spawn(
                MnistNode, np.asarray(sx), np.asarray(sy), i, backend=backend
            )
        )
    byz = [
        await ByzantineNodeActor.spawn(SignFlipNode, backend="thread")
        for _ in range(N_BYZ)
    ]

    # mixed aggregation pool: one device-capable worker + two host workers;
    # the trimmed-mean feature chunks carry no affinity so any worker takes
    # them, while device-affine subtasks would route to the tpu worker
    pool_cfg = [
        ActorPoolConfig(backend=fast_backend(), count=1, name="devw"),
        ActorPoolConfig(backend="process", count=2, name="hostw"),
    ]
    async with ActorPool(pool_cfg) as pool:
        print("pool workers:", {n: sorted(c) for n, c in pool.worker_capabilities.items()})
        ps = ParameterServer(
            honest, byz,
            aggregator=CoordinateWiseTrimmedMean(f=N_BYZ, chunk_size=16384),
            pool=pool,
        )
        for r in range(ROUNDS):
            await ps.round()
            if (r + 1) % 5 == 0 or r == ROUNDS - 1:
                acc = await honest[0].accuracy(x[:512], y[:512])
                print(f"round {r + 1:3d}  accuracy {acc:.3f}", flush=True)

    for actor in honest + byz:
        await actor.close()
    print("done", flush=True)


if __name__ == "__main__":
    asyncio.run(main())
