"""Autonomous decentralized cluster: nodes self-drive via background
tasks and messages — no central round loop.

Reference semantics: ``byzpy/examples/p2p/decentralized_autonomous_mnist.py``
— each DecentralizedNode starts an autonomous task that repeatedly
half-steps, broadcasts its vector, collects neighbors' vectors, and
robust-aggregates; the main coroutine just waits for everyone to report
done.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))  # repo root

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import asyncio
import os

import jax.numpy as jnp
import numpy as np

from byzpy_tpu.aggregators import CoordinateWiseMedian
from byzpy_tpu.engine.node import DecentralizedCluster, DecentralizedNode, InProcessContext
from byzpy_tpu.engine.peer_to_peer import Topology

N_NODES = int(os.environ.get("N_NODES", 4))
ROUNDS = int(os.environ.get("P2P_ROUNDS", 15))
DIM = 32


def autonomous_loop(target, done_event):
    """Build the per-node background coroutine: descend ||w - target||²,
    gossip, aggregate, repeat."""

    async def run(node: DecentralizedNode):
        agg = CoordinateWiseMedian()
        w = jnp.zeros((DIM,))
        n_in = len(node.router.in_neighbor_ids())
        for _ in range(ROUNDS):
            w = w - 0.3 * 2.0 * (w - target)          # local half step
            await node.broadcast_message("gossip", w)  # tell out-neighbors
            received = [
                jnp.asarray((await node.wait_for_message("gossip")).payload)
                for _ in range(n_in)
            ]
            w = agg.aggregate([w] + received)           # robust consensus
        node.final_w = w
        done_event.set()

    return run


async def main():
    topology = Topology.complete(N_NODES)
    cluster = DecentralizedCluster(topology)
    nodes, events = [], []
    targets = np.linspace(0.0, 2.0, N_NODES)  # median target is the goal
    for i in range(N_NODES):
        node = DecentralizedNode(f"auto-{i}", InProcessContext(f"auto-{i}"))
        cluster.add_node(node)
        nodes.append(node)
        events.append(asyncio.Event())

    async with cluster:
        for node, target, event in zip(nodes, targets, events, strict=False):
            node.start_autonomous_task(autonomous_loop(float(target), event))
        await asyncio.gather(*(e.wait() for e in events))

    finals = np.stack([np.asarray(n.final_w) for n in nodes])
    print("per-node final w[0]:", np.round(finals[:, 0], 3))
    spread = finals[:, 0].max() - finals[:, 0].min()
    print(f"consensus spread: {spread:.4f}")
    assert spread < 0.15, "nodes did not reach consensus"


if __name__ == "__main__":
    asyncio.run(main())
