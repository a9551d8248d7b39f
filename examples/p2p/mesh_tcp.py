"""Serverless full-mesh TCP cluster on loopback.

Reference semantics: ``byzpy/examples/p2p/remote_tcp/mesh_client.py`` —
every node runs its own TCP server and dials its peers; in production each
node is a separate host process (fill the address book with real
host:port pairs), here all three live in one event loop on loopback.
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

from byzpy_tpu.engine.node import DecentralizedNode, MeshRemoteContext
from byzpy_tpu.engine.peer_to_peer import Topology

N_NODES = int(os.environ.get("N_NODES", 3))


async def main():
    topology = Topology.complete(N_NODES)
    ids = {i: f"mesh-{i}" for i in range(N_NODES)}

    # start every node's server on an ephemeral port, then share the book
    ctxs = [MeshRemoteContext(ids[i]) for i in range(N_NODES)]
    nodes = []
    received = {ids[i]: [] for i in range(N_NODES)}
    all_in = asyncio.Event()

    def check_done() -> None:
        if all(len(v) >= N_NODES - 1 for v in received.values()):
            all_in.set()

    for i, ctx in enumerate(ctxs):
        node = DecentralizedNode(ids[i], ctx)
        node.bind_topology(topology, ids)

        async def keep(message, store=received[ids[i]]):
            store.append(message)
            check_done()

        node.register_handler("gradient", keep)
        await node.start()
        nodes.append(node)
    book = {c.node_id: (c.host, c.port) for c in ctxs}
    for ctx in ctxs:
        for pid, addr in book.items():
            if pid != ctx.node_id:
                ctx.add_peer(pid, addr)

    # everyone gossips a vector; everyone receives from all peers
    # (event-driven, not a sleep-poll loop: the handler signals arrival)
    for i, node in enumerate(nodes):
        await node.broadcast_message("gradient", jnp.full((8,), float(i)))
    await asyncio.wait_for(all_in.wait(), timeout=30.0)

    for nid, msgs in received.items():
        senders = sorted(m.sender for m in msgs)
        print(f"{nid} received from {senders}")
        assert len(msgs) == N_NODES - 1
        assert all(isinstance(m.payload, np.ndarray) for m in msgs)

    for node in nodes:
        await node.shutdown()
    print("mesh OK")


if __name__ == "__main__":
    asyncio.run(main())
