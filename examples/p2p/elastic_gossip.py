"""Elastic gossip: P2P training through a peer death (no reference analogue).

The decentralized twin of ``examples/ps/elastic_crash_recovery.py``:
four peers gossip toward consensus under coordinate-wise median; peer 3
dies unannounced mid-training; the built-in elastic policy
(``PeerToPeer(..., elastic=HeartbeatPolicy(...))``) suspects it via
heartbeats and excises it from the fabric, after which rounds keep
completing over the induced 3-node topology and consensus re-forms
WITHOUT the dead peer's (outlier) target. No monitor/callback wiring in
application code — detection and excision ship as one constructor knob.

Run: ``python examples/p2p/elastic_gossip.py``.
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp
import numpy as np

from byzpy_tpu.aggregators import CoordinateWiseMedian
from byzpy_tpu.engine.peer_to_peer import HeartbeatPolicy, PeerToPeer, Topology
from byzpy_tpu.engine.peer_to_peer.nodes import HonestP2PWorker

ROUNDS = int(os.environ.get("P2P_ROUNDS", 30))
DIM = 8


class QuadWorker(HonestP2PWorker):
    """Descends ||w - target||^2; gossip payload is the half-stepped w."""

    def __init__(self, target):
        self.target = jnp.full((DIM,), float(target), jnp.float32)
        self.w = jnp.zeros((DIM,), jnp.float32)

    def half_step(self, lr):
        self.w = self.w - lr * 2.0 * (self.w - self.target)
        return self.w

    def parameters(self):
        return self.w

    def apply_aggregate(self, vector):
        self.w = jnp.asarray(vector)


async def main() -> None:
    workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 50.0)]
    p2p = PeerToPeer(
        workers, aggregator=CoordinateWiseMedian(),
        topology=Topology.complete(4), learning_rate=0.3,
        elastic=HeartbeatPolicy(interval=0.1, max_missed=3),
    )
    runner = p2p.runner
    async with runner:
        for r in range(ROUNDS):
            await p2p.round()
            if r == ROUNDS // 3 and 3 in runner.nodes:
                victim_id = runner.node_ids[3]
                print(f"round {r + 1}: killing peer {victim_id} (target 50)")
                await runner.nodes[3].shutdown()
                # the shipped policy notices and excises — just wait for it
                for _ in range(300):
                    if (victim_id, "removed") in runner.elastic_events:
                        print(f"  [policy] suspected {victim_id} -> excised")
                        break
                    await asyncio.sleep(0.05)
                else:
                    raise TimeoutError("policy never excised the dead peer")
            if (r + 1) % 10 == 0:
                ws = [float(np.mean(workers[i].w)) for i in (0, 1, 2)]
                print(f"round {r + 1:3d}: survivor means "
                      f"{['%.3f' % v for v in ws]}")

    if ROUNDS >= 20:
        for i in (0, 1, 2):
            err = abs(float(np.mean(workers[i].w)) - 1.0)
            assert err < 0.2, (i, workers[i].w)
        print("consensus re-formed at the survivors' median target (1.0), "
              "free of the dead peer's outlier (50.0)")


if __name__ == "__main__":
    asyncio.run(main())
