"""Elastic P2P: gossip training continues through a node death.

The PS analogue lives in ``ParameterServer(elastic=...)``; for the
decentralized fabric the policy loop is liveness-driven —
``HeartbeatMonitor.on_suspect -> DecentralizedPeerToPeer.remove_node`` —
after which the survivors gossip over the induced sub-topology with
shrunken expected-message counts.
"""

from __future__ import annotations

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.aggregators import CoordinateWiseMedian
from byzpy_tpu.engine.node.context import InProcessContext
from byzpy_tpu.engine.node.liveness import HeartbeatMonitor
from byzpy_tpu.engine.peer_to_peer import HeartbeatPolicy, Topology
from byzpy_tpu.engine.peer_to_peer.nodes import HonestP2PWorker
from byzpy_tpu.engine.peer_to_peer.runner import DecentralizedPeerToPeer


class QuadWorker(HonestP2PWorker):
    def __init__(self, target, dim=6):
        self.target = jnp.full((dim,), float(target), jnp.float32)
        self.w = jnp.zeros((dim,), jnp.float32)

    def half_step(self, lr):
        self.w = self.w - lr * 2.0 * (self.w - self.target)
        return self.w

    def parameters(self):
        return self.w

    def apply_aggregate(self, vector):
        self.w = jnp.asarray(vector)


@pytest.fixture(autouse=True)
def clean_registry():
    InProcessContext._registry.clear()
    yield
    InProcessContext._registry.clear()


def test_remove_node_mid_training_rounds_continue():
    """Train, excise a node, keep training: the survivors' expected
    counts shrink with the induced topology and consensus proceeds
    without the removed peer."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(4), learning_rate=0.3,
        )
        async with p2p:
            for _ in range(3):
                await p2p.run_round_async()
            assert p2p._honest_expected(0) == 3
            await p2p.remove_node(3)  # the outlier-target peer leaves
            assert p2p.honest_indices == [0, 1, 2]
            assert p2p._honest_expected(0) == 2
            for _ in range(30):
                await p2p.run_round_async()
            # consensus over the survivors' targets (median of 0, 1, 2),
            # no longer dragged by the removed node's target 9
            for i in (0, 1, 2):
                np.testing.assert_allclose(
                    np.asarray(workers[i].w), 1.0, atol=0.1
                )
            assert p2p.rounds_completed == 33
    asyncio.run(run())


def test_remove_node_guards():
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(2), learning_rate=0.3,
        )
        async with p2p:
            with pytest.raises(KeyError):
                await p2p.remove_node(7)
            await p2p.remove_node(1)
            with pytest.raises(ValueError, match="last honest node"):
                await p2p.remove_node(0)
    asyncio.run(run())


def test_remove_node_rejects_unbounded_gossip_timeout():
    """gossip_timeout=None would make removal wait forever on an
    in-flight round's dead-peer gossip (advisor r4) — refused up front."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(3), learning_rate=0.3,
            gossip_timeout=None,
        )
        async with p2p:
            with pytest.raises(ValueError, match="finite gossip_timeout"):
                await p2p.remove_node(2)
    asyncio.run(run())


def test_heartbeat_drives_removal_end_to_end():
    """The full policy loop: a peer DIES (shutdown, no goodbye), the
    observer's heartbeat monitor suspects it, on_suspect excises it from
    the runner, and training rounds keep completing."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(4), learning_rate=0.3,
        )
        async with p2p:
            await p2p.run_round_async()
            removed = asyncio.Event()
            victim_gi = 3
            victim_id = p2p.node_ids[victim_gi]

            def on_suspect(peer_id):
                assert peer_id == victim_id

                async def act():
                    await p2p.remove_node(victim_gi)
                    removed.set()
                asyncio.get_running_loop().create_task(act())

            for gi, node in p2p.nodes.items():
                if gi != 0:
                    HeartbeatMonitor.install_responder(node)
            mon = HeartbeatMonitor(
                p2p.nodes[0], interval=0.05, max_missed=3,
                on_suspect=on_suspect,
            )
            await mon.start()
            try:
                # wait for the monitor to see everyone, then kill the peer
                for _ in range(100):
                    if len(mon.alive()) == 3:
                        break
                    await asyncio.sleep(0.05)
                await p2p.nodes[victim_gi].shutdown()
                await asyncio.wait_for(removed.wait(), timeout=10.0)
                for _ in range(20):
                    await p2p.run_round_async()
                for i in (0, 1, 2):
                    np.testing.assert_allclose(
                        np.asarray(workers[i].w), 1.0, atol=0.15
                    )
            finally:
                await mon.stop()
    asyncio.run(run())


def test_heartbeat_policy_excises_dead_peer_without_wiring():
    """The shipped default policy: construct with
    ``elastic=HeartbeatPolicy(...)`` and a dead peer is excised with NO
    test-side monitor/responder/callback wiring at all."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(4), learning_rate=0.3,
            elastic=HeartbeatPolicy(interval=0.05, max_missed=3),
        )
        async with p2p:
            await p2p.run_round_async()
            victim_id = p2p.node_ids[3]
            await p2p.nodes[3].shutdown()  # dies, no goodbye
            for _ in range(200):
                if (victim_id, "removed") in p2p.elastic_events:
                    break
                await asyncio.sleep(0.05)
            assert (victim_id, "removed") in p2p.elastic_events
            assert p2p.honest_indices == [0, 1, 2]
            for _ in range(20):
                await p2p.run_round_async()
            for i in (0, 1, 2):
                np.testing.assert_allclose(
                    np.asarray(workers[i].w), 1.0, atol=0.15
                )
    asyncio.run(run())


def test_heartbeat_policy_requires_finite_gossip_timeout():
    with pytest.raises(ValueError, match="finite gossip_timeout"):
        DecentralizedPeerToPeer(
            [QuadWorker(0.0), QuadWorker(1.0)], [],
            aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(2), gossip_timeout=None,
            elastic=HeartbeatPolicy(),
        )


def test_resetup_after_removal_uses_shrunken_fabric():
    """shutdown() then re-enter: the fabric must come back up with only
    the survivors (review finding: re-setup used to iterate the full
    original topology and KeyError on the popped worker)."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(4), learning_rate=0.3,
        )
        async with p2p:
            await p2p.run_round_async()
            await p2p.remove_node(3)
        # re-enter on the shrunken fabric
        async with p2p:
            assert sorted(p2p.nodes) == [0, 1, 2]
            assert p2p._honest_expected(0) == 2
            for _ in range(20):
                await p2p.run_round_async()
            for i in (0, 1, 2):
                np.testing.assert_allclose(
                    np.asarray(workers[i].w), 1.0, atol=0.15
                )
    asyncio.run(run())


def test_remove_node_serializes_with_inflight_round():
    """A round already in flight completes against the OLD membership
    (the lock delays the removal); the next round sees the new one."""
    async def run():
        workers = [QuadWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]
        p2p = DecentralizedPeerToPeer(
            workers, [], aggregator=CoordinateWiseMedian(),
            topology=Topology.complete(4), learning_rate=0.3,
        )
        async with p2p:
            round_task = asyncio.create_task(p2p.run_round_async())
            await asyncio.sleep(0)  # let the round take the lock
            await p2p.remove_node(3)
            out = await round_task  # must not have raced the removal
            assert sorted(out) in ([0, 1, 2], [0, 1, 2, 3])
            out = await p2p.run_round_async()
            assert sorted(out) == [0, 1, 2]
    asyncio.run(run())
