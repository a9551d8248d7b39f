"""Multi-host bring-up: ``initialize_multihost`` exercised for real.

Spawns two worker processes that initialize the JAX distributed runtime
against a local coordinator, build one global 2-device mesh, and run a
cross-process psum (``examples/distributed/two_host_psum.py`` is the
worker). This is the only public entry point that cannot be covered by
the in-process 8-device mesh — the reference's analogue is its TCP
server/client integration tests (SURVEY §4 "subprocess integration").
"""

import os
import re
import socket
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.heavy]

EXAMPLE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples", "distributed", "two_host_psum.py",
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_psum_over_distributed_runtime():
    proc = subprocess.run(
        [sys.executable, EXAMPLE, "--port", str(_free_port())],
        capture_output=True,
        text=True,
        timeout=420,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    assert out.count("cross-host psum OK") == 2, out
    # device count per process varies with XLA_FLAGS (the suite's conftest
    # exposes 8 virtual CPU devices); the invariant is global == 2 x local
    m = re.search(r"global devices=(\d+) local=(\d+)", out)
    assert m and int(m.group(1)) == 2 * int(m.group(2)), out


# -- fault drills: death + recovery on the cross-process path ---------------
#
# The reference's multi-backend paranoia (its per-backend copies of the
# decentralized suites, e.g. node/tests/test_decentralized_process.py)
# is matched here with drills against REAL OS-process deaths: a SIGKILLed
# actor host mid-round, a byzantine peer living in a child process, and a
# heartbeat-policy excision of a killed subprocess peer.

import asyncio
import signal
import time

import numpy as np


def _spawn_drill_server():
    """Start tests/remote_drill_server.py in its own OS process; return
    (Popen, port)."""
    import select

    helper = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "remote_drill_server.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, helper], stdout=subprocess.PIPE, text=True, env=env,
    )
    deadline = time.monotonic() + 120
    line = ""
    while time.monotonic() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if ready:
            line = proc.stdout.readline()
            break
        if proc.poll() is not None:
            break
    if not line.startswith("PORT "):
        proc.kill()
        raise RuntimeError(f"drill server failed to start (got {line!r})")
    return proc, int(line.split()[1])


def test_elastic_ps_survives_sigkilled_host_process_midround():
    """A node's host process is SIGKILLed while its gradient call is IN
    FLIGHT: the elastic round completes on the survivors and the dead
    host is suspected; later rounds keep flowing without it."""
    from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
    from byzpy_tpu.engine.node.actors import HonestNodeActor
    from byzpy_tpu.engine.parameter_server import ElasticPolicy, ParameterServer
    from remote_drill_server import D, SlowRemoteNode

    class LocalNode:
        def __init__(self, value):
            self.value = float(value)

        def honest_gradient_for_next_batch(self):
            return [np.full(D, self.value, np.float32)]

        def apply_server_gradient(self, g):
            pass

    async def drill():
        proc, port = _spawn_drill_server()
        try:
            remote = await HonestNodeActor.spawn(
                SlowRemoteNode, 9.0, 3.0,
                backend=f"tcp://127.0.0.1:{port}",
            )
            ps = ParameterServer(
                honest_nodes=[LocalNode(1.0), LocalNode(2.0), remote],
                aggregator=CoordinateWiseTrimmedMean(f=0),
                elastic=ElasticPolicy(min_quorum=2, call_timeout=20.0),
            )
            round_task = asyncio.create_task(ps.round())
            await asyncio.sleep(1.0)  # remote is inside its 3 s gradient
            proc.send_signal(signal.SIGKILL)  # host dies mid-round
            out = await asyncio.wait_for(round_task, timeout=60.0)
            np.testing.assert_allclose(
                np.asarray(out[0]), np.full(D, 1.5), rtol=1e-6
            )
            assert "honest:2" in ps.elastic_state.suspects
            # the fabric keeps training without the dead host
            out = await asyncio.wait_for(ps.round(), timeout=60.0)
            np.testing.assert_allclose(
                np.asarray(out[0]), np.full(D, 1.5), rtol=1e-6
            )
            assert ps.rounds_completed == 2
        finally:
            proc.kill()

    asyncio.run(drill())


class _DrillWorker:
    """Quadratic-descent gossip worker (picklable for subprocess peers)."""

    def __init__(self, target, dim=4):
        import jax.numpy as jnp

        self.target = jnp.full((dim,), float(target), jnp.float32)
        self.w = jnp.zeros((dim,), jnp.float32)

    def half_step(self, lr):
        self.w = self.w - lr * 2.0 * (self.w - self.target)
        return self.w

    def parameters(self):
        return self.w

    def apply_aggregate(self, vector):
        import jax.numpy as jnp

        self.w = jnp.asarray(vector)


def _byz_outlier(honest_vectors):
    import jax.numpy as jnp

    return jnp.full((4,), 1e3, jnp.float32)


def test_gossip_with_byzantine_process():
    """A byzantine peer living in a CHILD OS PROCESS (its attack pipeline
    installed child-side via the configure hook): robust consensus among
    the in-process honest peers must hold against the subprocess's
    outlier vectors."""
    from byzpy_tpu.aggregators import CoordinateWiseMedian
    from byzpy_tpu.engine.node.context import InProcessContext
    from byzpy_tpu.engine.node.process_context import ProcessContext
    from byzpy_tpu.engine.peer_to_peer import Topology
    from byzpy_tpu.engine.peer_to_peer.nodes import FunctionP2PWorker
    from byzpy_tpu.engine.peer_to_peer.runner import DecentralizedPeerToPeer

    InProcessContext._registry.clear()
    ProcessContext.clear_registry()
    workers = [_DrillWorker(t) for t in (0.0, 1.0, 2.0)]
    byz = [FunctionP2PWorker(_byz_outlier)]

    def ctx_factory(nid):
        return (
            ProcessContext(nid) if nid == "node-3" else InProcessContext(nid)
        )

    p2p = DecentralizedPeerToPeer(
        workers, byz,
        aggregator=CoordinateWiseMedian(),
        topology=Topology.complete(4),
        learning_rate=0.3,
        context_factory=ctx_factory,
        gossip_timeout=120.0,
    )

    async def drill():
        async with p2p:
            for _ in range(8):
                await p2p.run_round_async()

    asyncio.run(drill())
    # each honest node medians 4 vectors (an even count: its own + three
    # in-neighbors, one byzantine) — the middle pair averages the honest
    # 1.0/2.0 targets, so consensus sits at 1.5, UNDRAGGED by the
    # subprocess's 1e3 outlier (mean aggregation would sit near 250)
    for i in (0, 1, 2):
        np.testing.assert_allclose(np.asarray(workers[i].w), 1.5, atol=0.3)


def test_heartbeat_policy_excises_sigkilled_process_peer():
    """Full DCN-path drill of the shipped elastic policy: an honest peer
    lives in a child OS process, the process is SIGKILLed mid-training,
    the heartbeat monitor suspects it (no pongs from a dead process), the
    policy excises it, and gossip continues among the survivors."""
    from byzpy_tpu.aggregators import CoordinateWiseMedian
    from byzpy_tpu.engine.node.context import InProcessContext
    from byzpy_tpu.engine.node.process_context import ProcessContext
    from byzpy_tpu.engine.peer_to_peer import HeartbeatPolicy, Topology
    from byzpy_tpu.engine.peer_to_peer.runner import DecentralizedPeerToPeer

    InProcessContext._registry.clear()
    ProcessContext.clear_registry()
    workers = [_DrillWorker(t) for t in (0.0, 1.0, 2.0, 9.0)]

    def ctx_factory(nid):
        return (
            ProcessContext(nid) if nid == "node-3" else InProcessContext(nid)
        )

    p2p = DecentralizedPeerToPeer(
        workers, [],
        aggregator=CoordinateWiseMedian(),
        topology=Topology.complete(4),
        learning_rate=0.3,
        context_factory=ctx_factory,
        gossip_timeout=60.0,
        # a subprocess peer's event loop stalls for seconds at a time
        # while jax traces/compiles its pipelines — give the detector
        # enough misses that only a real death (no pongs ever again)
        # trips it, not a compile pause
        elastic=HeartbeatPolicy(interval=1.0, max_missed=12),
    )

    async def drill():
        async with p2p:
            for _ in range(3):
                await p2p.run_round_async()
            assert p2p.honest_indices == [0, 1, 2, 3], p2p.elastic_events
            victim_id = p2p.node_ids[3]
            # SIGKILL the subprocess peer — no goodbye, no queue drain
            p2p.nodes[3].context._proc.kill()
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if (victim_id, "removed") in p2p.elastic_events:
                    break
                await asyncio.sleep(0.1)
            assert (victim_id, "removed") in p2p.elastic_events, (
                p2p.elastic_events
            )
            assert p2p.honest_indices == [0, 1, 2]
            for _ in range(12):
                await p2p.run_round_async()

    asyncio.run(drill())
    for i in (0, 1, 2):
        np.testing.assert_allclose(np.asarray(workers[i].w), 1.0, atol=0.3)
