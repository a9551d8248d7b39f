"""Elastic PS training through node crashes (no reference analogue).

The reference's PS round dies with any node
(``byzpy/engine/parameter_server/ps.py:103-144``): a worker that loses
its link mid-training kills the job. With
``ParameterServer(elastic=ElasticPolicy(...))`` a crash costs the node
its slot for the round; the server keeps training on the survivors,
probes the suspect every round, and re-admits it on the first success —
while ``min_quorum`` refuses to continue below the aggregator's f-of-n
assumption.

This demo trains a linear regression on synthetic data with 6 honest
nodes + 1 sign-flipping byzantine node under Multi-Krum. Node 2 "dies"
for rounds 10-19 (raises ConnectionError) and recovers at round 20.
Watch the loss keep falling through the outage and the suspect set empty
itself after recovery.

Run: ``python examples/ps/elastic_crash_recovery.py`` (any backend).
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import numpy as np

from byzpy_tpu.aggregators import MultiKrum
from byzpy_tpu.engine.parameter_server import ElasticPolicy, ParameterServer

RNG = np.random.default_rng(0)
DIM = 32
W_TRUE = RNG.standard_normal(DIM).astype(np.float32)
ROUNDS = int(os.environ.get("PS_ROUNDS", 40))
LR = 0.05


class RegressionNode:
    """Least-squares worker on its own data shard (host-resident)."""

    def __init__(self, seed: int, crash_rounds=()):
        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((128, DIM)).astype(np.float32)
        self.y = self.x @ W_TRUE + 0.01 * rng.standard_normal(128).astype(
            np.float32
        )
        self.w = np.zeros(DIM, np.float32)
        self.round_no = 0
        self.crash_rounds = set(crash_rounds)

    def honest_gradient_for_next_batch(self):
        self.round_no += 1
        if self.round_no in self.crash_rounds:
            raise ConnectionError("simulated link failure")
        resid = self.x @ self.w - self.y
        return [(self.x.T @ resid / len(self.y)).astype(np.float32)]

    def apply_server_gradient(self, g):
        self.w = self.w - LR * np.asarray(g[0])

    def loss(self) -> float:
        return float(np.mean((self.x @ self.w - self.y) ** 2))


class SignFlipNode(RegressionNode):
    def byzantine_gradient_for_next_batch(self, honest):
        stacked = np.stack([np.asarray(g[0]) for g in honest])
        return [(-4.0 * stacked.mean(axis=0)).astype(np.float32)]


async def main() -> None:
    nodes = [
        RegressionNode(i, crash_rounds=range(10, 20) if i == 2 else ())
        for i in range(6)
    ]
    ps = ParameterServer(
        honest_nodes=nodes,
        byzantine_nodes=[SignFlipNode(99)],
        aggregator=MultiKrum(f=1, q=3),
        elastic=ElasticPolicy(min_quorum=4, call_timeout=10.0),
    )
    for r in range(ROUNDS):
        await ps.round()
        if (r + 1) % 5 == 0:
            alive = [n.loss() for i, n in enumerate(nodes) if i != 2]
            print(
                f"round {r + 1:3d}  loss={np.mean(alive):.5f}  "
                f"suspects={sorted(ps.elastic_state.suspects) or '-'}"
            )
    if ROUNDS >= 20:  # smoke runs use PS_ROUNDS=2 and never reach the crash
        assert ps.elastic_state.suspects == {}, "node 2 should have re-admitted"
        kinds = {
            k for _, nid, k in ps.elastic_state.events if nid == "honest:2"
        }
        assert {"suspected", "readmitted"} <= kinds
        print("\nnode 2 died rounds 10-19, re-admitted on recovery; "
              f"final mean loss {np.mean([n.loss() for n in nodes]):.5f}")


if __name__ == "__main__":
    asyncio.run(main())
