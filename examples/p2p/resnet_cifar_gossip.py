"""Byzantine-robust ResNet-18 training over P2P gossip (BASELINE config #4).

CIFAR-shaped ResNet-18 (GroupNorm, pure-functional), n nodes gossiping on
a ring, aggregation = NNM pre-mixing then geometric median — the
composition the reference benchmarks for P2P CIFAR. Data is synthetic
class-conditional blobs (no downloads); swap in real CIFAR by replacing
the (x, y) arrays.

Two execution modes:

* default — the fused single-program gossip step
  (``build_gossip_train_step``): all node states live as one stacked
  ``(n, d)`` matrix on the default device. Works on CPU and a single TPU.
* ``P2P_RING=1`` with >= n devices — the ``shard_map`` ring
  (``build_ring_gossip_train_step``): one node per device, parameters
  move only as ``ppermute`` neighbor traffic.

    python examples/p2p/resnet_cifar_gossip.py
    P2P_STEPS=20 P2P_FILTERS=64 python examples/p2p/resnet_cifar_gossip.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax

if os.environ.get("BYZPY_TPU_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["BYZPY_TPU_PLATFORM"])

import math
from functools import partial

import flax.linen as nn
import jax.numpy as jnp

from byzpy_tpu.engine.peer_to_peer import Topology
from byzpy_tpu.models.data import ShardedDataset, synthetic_classification
from byzpy_tpu.models.nets import ResNet18, make_bundle
from byzpy_tpu.ops import preagg, robust
from byzpy_tpu.parallel import (
    GossipStepConfig,
    build_gossip_train_step,
    build_ring_gossip_train_step,
)
from byzpy_tpu.parallel.mesh import make_mesh

N_NODES = int(os.environ.get("N_NODES", 8))
N_BYZ = int(os.environ.get("N_BYZ", 1))
STEPS = int(os.environ.get("P2P_STEPS", 10))
FILTERS = int(os.environ.get("P2P_FILTERS", 64))  # 64 = real ResNet-18
BATCH = int(os.environ.get("P2P_BATCH", 32))


def robust_aggregate(m: jnp.ndarray) -> jnp.ndarray:
    """NNM mixing then geometric median over the (k+1, d) received stack."""
    mixed = preagg.nnm(m, f=min(N_BYZ, m.shape[0] - 1))
    return robust.geometric_median(mixed, max_iter=32)


def main() -> None:
    # GroupNorm groups must divide every stage's channel count (multiples
    # of FILTERS); gcd keeps tiny test widths valid
    norm = partial(nn.GroupNorm, num_groups=math.gcd(32, FILTERS))
    bundle = make_bundle(
        ResNet18(num_classes=10, num_filters=FILTERS, norm=norm),
        (1, 32, 32, 3), seed=0,
    )
    d = sum(p.size for p in jax.tree_util.tree_leaves(bundle.params))
    print(f"ResNet-18 (filters={FILTERS}): {d:,} params, "
          f"{N_NODES} nodes ({N_BYZ} byzantine), device={jax.devices()[0]}")

    # 4 rotating batches per node
    n_batches = 4
    x, y = synthetic_classification(
        n_samples=N_NODES * BATCH * n_batches, input_shape=(32, 32, 3), seed=0
    )
    xs_all, ys_all = ShardedDataset(x, y, n_nodes=N_NODES).stacked_shards()

    def batch_at(s):
        start = (s % n_batches) * BATCH
        return xs_all[:, start:start + BATCH], ys_all[:, start:start + BATCH]

    cfg = GossipStepConfig(n_nodes=N_NODES, n_byzantine=N_BYZ, learning_rate=0.05)
    ring_mode = os.environ.get("P2P_RING") == "1"
    if ring_mode:
        if len(jax.devices()) < N_NODES:
            raise SystemExit(
                f"P2P_RING=1 needs >= {N_NODES} devices (have {len(jax.devices())})"
            )
        mesh = make_mesh([N_NODES], ("nodes",))
        step, init = build_ring_gossip_train_step(
            bundle, robust_aggregate, cfg, mesh, k=2
        )
        print(f"ring mode: shard_map over {N_NODES} devices (ppermute ring)")
    else:
        step, init = build_gossip_train_step(
            bundle, robust_aggregate, Topology.ring(N_NODES, 2), cfg
        )
    theta = init()
    jit_step = jax.jit(step)

    key = jax.random.PRNGKey(0)
    device_losses = []
    xs, ys = batch_at(0)
    theta1, metrics = jit_step(theta, xs, ys, key)  # compile
    jax.block_until_ready(theta1)
    t0 = time.perf_counter()
    for s in range(STEPS):
        key, sub = jax.random.split(key)
        xs, ys = batch_at(s)
        theta, metrics = jit_step(theta, xs, ys, sub)
        # keep losses on device: a float() here would sync every step and
        # time the host round-trip instead of the step
        device_losses.append(
            metrics["honest_loss"] if isinstance(metrics, dict) else metrics
        )
    jax.block_until_ready(theta)
    dt = time.perf_counter() - t0
    losses = [float(l) for l in device_losses]
    for s, l in enumerate(losses):
        print(f"step {s + 1:3d}  honest loss {l:.4f}")
    print(f"{STEPS / dt:.2f} steps/sec  ({dt / STEPS * 1e3:.1f} ms/step)")
    if STEPS >= 5:  # smoke runs (P2P_STEPS=2) are too short to descend
        assert losses[-1] < losses[0], "loss did not decrease"
        print("loss decreased:", f"{losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
