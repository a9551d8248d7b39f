"""Multi-machine parameter-server coordinator.

Reads a YAML/JSON node manifest (ref:
``byzpy/examples/ps/remote_tcp/nodes_example.yaml``), spawns each training
node on its machine's actor server over ``tcp://``, and drives robust PS
rounds from here. Gradient payloads travel the control wire as host
arrays; on a real deployment keep this for orchestration and let bulk
tensors ride jax multi-host collectives (see ``byzpy_tpu.parallel``).

    BYZPY_TPU_WIRE_KEY=cluster-secret \
    python examples/ps/remote_tcp/coordinator.py --manifest nodes.yaml
"""

import argparse
import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), *[".."] * 3))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax

# honor a platform override BEFORE any jax use: on shared single-chip dev
# hosts the demo pins workers to CPU (real deployments use each machine's
# own accelerators and leave this unset)
if os.environ.get("BYZPY_TPU_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["BYZPY_TPU_PLATFORM"])

import jax.numpy as jnp

from byzpy_tpu.aggregators import CoordinateWiseTrimmedMean
from byzpy_tpu.engine.node.actors import ByzantineNodeActor, HonestNodeActor
from byzpy_tpu.engine.node.base import ByzantineNode, HonestNode
from byzpy_tpu.engine.parameter_server import ParameterServer
from byzpy_tpu.models.data import ShardedDataset, sample_batch, synthetic_classification
from byzpy_tpu.models.nets import mnist_mlp

ROUNDS = int(os.environ.get("PS_ROUNDS", 10))
BATCH = 64
LR = 0.1


class RemoteMnistNode(HonestNode):
    """Honest worker constructed BY VALUE on its hosting machine: the class
    and its shard ship through cloudpickle at spawn."""

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


class EmpireNode(ByzantineNode):
    def next_batch(self):
        return None, None

    def byzantine_gradient(self, honest_gradients):
        mean = jax.tree_util.tree_map(
            lambda *gs: sum(jnp.asarray(g) for g in gs) / len(gs), *honest_gradients
        )
        return jax.tree_util.tree_map(lambda g: -1.0 * g, mean)

    def apply_server_gradient(self, gradient):
        pass


def load_manifest(path: str) -> dict:
    with open(path) as fh:
        text = fh.read()
    try:
        import yaml
    except ImportError:
        try:
            return json.loads(text)
        except json.JSONDecodeError as exc:
            raise RuntimeError(
                f"{path} is not JSON and PyYAML is not installed; "
                "pip install pyyaml or supply a JSON manifest"
            ) from exc
    return yaml.safe_load(text)


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--manifest",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)), "nodes.yaml"),
    )
    args = ap.parse_args()
    manifest = load_manifest(args.manifest)
    if not os.environ.get(manifest.get("secret_env", "BYZPY_TPU_WIRE_KEY")):
        print("warning: wire key unset — frames are unsigned", file=sys.stderr)

    entries = manifest["nodes"]
    honest_entries = [e for e in entries if e["role"] == "honest"]
    byz_entries = [e for e in entries if e["role"] == "byzantine"]

    x, y = synthetic_classification(n_samples=4096, seed=0)
    data = ShardedDataset(x, y, len(honest_entries))

    honest = []
    for i, entry in enumerate(honest_entries):
        sx, sy = data.node_slice(i)
        import numpy as np

        actor = await HonestNodeActor.spawn(
            RemoteMnistNode, np.asarray(sx), np.asarray(sy), i,
            backend=f"tcp://{entry['address']}",
        )
        honest.append(actor)
    byz = [
        await ByzantineNodeActor.spawn(EmpireNode, backend=f"tcp://{e['address']}")
        for e in byz_entries
    ]

    ps = ParameterServer(honest, byz, aggregator=CoordinateWiseTrimmedMean(f=max(1, len(byz))))
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
