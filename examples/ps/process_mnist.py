"""Parameter-server training with process-actor nodes.

Reference semantics: ``byzpy/examples/ps/process/`` — nodes live in
spawned OS processes; gradients cross the boundary through the native shm
store (``byzpy_tpu.engine.storage``) rather than the pickle pipe. Children
run on CPU (a TPU chip admits one process); this layout fits host-side
workloads or CPU-only robust-aggregation research.
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

from byzpy_tpu.aggregators import CoordinateWiseMedian
from byzpy_tpu.engine.node.actors import HonestNodeActor
from byzpy_tpu.engine.parameter_server import ParameterServer

# node classes are shared with the thread example; import from a module so
# the spawned child can re-import them (cloudpickle ships the class, but
# module-level definitions keep the pickles small)
from examples.ps.thread_mnist import MnistNode

N_NODES = int(os.environ.get("N_NODES", 3))
ROUNDS = int(os.environ.get("PS_ROUNDS", 10))


async def main():
    from byzpy_tpu.models.data import ShardedDataset, synthetic_classification

    x, y = synthetic_classification(n_samples=1024, seed=0)
    data = ShardedDataset(x, y, N_NODES)
    honest = [
        await HonestNodeActor.spawn(
            MnistNode, *map(lambda a: a.__array__(), data.node_slice(i)), i,
            backend="process",
        )
        for i in range(N_NODES)
    ]
    ps = ParameterServer(honest, aggregator=CoordinateWiseMedian())
    for r in range(ROUNDS):
        await ps.round()
        if (r + 1) % 5 == 0:
            acc = await honest[0].accuracy(x.__array__(), y.__array__())
            print(f"round {r + 1}: accuracy {acc:.3f}")
    for a in honest:
        await a.close()


if __name__ == "__main__":
    asyncio.run(main())
