"""Test configuration: run everything on a simulated 8-device CPU mesh.

Multi-chip sharding logic is validated without TPU hardware by forcing the
host platform to expose 8 virtual devices (the reference validates its
multi-node logic analogously with an in-process cluster registry, ref:
``byzpy/engine/node/context.py:56-123``). The platform is pinned through
``jax.config`` so the suite runs on the CPU whatever ``JAX_PLATFORMS`` says.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache: the suite's wall time is dominated by
# compiles on the 8-virtual-device mesh, and they repeat identically
# between runs. One directory for every entry point of this checkout
# (utils.platform.enable_compile_cache); JAX_COMPILATION_CACHE_DIR
# places it elsewhere.
from byzpy_tpu.utils.platform import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


# -- shared decentralized-cluster helpers (used by the node-layer suites) ----

@pytest.fixture(autouse=True)
def _clear_node_registries():
    """Every test starts with clean in-process/process node registries."""
    from byzpy_tpu.engine.node import InProcessContext, ProcessContext

    InProcessContext.clear_registry()
    ProcessContext.clear_registry()
    yield
    InProcessContext.clear_registry()
    ProcessContext.clear_registry()


@pytest.fixture
def make_cluster():
    from byzpy_tpu.engine.node import (
        DecentralizedCluster, DecentralizedNode, InProcessContext,
    )
    from byzpy_tpu.engine.peer_to_peer import Topology

    def factory(n, topology=None):
        topo = topology or Topology.complete(n)
        cluster = DecentralizedCluster(topo)
        for i in range(n):
            nid = f"node-{i}"
            cluster.add_node(DecentralizedNode(nid, InProcessContext(nid)))
        return cluster

    return factory
