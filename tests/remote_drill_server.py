"""Standalone actor-server host for the multi-host fault drills.

Runs a :class:`RemoteActorServer` on a loopback port in its OWN OS
process, prints ``PORT <n>`` once ready, and serves until killed — the
drills in ``test_multihost.py`` SIGKILL it mid-round to exercise the
elastic PS path against a genuine host death (not a graceful close).

The node class lives here (not in the test module) so the server process
can resolve it by reference when the client ships it over the wire.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root
sys.path.insert(0, _HERE)  # this dir, for class-by-reference resolution

import numpy as np

from byzpy_tpu.engine.node.base import HonestNode

D = 32


class SlowRemoteNode(HonestNode):
    """Gradient takes ``delay`` seconds — a window wide enough for the
    drill to SIGKILL this host while the call is in flight."""

    def __init__(self, value: float, delay: float = 3.0) -> None:
        self.value = float(value)
        self.delay = float(delay)

    def next_batch(self):
        return None, None

    def honest_gradient(self, x, y):
        time.sleep(self.delay)
        return [np.full(D, self.value, np.float32)]

    def apply_server_gradient(self, g) -> None:
        pass


async def _serve() -> None:
    from byzpy_tpu.engine.actor.backends.remote import RemoteActorServer

    server = RemoteActorServer("127.0.0.1", 0)
    await server.start()
    print(f"PORT {server.port}", flush=True)
    await asyncio.Event().wait()  # until killed


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    asyncio.run(_serve())
