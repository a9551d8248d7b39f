"""Actor-hosting server for the multi-machine parameter server.

Run one per machine (ref: ``byzpy/examples/ps/remote_tcp/ps_node.py``):

    BYZPY_TPU_WIRE_KEY=cluster-secret \
    python examples/ps/remote_tcp/node_server.py --host 0.0.0.0 --port 7781

The coordinator constructs node actors here over ``tcp://``; frames are
HMAC-signed when ``BYZPY_TPU_WIRE_KEY`` is set (strongly recommended —
see ``byzpy_tpu.engine.actor.wire``).
"""

import argparse
import asyncio
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

from byzpy_tpu.engine.actor.backends.remote import RemoteActorServer


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    args = ap.parse_args()

    if not os.environ.get("BYZPY_TPU_WIRE_KEY"):
        print("warning: BYZPY_TPU_WIRE_KEY unset — frames are unsigned", file=sys.stderr)
    server = RemoteActorServer(host=args.host, port=args.port)
    await server.start()
    print(f"node server ready on {server.address}", flush=True)
    try:
        await asyncio.Event().wait()  # serve forever
    finally:
        await server.close()


if __name__ == "__main__":
    asyncio.run(main())
