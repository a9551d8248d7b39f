"""Multi-host bring-up: ``initialize_multihost`` + one cross-process psum.

The reference spans machines with TCP actor servers
(ref: ``examples/distributed/mnist.py:1-28``, ``server.py``); the TPU-native
control plane is the JAX distributed runtime — each host calls
:func:`byzpy_tpu.parallel.collectives.initialize_multihost`, after which
``jax.devices()`` is GLOBAL (every host's chips) and one ``Mesh`` spans the
pod. Bulk tensors then move as XLA collectives over ICI/DCN; no sockets in
user code.

Self-launching demo (two processes on this machine, one CPU device each)::

    python examples/distributed/two_host_psum.py

Real deployment: run the same worker code on every host with
``--coordinator host0:12355 --num-processes N --process-id <i>``.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)



def worker(coordinator: str, num_processes: int, process_id: int) -> None:
    # Platform choice must precede any jax backend touch; jax.config pins
    # it whatever the environment says. One CPU device per process plays
    # one chip per host.
    import jax

    jax.config.update("jax_platforms", "cpu")

    from byzpy_tpu.parallel.collectives import initialize_multihost

    started = initialize_multihost(coordinator, num_processes, process_id)

    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from byzpy_tpu.parallel.collectives import sharded_fn

    assert started, "initialize_multihost should have initialized the runtime"
    assert jax.process_count() == num_processes, jax.process_count()

    # After initialize, jax.devices() is global: one mesh over every
    # host's devices. local_devices() is what this host contributes
    # (device count per host varies — e.g. XLA_FLAGS can expose several
    # virtual CPU devices — so everything below is count-agnostic).
    n_local = len(jax.local_devices())
    mesh = Mesh(np.array(jax.devices()), ("nodes",))
    print(
        f"[proc {process_id}] global devices={len(jax.devices())} "
        f"local={n_local}",
        flush=True,
    )

    # Each process contributes one row per local device, filled with its
    # process id + 1; the psum crosses the process boundary over the DCN
    # control plane's data channels.
    local = np.full((n_local, 4), float(process_id + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("nodes")), local
    )
    psum = sharded_fn(
        mesh, "nodes", lambda s: lax.psum(s, "nodes"),
        in_spec=P("nodes"), out_spec=P("nodes"),
    )
    out = psum(arr)
    mine = np.asarray(out.addressable_data(0))
    # each global device's row carries (owner process + 1); hosts may
    # contribute different device counts, so sum over the real ownership
    want = sum(dev.process_index + 1 for dev in jax.devices())
    assert (mine == want).all(), (mine, want)
    print(f"[proc {process_id}] cross-host psum OK: {mine[0, 0]} == {want}", flush=True)


def launch(num_processes: int, port: int) -> int:
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--coordinator", f"localhost:{port}",
                "--num-processes", str(num_processes),
                "--process-id", str(i),
            ],
        )
        for i in range(num_processes)
    ]
    rc = 0
    for p in procs:
        rc |= p.wait()
    print("all processes done" if rc == 0 else f"FAILED rc={rc}")
    return rc


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--coordinator", default=None)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--port", type=int, default=12355)
    args = parser.parse_args()
    if args.process_id is None:
        return launch(args.num_processes, args.port)
    worker(args.coordinator, args.num_processes, args.process_id)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
