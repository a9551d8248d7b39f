"""Latency-aware compute placement for actor-mode operators.

Actor-mode nodes (threads, processes, remote hosts) hand the framework
*host-resident* gradients — numpy arrays, or jax arrays already on the
CPU backend. For small payloads, shipping them to an accelerator can
cost more than the whole robust aggregate: a host->device transfer, a
dispatch and a device->host copy bracket a sub-millisecond reduction.
The reference's CPU nodes never pay this tax — aggregation happens where
the gradients live (``byzpy/engine/parameter_server/ps.py:131-137``) —
and neither should actor-mode rounds here.

What those steps cost on a co-located v5e (chip run, PR 21): host->device
0.66 ms at 0.25 MiB, 0.77 ms at 1 MiB, 2.0 ms at 8 MiB, 12 ms at 64 MiB;
one small dispatch round-trip 0.59 ms; a 4 KiB device->host copy
0.41 ms. A host-in/host-out Multi-Krum of a 64x8,192 f32 stack (2 MiB)
took 1.5 ms through the chip and 1.9 ms on the CPU backend — so the
8 MiB cap below, chosen on another machine, is probably several times
too high here. It is not retuned in this module yet (ROADMAP S1/D4).

Policy (``compute_device``): run on the CPU backend iff

* every array leaf of the inputs is host-resident (numpy scalar/array,
  Python number, or a jax array on a CPU device) — if anything already
  lives on an accelerator, moving it *back* would pay the same tax; and
* the total payload is at most ``BYZPY_TPU_HOST_COMPUTE_BYTES`` (default
  8 MiB); and
* the default backend is an accelerator (on a CPU-only host there is
  nothing to avoid).

Fused SPMD paths (``byzpy_tpu.parallel``) are untouched: their data is
born sharded on the mesh and never passes through this policy.

Opt out with ``BYZPY_TPU_HOST_COMPUTE_BYTES=0``; force a device with
``jax.default_device`` (an explicit caller context wins — the policy
only ever *narrows* to the host, and only when no context is active).
"""

from __future__ import annotations

import os
from contextlib import nullcontext
from typing import Any, ContextManager, Optional

import jax
import numpy as np

DEFAULT_HOST_COMPUTE_BYTES = 8 << 20


def host_compute_max_bytes() -> int:
    """Payload cap for host placement (env-overridable, 0 disables)."""
    try:
        return int(
            os.environ.get(
                "BYZPY_TPU_HOST_COMPUTE_BYTES", str(DEFAULT_HOST_COMPUTE_BYTES)
            )
        )
    except ValueError:
        return DEFAULT_HOST_COMPUTE_BYTES


def _leaf_host_bytes(leaf: Any) -> Optional[int]:
    """Size in bytes if ``leaf`` is host-resident, else ``None``."""
    if isinstance(leaf, (bool, int, float, complex)) or leaf is None:
        return 0
    if isinstance(leaf, np.ndarray) or np.isscalar(leaf):
        return int(getattr(leaf, "nbytes", 0))
    if isinstance(leaf, jax.Array):
        try:
            devices = leaf.devices()
        except Exception:  # deleted/donated buffers: not placeable
            return None
        if all(d.platform == "cpu" for d in devices):
            return int(leaf.nbytes)
        return None
    return None


def compute_device(*trees: Any) -> Optional[Any]:
    """The CPU device to run on, or ``None`` for the default device.

    ``trees`` are the operator inputs (any pytrees). See the module
    docstring for the policy.
    """
    cap = host_compute_max_bytes()
    if cap <= 0:
        return None
    if jax.config.jax_default_device is not None:
        return None  # explicit caller placement wins
    if jax.default_backend() == "cpu":
        return None  # already on the host backend
    total = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            nbytes = _leaf_host_bytes(leaf)
            if nbytes is None:
                return None
            total += nbytes
    if total > cap:
        return None
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def on(device: Optional[Any]) -> ContextManager[Any]:
    """Context manager placing jax computation on ``device`` (no-op for
    ``None``)."""
    if device is None:
        return nullcontext()
    return jax.default_device(device)


__all__ = ["compute_device", "host_compute_max_bytes", "on"]
