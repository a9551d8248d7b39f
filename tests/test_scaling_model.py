"""Scaling-model validation beyond n=8.

``docs/comm_model.md`` extrapolates 8→128-chip efficiency from HLO
collective inventories measured at n=8 plus closed-form per-collective
laws. These tests pin those laws against FRESH compilations at n ∈
{8, 16, 32} for all three round fabrics (PS, ring gossip, ring
attention), and dryrun-execute the full multichip training step at 16
and 32 virtual devices (the driver itself only runs n=8).

Each probe compiles in its own subprocess because the suite's conftest
pins this process to an 8-device CPU mesh.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.heavy]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = os.path.join(REPO, "benchmarks", "fabric_traffic_probe.py")


def _probe(fabric: str, n: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the probe pins its own device count
    out = subprocess.run(
        [sys.executable, PROBE, fabric, str(n)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("n", [8, 16, 32])
def test_ps_round_follows_saturating_collective_law(n):
    """Fused PS round: per-device wire bytes = 2 * d * dtype * (n-1)/n
    (gradient-transpose all-to-all + update all-gather) — the saturating
    law behind the ~99% 8→128 efficiency-retention claim."""
    t = _probe("ps", n)
    d, dt = t["d"], t["dtype_bytes"]
    law = 2 * d * dt * (n - 1) / n
    assert abs(t["wire_bytes_per_device"] - law) / law < 0.02, (t, law)
    # and the split is exactly the two dominant collectives
    per = t["per_opcode_bytes"]
    assert abs(per["all-to-all"] - d * dt * (n - 1) / n) / law < 0.02
    assert abs(per["all-gather"] - d * dt * (n - 1) / n) / law < 0.02


@pytest.mark.parametrize("n", [8, 16, 32])
def test_gossip_round_bytes_constant_in_ring_size(n):
    """Ring gossip: each chip exchanges with its 2k neighbors regardless
    of ring size — per-device ppermute bytes must not grow with n."""
    t = _probe("gossip", n)
    d, dt = t["d"], t["dtype_bytes"]
    assert t["per_opcode_bytes"]["collective-permute"] == d * dt, t


def test_ring_attention_per_trip_bytes_constant_under_weak_scaling():
    """Ring attention with the context axis scaled with the mesh
    (L = 8n): the K/V block per chip is constant, so the in-loop
    ppermute bytes PER TRIP are constant and the trip count is n-1."""
    results = {n: _probe("ring_attention", n) for n in (8, 16, 32)}
    per_trip = {n: r["loop_body_bytes_per_iteration"] for n, r in results.items()}
    assert per_trip[8] > 0
    assert per_trip[8] == per_trip[16] == per_trip[32], per_trip
    for n, r in results.items():
        assert r["ring_trips"] == n - 1, r


@pytest.mark.parametrize("n", [16, 32])
def test_dryrun_multichip_beyond_driver_mesh(n):
    """The full multichip training step (all fabrics in
    ``__graft_entry__.dryrun_multichip``) compiles AND executes at mesh
    sizes the driver never runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    code = (
        "import __graft_entry__ as g; "
        f"g.dryrun_multichip({n}); "
        "print('dryrun-ok')"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "dryrun-ok" in out.stdout, out.stdout
