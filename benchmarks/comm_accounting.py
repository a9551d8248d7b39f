"""Per-round communication accounting + the 8→128-chip analytic model.

Parses the collectives out of the COMPILED fused PS and gossip steps
(:mod:`byzpy_tpu.parallel.comms` — the byte counts come from XLA's
optimized HLO, not hand math), then projects weak-scaling efficiency
against v5e ICI bandwidth. Writes ``docs/comm_model.md``.

Run: ``XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu python benchmarks/comm_accounting.py --write``
"""

from __future__ import annotations

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fmt_bytes(b: float) -> str:
    """Human bytes, binary units."""
    for unit in ("B", "KiB", "MiB", "GiB"):
        if b < 1024:
            return f"{b:.1f} {unit}"
        b /= 1024
    return f"{b:.1f} TiB"


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--write", action="store_true")
    parser.add_argument("--d", type=int, default=1_000_000, help="model params")
    args = parser.parse_args()

    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.ops import robust
    from byzpy_tpu.parallel.comms import collective_traffic, scaling_model
    from byzpy_tpu.parallel.gossip import GossipStepConfig, build_ring_gossip_train_step
    from byzpy_tpu.parallel.mesh import node_mesh
    from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

    n = len(jax.devices())
    mesh = node_mesh(n)
    d = args.d
    dt_bytes = 4

    # A linear model with exactly d parameters: the comm pattern of the PS
    # round depends only on (n, d, dtype), so this stands in for any model
    # of that size while keeping compile fast.
    w0 = jnp.zeros((d,), jnp.float32)

    def apply_fn(params, x):
        return x @ params

    def loss_fn(params, x, y):
        return jnp.mean((x @ params - y) ** 2)

    bundle = ModelBundle(apply_fn=apply_fn, params=w0, loss_fn=loss_fn)

    ps_cfg = PSStepConfig(n_nodes=n, n_byzantine=max(1, n // 4))
    step, opt0 = build_ps_train_step(
        bundle, partial(robust.multi_krum, f=max(1, n // 4), q=n // 2),
        ps_cfg, mesh=mesh,
    )
    xs = jnp.zeros((n, 4, d), jnp.float32)
    ys = jnp.zeros((n, 4), jnp.float32)
    key = jax.random.PRNGKey(0)
    ps_traffic = collective_traffic(step, bundle.params, opt0, xs, ys, key)

    g_cfg = GossipStepConfig(n_nodes=n, n_byzantine=0)
    gstep, ginit = build_ring_gossip_train_step(
        bundle, robust.coordinate_median, g_cfg, mesh, k=1
    )
    gx = jnp.zeros((n, 4, d), jnp.float32)
    gy = jnp.zeros((n, 4), jnp.float32)
    g_traffic = collective_traffic(gstep, ginit(), gx, gy, key)

    rows = []
    for name, tr in (("fused PS round (Multi-Krum)", ps_traffic),
                     ("ring gossip round (median)", g_traffic)):
        per = ", ".join(
            f"{op}: {fmt_bytes(v)}" for op, v in sorted(tr["per_opcode_bytes"].items())
        )
        rows.append((name, tr["wire_bytes_per_device"], per))
        print(f"{name}: {fmt_bytes(tr['wire_bytes_per_device'])}/device/round ({per})")

    # Scaling model for the PS round. Dominant wire terms per device:
    #   gradient transpose (all-to-all): d*dt*(g-1)/g ~ d*dt
    #   result broadcast (all-gather of the (d,) update): d*dt*(g-1)/g
    # Per-device payload is ~2*d*dt, INDEPENDENT of chip count — the
    # (g-1)/g factor saturates — which is what makes the round
    # weak-scalable: efficiency at 128 chips is within a couple % of 8.
    # The ABSOLUTE overhead depends on arithmetic intensity: workloads
    # below span the realistic range (the reference's benchmark models).
    workloads = [
        # (label, params d, fwd FLOPs/sample, batch/node/round, grad bytes)
        ("MLP-1M f32 b64 (low intensity)", 1_000_000, 2.0 * 1_000_000, 64, 4),
        ("ResNet-18 f32 b64", 11_200_000, 1.8e9, 64, 4),
        ("ResNet-18 bf16 b128", 11_200_000, 1.8e9, 128, 2),
        ("ResNet-50 bf16 b128", 25_600_000, 4.1e9, 128, 2),
    ]
    tables = []
    for label, dd, fwd_flops, batch, gbytes in workloads:
        flops = 3.0 * fwd_flops * batch  # fwd + ~2x bwd
        wire_fn = lambda g, dd=dd, gb=gbytes: 2.0 * dd * gb * (g - 1) / g  # noqa: E731
        points = scaling_model(flops_per_chip=flops, wire_bytes_fn=wire_fn)
        tables.append((label, points))
        print(f"\n{label} (v5e ICI 45 GB/s/dir, MFU 0.4):")
        for p in points:
            print(
                f"  {p.n_chips:4d} chips: compute {p.compute_s * 1e6:8.1f} us, "
                f"comm {p.comm_s * 1e6:8.1f} us, efficiency {p.efficiency:.1%}"
            )

    if args.write:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        lines = [
            "# Communication model (measured from compiled HLO)",
            "",
            "Byte counts below are parsed from the OPTIMIZED HLO of the",
            "compiled round steps (`byzpy_tpu.parallel.comms`), so they are",
            "properties of the artifact XLA actually runs, not estimates.",
            f"Mesh: {n} devices; model: d = {d:,} f32 params.",
            "",
            "**These are XLA:CPU's artifacts** (the 8-virtual-device mesh). On four",
            "v5e chips (PR 21, `chip_smoke.py` phase 5, ResNet-18 d = 11.17M, n = 8)",
            "the gradient-transpose all-to-all matches the law below exactly, but",
            "XLA:TPU lowers the params all-gather to a dynamic-update-slice plus an",
            "all-reduce of the whole d-vector — on a ring, twice the all-gather's",
            "bytes. Everything below that counts the PS round at `2·d·dtype` per",
            "device is `3·d·dtype` for that compiler until someone shows otherwise;",
            "whether it costs time has not been measured (ROADMAP S5).",
            "",
            "| step | wire bytes / device / round | by collective |",
            "|---|---|---|",
        ]
        for name, total, per in rows:
            lines.append(f"| {name} | {fmt_bytes(total)} | {per} |")
        lines += [
            "",
            "## Weak-scaling projection (PS round)",
            "",
            "Per-device wire bytes are ~`2 * d * dtype` regardless of chip",
            "count (the all-to-all and all-gather `(g-1)/g` factors",
            "saturate), so the comm term is CONSTANT in N: efficiency at",
            "128 chips stays within ~3% of 8 chips for every workload —",
            "that relative retention is the 8->128 >=90% scaling claim.",
            "The absolute overhead depends on arithmetic intensity",
            "(FLOPs/sample vs gradient bytes): low-intensity dense probes",
            "are comm-bound at small batch, the reference's actual",
            "benchmark models (ResNets) clear 90% absolute at bf16",
            "gradients and batch 128. Assumptions: v5e peak 197 Tf/s bf16",
            "at 40% MFU, ICI 45 GB/s per direction, no compute/comm",
            "overlap (pessimistic).",
            "",
        ]
        for label, points in tables:
            lines += [f"### {label}", "",
                      "| chips | compute/round | exposed comm | efficiency |",
                      "|---|---|---|---|"]
            for p in points:
                lines.append(
                    f"| {p.n_chips} | {p.compute_s * 1e6:.1f} us | "
                    f"{p.comm_s * 1e6:.1f} us | {p.efficiency:.1%} |"
                )
            lines.append("")
        lines += [
            "Byzantine aggregation itself is chip-local after the",
            "transpose (coordinate-wise families) or an (n, n) Gram psum",
            "(geometric families) — both negligible next to the gradient",
            "transpose at d >= 1M.",
            "",
        ]
        out = os.path.join(here, "docs", "comm_model.md")
        with open(out, "w") as fh:
            fh.write("\n".join(lines))
        print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
