"""Sharded weight update benchmark: trajectory parity + HLO byte evidence.

Measures, per update-shard variant of the fused PS round (replicated /
sharded × params-gather precision off|bf16|int8), all from the compiled
artifact (`byzpy_tpu.parallel.comms` parses the optimized HLO):

1. **per-round collective wire bytes** — the gradient-transpose
   all-to-all is identical across variants; the update move changes from
   an exact f32 aggregated-gradient all-gather (replicated: it feeds
   every chip's optimizer state) to a params all-gather that compresses
   freely (sharded: each chip's exact shard stays in the carried state).
2. **per-chip carried update state** — replicated keeps every optimizer
   moment whole on every chip; the sharded update splits moments + the
   authoritative flat param shard over the feature grid
   (`comms.opt_state_bytes` law, checked against the leaves' actual
   shard shapes).
3. **fixed-seed trajectory parity** — sharded f32 must match the
   replicated round within f32 fusion-reorder noise (their per-coordinate
   math is identical for coordinate-wise aggregators + elementwise
   optimizers); bf16/int8 gathers must stay inside the blockwise error
   contract per round. The same check runs for the gossip builders
   (feature-sharded exchange) on the general-topology and ring fabrics.

``--smoke`` is the CI leg: a 2-device CPU mesh, hard parity assertions,
and the byte floors (sharded opt state < replicated; int8 params gather
< f32/3). Full runs append provenance-stamped JSON lines to
``results/sharded_update_<platform>.jsonl``.

Run: ``JAX_PLATFORMS=cpu python benchmarks/sharded_update_bench.py [--smoke]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))


def _provenance(platform: str) -> dict:
    return {
        "platform": platform,
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI leg: 2-device mesh + hard assertions")
    ap.add_argument("--out", default=None, help="JSONL sink override")
    ap.add_argument("--steps", type=int, default=4,
                    help="fixed-seed parity trajectory length")
    args = ap.parse_args()

    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from byzpy_tpu.engine.peer_to_peer.topology import Topology
    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.ops import robust
    from byzpy_tpu.parallel.comms import (
        collective_traffic,
        measured_opt_state_bytes,
        opt_state_bytes,
        ps_round_wire_bytes,
    )
    from byzpy_tpu.parallel.gossip import (
        GossipStepConfig,
        build_gossip_train_step,
        build_ring_gossip_train_step,
    )
    from byzpy_tpu.parallel.mesh import node_mesh
    from byzpy_tpu.parallel.ps import (
        PSStepConfig,
        ShardedUpdateConfig,
        build_ps_train_step,
    )
    from byzpy_tpu.observability.compat import timed_call_s

    platform = jax.default_backend()
    n_dev = 2 if args.smoke else min(8, len(jax.devices()))
    mesh = node_mesh(n_dev, devices=jax.devices()[:n_dev])
    d_model, d_out = (64, 32) if args.smoke else (1024, 1024)
    d = d_model * d_out
    out_path = args.out or os.path.join(
        HERE, "results", f"sharded_update_{platform}.jsonl"
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    rows = []

    params = {
        "w": jax.random.normal(jax.random.PRNGKey(2), (d_model, d_out)) * 0.1
    }
    bundle = ModelBundle(
        apply_fn=lambda p, xb: xb @ p["w"],
        params=params,
        loss_fn=lambda p, xb, yb: jnp.mean((xb @ p["w"] - yb) ** 2),
    )
    cfg = PSStepConfig(n_nodes=n_dev, n_byzantine=0 if n_dev < 4 else 1)
    bx = jax.random.normal(jax.random.PRNGKey(3), (n_dev, 16, d_model))
    by = jax.random.normal(jax.random.PRNGKey(4), (n_dev, 16, d_out))
    key = jax.random.PRNGKey(5)
    agg = (lambda m: jnp.mean(m, axis=0)) if n_dev < 4 else (
        lambda m: robust.trimmed_mean(m, f=1)
    )

    # -- 1+2. PS round: wire bytes + carried-state HBM per variant ------
    VARIANTS = (
        ("replicated", "off", "off"),
        ("sharded_f32", "on", "off"),
        ("sharded_bf16",
         ShardedUpdateConfig(mode="on", param_gather_precision="bf16"), "bf16"),
        ("sharded_int8",
         ShardedUpdateConfig(mode="on", param_gather_precision="int8"), "int8"),
    )
    gathers = {}
    states = {}
    trajs = {}
    import optax

    for label, su, pprec in VARIANTS:
        # Adam: 2 moment slots — the carried-state law (slots·n/(slots+1))
        # shows a reduction at every mesh size, incl. the 2-device smoke
        step, o0 = build_ps_train_step(
            bundle, agg, cfg, mesh=mesh, sharded_update=su,
            optimizer=optax.adam(1e-3),
        )
        jitted = jax.jit(step)
        traffic = collective_traffic(jitted, params, o0, bx, by, key)
        state_b = measured_opt_state_bytes(o0)
        law_wire = ps_round_wire_bytes(
            d, n_dev, update_sharded=label != "replicated",
            param_precision=pprec,
        )
        law_state = opt_state_bytes(
            d, slots=2, update_sharded=label != "replicated", n_shards=n_dev,
        )
        ms = timed_call_s(
            lambda p, o: jitted(p, o, bx, by, key)[0], params, o0,
            warmup=1, repeat=3 if args.smoke else 10,
        ) * 1e3
        gathers[label] = traffic["per_opcode_bytes"].get("all-gather", 0)
        states[label] = state_b
        p, o = params, o0
        for _ in range(args.steps):
            p, o, m = jitted(p, o, bx, by, key)
        trajs[label] = np.asarray(p["w"]).ravel()
        rows.append({
            "bench": "ps_update_shard", "variant": label, "d": d,
            "n_dev": n_dev,
            "wire_bytes_per_device": traffic["wire_bytes_per_device"],
            "per_opcode_bytes": traffic["per_opcode_bytes"],
            "carried_state_bytes_per_chip": state_b,
            "law_wire_bytes": round(law_wire, 1),
            "law_state_bytes": law_state,
            "ms_per_step": round(ms, 3),
            **_provenance(platform),
        })
        print(f"ps {label:13s}: wire {traffic['wire_bytes_per_device']:>10,} "
              f"B/dev  gather {gathers[label]:>9,}  state {state_b:>9,} "
              f"B/chip  {ms:.2f} ms/step")

    # -- 3. fixed-seed trajectory parity --------------------------------
    dev_f32 = float(np.abs(trajs["sharded_f32"] - trajs["replicated"]).max())
    scale = float(np.abs(trajs["replicated"]).max())
    print(f"parity sharded_f32 vs replicated: max|Δ| {dev_f32:.3e} "
          f"(|params| max {scale:.3f})")
    rows.append({
        "bench": "ps_parity", "steps": args.steps, "max_abs_dev_f32": dev_f32,
        "max_abs_dev_bf16": float(
            np.abs(trajs["sharded_bf16"] - trajs["replicated"]).max()
        ),
        "max_abs_dev_int8": float(
            np.abs(trajs["sharded_int8"] - trajs["replicated"]).max()
        ),
        "params_scale": scale, **_provenance(platform),
    })

    # -- 4. gossip builders: feature-sharded exchange -------------------
    gcfg = GossipStepConfig(n_nodes=n_dev, n_byzantine=0)
    topo = Topology.ring(n_dev, min(2, n_dev - 1))
    g_traj = {}
    for label, us in (("replicated", "off"), ("sharded", "on")):
        gstep, ginit = build_gossip_train_step(
            bundle, agg, topo, gcfg, mesh=mesh, update_sharding=us,
        )
        gstep = jax.jit(gstep)
        theta = ginit()
        traffic = collective_traffic(gstep, theta, bx, by, key)
        for _ in range(args.steps):
            theta, _ = gstep(theta, bx, by, key)
        g_traj[label] = np.asarray(theta)
        rows.append({
            "bench": "gossip_update_shard", "variant": label, "d": d,
            "n_dev": n_dev,
            "wire_bytes_per_device": traffic["wire_bytes_per_device"],
            "per_opcode_bytes": traffic["per_opcode_bytes"],
            **_provenance(platform),
        })
        print(f"gossip {label:10s}: wire "
              f"{traffic['wire_bytes_per_device']:>10,} B/dev  "
              f"{traffic['per_opcode_bytes']}")
    g_dev = float(np.abs(g_traj["sharded"] - g_traj["replicated"]).max())
    print(f"parity gossip sharded vs replicated: max|Δ| {g_dev:.3e}")

    # ring gossip shard split (coordinate-wise contract; win at k >= 2)
    r_traj = {}
    k = min(2, n_dev - 1)
    for label, us in (("replicated", "off"), ("sharded", "on")):
        rstep, rinit = build_ring_gossip_train_step(
            bundle, robust.coordinate_median, gcfg, mesh, k=k,
            update_sharding=us,
        )
        rstep = jax.jit(rstep)
        theta = rinit()
        traffic = collective_traffic(rstep, theta, bx, by, key)
        for _ in range(args.steps):
            theta, _ = rstep(theta, bx, by, key)
        r_traj[label] = np.asarray(theta)
        rows.append({
            "bench": "ring_gossip_update_shard", "variant": label, "d": d,
            "k": k, "n_dev": n_dev,
            "wire_bytes_per_device": traffic["wire_bytes_per_device"],
            "per_opcode_bytes": traffic["per_opcode_bytes"],
            **_provenance(platform),
        })
        print(f"ring   {label:10s}: wire "
              f"{traffic['wire_bytes_per_device']:>10,} B/dev  "
              f"{traffic['per_opcode_bytes']}")
    r_dev = float(np.abs(r_traj["sharded"] - r_traj["replicated"]).max())
    print(f"parity ring sharded vs replicated: max|Δ| {r_dev:.3e}")
    rows.append({
        "bench": "gossip_parity", "steps": args.steps,
        "max_abs_dev_gossip": g_dev, "max_abs_dev_ring": r_dev,
        **_provenance(platform),
    })

    with open(out_path, "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows -> {out_path}")

    # -- acceptance floors ---------------------------------------------
    ok = True
    # f32 fusion-reorder noise only: ~ulp-scale, far under any gradient
    tol = 1e-6 * max(scale, 1.0)
    if dev_f32 > tol:
        print(f"FAIL: sharded f32 trajectory deviates {dev_f32:.3e} > {tol:.1e}",
              file=sys.stderr)
        ok = False
    if g_dev > tol or r_dev > tol:
        print(f"FAIL: gossip parity ({g_dev:.3e} / {r_dev:.3e}) > {tol:.1e}",
              file=sys.stderr)
        ok = False
    if states["sharded_f32"] * 2 > states["replicated"] and n_dev >= 4:
        print("FAIL: sharded opt state not reduced >= 2x", file=sys.stderr)
        ok = False
    if states["sharded_f32"] >= states["replicated"]:
        print("FAIL: sharded opt state not below replicated", file=sys.stderr)
        ok = False
    if gathers["sharded_int8"] * 3 > gathers["sharded_f32"]:
        print("FAIL: int8 params gather not >= 3x smaller", file=sys.stderr)
        ok = False
    if not ok:
        return 1
    print("sharded-update parity + byte floors: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
