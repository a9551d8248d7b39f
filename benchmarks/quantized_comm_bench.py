"""Quantized communication fabric benchmark: wire bytes + steps/sec.

Three measurements per precision mode (off / bf16 / int8 plus the
sub-int8 tier fp8 / fp8_e5m2 / s4), all from the *compiled artifact*
(`byzpy_tpu.parallel.comms` parses the optimized HLO, so byte counts
are facts about the program XLA runs, not estimates):

1. **collective wire bytes** — ``all_gather_q`` and
   ``reduce_scatter_sum_q`` over an 8-way mesh: per-device interconnect
   bytes per invocation, and the compression ratio vs the f32 fabric
   (acceptance floor for this round: >= 1.5x at int8; blockwise int8
   with 256-wide blocks delivers ~3.9x).
2. **PS round wire bytes** — the fused SPMD parameter-server step with
   ``comm_precision`` threaded through ``build_ps_train_step``: the
   gradient-transpose all-to-all is the round's dominant term and must
   shrink by the same factor.
3. **steps/sec** of that PS step per mode (on CPU the interconnect is
   memcpy so the win is bytes, not time; on ICI both move together —
   not measured on a chip yet, ROADMAP S5).

A quantize/dequantize round-trip error-bound parity check runs first —
`--smoke` is the CI leg (small shapes, asserts the ratio floor and the
error contract, one quantized-collective step executed end to end).

Appends one provenance-stamped JSON line per (measurement, mode) to
``results/quantized_comm_<platform>.jsonl`` (``--out`` overrides).

Run: ``JAX_PLATFORMS=cpu python benchmarks/quantized_comm_bench.py [--smoke]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))

MODES = ("off", "bf16", "int8", "fp8", "fp8_e5m2", "s4")


def _provenance(platform: str) -> dict:
    return {
        "platform": platform,
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes + hard assertions")
    ap.add_argument("--out", default=None, help="JSONL sink override")
    ap.add_argument("--d", type=int, default=None,
                    help="feature dim for the collective probes")
    ap.add_argument("--repeat", type=int, default=None)
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
    from jax.sharding import PartitionSpec as P

    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.ops import robust
    from byzpy_tpu.parallel import collectives as coll
    from byzpy_tpu.parallel import quantization as qz
    from byzpy_tpu.parallel.comms import collective_traffic
    from byzpy_tpu.parallel.mesh import node_mesh, sharding
    from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step
    from byzpy_tpu.observability.compat import timed_call_s

    platform = jax.default_backend()
    d = args.d or (8_192 if args.smoke else 262_144)
    repeat = args.repeat or (3 if args.smoke else 10)
    out_path = args.out or os.path.join(
        HERE, "results", f"quantized_comm_{platform}.jsonl"
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    rows = []

    # -- 0. round-trip parity gate ------------------------------------
    x = jax.random.normal(jax.random.PRNGKey(0), (64, d), jnp.float32) * 2.0
    q = qz.quantize_blockwise(x)
    err = np.abs(np.asarray(q.dequantize() - x))
    bound = np.asarray(qz.quantization_error_bound(x))
    # the half-step bound holds up to f32 roundoff in x/scale (~1e-5 rel)
    assert (err <= bound * 1.0001 + 1e-7).all(), \
        "int8 round-trip violates absmax/254"
    rows.append({
        "bench": "quant_roundtrip", "d": d, "max_err": float(err.max()),
        "max_bound": float(bound.max()), **_provenance(platform),
    })
    print(f"round-trip parity OK (max err {err.max():.3e} <= bound)")

    # -- 1. collective wire bytes -------------------------------------
    mesh = node_mesh(8)
    xs = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (8, d), jnp.float32),
        sharding(mesh, "nodes"),
    )

    def gather_fn(mode):
        return coll.sharded_fn(
            mesh, "nodes",
            lambda s: coll.all_gather_q(s, "nodes", precision=mode),
            in_spec=P("nodes"), out_spec=P(),
        )

    def scatter_fn(mode):
        return coll.sharded_fn(
            mesh, "nodes",
            lambda s: coll.reduce_scatter_sum_q(s[0], "nodes", precision=mode)[None],
            in_spec=P("nodes"), out_spec=P("nodes"),
        )

    ratios = {}
    for name, build in (("all_gather_q", gather_fn), ("reduce_scatter_sum_q", scatter_fn)):
        base_bytes = None
        for mode in MODES:
            fn = build(mode)
            traffic = collective_traffic(fn, xs)
            wire = traffic["wire_bytes_per_device"]
            ms = timed_call_s(fn, xs, warmup=1, repeat=repeat) * 1e3
            if mode == "off":
                base_bytes = wire
            ratio = base_bytes / wire if wire else float("inf")
            ratios[(name, mode)] = ratio
            rows.append({
                "bench": name, "mode": mode, "d": d,
                "wire_bytes_per_device": wire,
                "bytes_ratio_vs_off": round(ratio, 3),
                "ms": round(ms, 3),
                "per_opcode_bytes": traffic["per_opcode_bytes"],
                **_provenance(platform),
            })
            print(f"{name:22s} {mode:5s}: {wire:>12,} B/device "
                  f"({ratio:.2f}x vs off)  {ms:.2f} ms")

    # -- 2+3. PS round: wire bytes + steps/sec ------------------------
    d_model, d_out = (64, 8) if args.smoke else (512, 32)
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(2), (d_model, d_out)) * 0.1
    }

    def apply_fn(p, xb):
        return xb @ p["w"]

    def loss_fn(p, xb, yb):
        return jnp.mean((apply_fn(p, xb) - yb) ** 2)

    bundle = ModelBundle(apply_fn=apply_fn, params=params, loss_fn=loss_fn)
    cfg = PSStepConfig(n_nodes=8, n_byzantine=1)
    bx = jax.random.normal(jax.random.PRNGKey(3), (8, 32, d_model))
    by = jax.random.normal(jax.random.PRNGKey(4), (8, 32, d_out))
    key = jax.random.PRNGKey(5)

    ps_base = None
    for mode in MODES:
        step, o0 = build_ps_train_step(
            bundle, lambda m: robust.trimmed_mean(m, f=1), cfg,
            mesh=mesh, comm_precision=mode,
        )
        jitted = jax.jit(step)
        traffic = collective_traffic(jitted, params, o0, bx, by, key)
        wire = traffic["wire_bytes_per_device"]
        ms = timed_call_s(
            lambda p, o: jitted(p, o, bx, by, key)[0], params, o0,
            warmup=1, repeat=repeat,
        ) * 1e3
        if mode == "off":
            ps_base = wire
        ratio = ps_base / wire if wire else float("inf")
        rows.append({
            "bench": "ps_round", "mode": mode,
            "d_params": d_model * d_out,
            "wire_bytes_per_device": wire,
            "bytes_ratio_vs_off": round(ratio, 3),
            "ms_per_step": round(ms, 3),
            "steps_per_sec": round(1e3 / ms, 2) if ms else None,
            **_provenance(platform),
        })
        print(f"{'ps_round':22s} {mode:5s}: {wire:>12,} B/device "
              f"({ratio:.2f}x vs off)  {ms:.2f} ms/step")

    with open(out_path, "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows -> {out_path}")

    # acceptance floors: quantized collectives move >= 1.5x fewer bytes
    # at int8; the sub-int8 tier must clear >= 3.5x at fp8 and >= 7x at
    # s4 vs f32 (fp8 is byte-identical to int8 — 1 B/value — so its win
    # vs f32 matches int8's ~3.9x; s4 halves the payload again)
    floors = {"int8": 1.5, "fp8": 3.5, "s4": 7.0}
    bad = [
        (name, mode, ratios[(name, mode)])
        for name in ("all_gather_q", "reduce_scatter_sum_q")
        for mode, fl in floors.items()
        if ratios[(name, mode)] < fl
    ]
    if bad:
        print(f"FAIL: wire-bytes reduction below floor: {bad}", file=sys.stderr)
        return 1
    print("wire-bytes reduction floors (int8 1.5x, fp8 3.5x, s4 7x): OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
