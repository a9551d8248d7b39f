"""Robustness study: aggregator output error under quantized comms vs
the Byzantine perturbation each aggregator already tolerates.

The argument for int8 wire traffic in a *robust* aggregation system is
not "the error is small in absolute terms" — it is that every aggregator
here is built to absorb ADVERSARIAL per-row perturbations, and the
bounded, symmetric, per-coordinate error of blockwise int8 is a far
weaker disturbance than the attacks in its design envelope. This study
measures that claim per aggregator at the BASELINE grid shapes:

* ``byz_shift``  = ||agg(X_attacked) - agg(X_clean)||_2 — how far a real
  attack (within the aggregator's f-tolerance) moves the output: the
  perturbation the aggregator is already accepted to tolerate. A
  selection aggregator can absorb an attack EXACTLY (Krum picking the
  same winner -> shift 0), so the tolerance denominator is
  ``max(byz_shift, resample_shift)`` where ``resample_shift`` is the
  output movement between two legitimate honest draws — the noise floor
  any deployment already accepts per round.
* ``int8_err`` / ``bf16_err`` = ||agg(wire(X_attacked)) - agg(X_attacked)||_2
  where ``wire`` is the quantize->dequantize round trip every row pays
  on a compressed fabric (the worst case: *all* rows quantized, as in
  the PS gradient transpose).
* ``ratio`` = quant error / byz shift. The acceptance bar for this
  round: int8 ratio < 1 for every aggregator/attack pair (in practice
  it sits around 1e-2 — two orders of magnitude below the tolerated
  perturbation).

Appends one provenance-stamped JSON line per (aggregator, attack, mode)
to ``results/quant_robustness_<platform>.jsonl`` (``--out`` overrides)
and prints the summary table committed in ``benchmarks/RESULTS.md``.

Run: ``JAX_PLATFORMS=cpu python benchmarks/quant_robustness_study.py [--smoke]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: small d, core aggregators")
    ap.add_argument("--out", default=None, help="JSONL sink override")
    ap.add_argument("--d", type=int, default=None)
    args = ap.parse_args()

    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from byzpy_tpu.ops import attack_ops, robust
    from byzpy_tpu.parallel import quantization as qz

    platform = jax.default_backend()
    # BASELINE.md grid row: 64 nodes x 65,536 features, f = 8
    n, f = 64, 8
    d = args.d or (4_096 if args.smoke else 65_536)
    q_sel = n - f - 2  # Multi-Krum selection size at the grid config

    aggregators = {
        "cw_median": robust.coordinate_median,
        "cw_trimmed_mean": partial(robust.trimmed_mean, f=f),
        "meamed": partial(robust.mean_of_medians, f=f),
        "multi_krum": partial(robust.multi_krum, f=f, q=q_sel),
        "krum": partial(robust.krum, f=f),
        "cge": partial(robust.cge, f=f),
        "monna": partial(robust.monna, f=f),
        "geometric_median": robust.geometric_median,
        "centered_clipping": partial(robust.centered_clipping, c_tau=10.0),
    }
    if args.smoke:
        for name in ("geometric_median", "centered_clipping", "monna"):
            aggregators.pop(name)

    key = jax.random.PRNGKey(0)
    k_clean, k_extra, k_g = jax.random.split(key, 3)
    # heterogeneous-ish honest gradients: shared signal + per-node noise
    signal = jax.random.normal(k_g, (1, d), jnp.float32)
    x_clean = signal + jax.random.normal(k_clean, (n, d), jnp.float32)
    x_clean2 = signal + jax.random.normal(k_extra, (n, d), jnp.float32)

    def attacked(kind):
        honest = x_clean[: n - f]
        if kind == "empire":
            vec = attack_ops.empire(honest, scale=-1.1)
        elif kind == "little":
            vec = attack_ops.little(honest, f=f, n_total=n)
        elif kind == "sign_flip":
            vec = attack_ops.sign_flip(jnp.mean(honest, axis=0), scale=-4.0)
        else:
            raise ValueError(kind)
        return jnp.concatenate(
            [honest, jnp.broadcast_to(vec, (f, d)).astype(honest.dtype)], axis=0
        )

    attacks = ("empire", "little") if args.smoke else (
        "empire", "little", "sign_flip"
    )

    out_path = args.out or os.path.join(
        HERE, "results", f"quant_robustness_{platform}.jsonl"
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    provenance = {
        "platform": platform, "n": n, "d": d, "f": f,
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

    rows, failures = [], []
    hdr = (f"{'aggregator':18s} {'attack':9s} {'tolerance':>11s} "
           f"{'int8_err':>11s} {'bf16_err':>11s} {'int8/tol':>9s}")
    print(hdr)
    print("-" * len(hdr))
    for agg_name, agg in aggregators.items():
        agg_j = jax.jit(agg)
        base_clean = agg_j(x_clean)
        resample_shift = float(jnp.linalg.norm(agg_j(x_clean2) - base_clean))
        for att in attacks:
            x_att = attacked(att)
            base_att = agg_j(x_att)
            byz_shift = float(jnp.linalg.norm(base_att - base_clean))
            tolerance = max(byz_shift, resample_shift)
            errs = {}
            for mode in ("int8", "bf16", "fp8", "fp8_e5m2", "s4"):
                if mode == "bf16":
                    wire = x_att.astype(jnp.bfloat16).astype(jnp.float32)
                else:
                    wire = qz.dequantize_blockwise(
                        qz.encode_blockwise(x_att, mode)
                    )
                errs[mode] = float(jnp.linalg.norm(agg_j(wire) - base_att))
            ratio = errs["int8"] / tolerance if tolerance else float("inf")
            # the sub-int8 precision floor: the coarsest mode (down the
            # int8 -> fp8 -> fp8_e5m2 -> s4 ladder) reachable without
            # crossing a failed finer rung (boundary err/tol <= 1,
            # same rule as the chaos subint8_floor lane)
            floor = None
            for mode in ("int8", "fp8", "fp8_e5m2", "s4"):
                if not tolerance or errs[mode] / tolerance > 1.0:
                    break
                floor = mode
            rows.append({
                "aggregator": agg_name, "attack": att,
                "byz_shift": byz_shift, "resample_shift": resample_shift,
                "tolerance": tolerance,
                "int8_err": errs["int8"], "bf16_err": errs["bf16"],
                "fp8_err": errs["fp8"], "fp8_e5m2_err": errs["fp8_e5m2"],
                "s4_err": errs["s4"],
                "int8_over_tolerance": ratio,
                "fp8_over_tolerance": (
                    errs["fp8"] / tolerance if tolerance else float("inf")
                ),
                "s4_over_tolerance": (
                    errs["s4"] / tolerance if tolerance else float("inf")
                ),
                "precision_floor": floor, **provenance,
            })
            print(f"{agg_name:18s} {att:9s} {tolerance:11.4f} "
                  f"{errs['int8']:11.4f} {errs['bf16']:11.4f} {ratio:9.4f} "
                  f"fp8={errs['fp8']:.4f} s4={errs['s4']:.4f} "
                  f"floor={floor}")
            if ratio >= 1.0:
                failures.append((agg_name, att, ratio))

    with open(out_path, "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows -> {out_path}")

    if failures:
        print(f"FAIL: int8 error exceeds Byzantine tolerance for {failures}",
              file=sys.stderr)
        return 1
    print("int8 comm error below every aggregator's Byzantine tolerance: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
