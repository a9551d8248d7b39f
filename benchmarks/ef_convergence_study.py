"""Error-feedback convergence study: sub-int8 compression with and
without residual carry (ISSUE 15 acceptance: EF demonstrably
non-compounding).

One fused PS training run per (precision x error_feedback) cell — the
REAL ``build_ps_train_step`` on the 8-way CPU mesh with the
gradient-transpose fabric AND the params gather compressed — tracked
against the f32 twin for N full-batch rounds in the regime where
blockwise coding actually biases: **outlier-dominated blocks** (every
16th input feature is hot, so one coordinate sets each 256-wide block's
absmax and its quiet neighbors sit in the coarse grid's dead zone —
the embedding/layer-norm gradient shape). ``traj_dist_curve`` is
||params - params_f32|| sampled over rounds.

What the committed rows show (the study's science, reported as
measured):

* **s4 without EF ratchets**: deterministic round-to-nearest on a
  uniform 4-bit grid re-rounds the quiet coordinates the same way
  every round — the trajectory distance to f32 GROWS monotonically all
  run (compounding loss). **s4 with EF plateaus**: the carried
  residual re-injects what the grid lost, the transmitted stream
  telescopes, and the distance flattens — tracking f32 where no-EF
  diverges. The assertion: no-EF/EF final-distance ratio >=
  ``S4_EF_WIN_FLOOR`` AND the no-EF curve is still climbing at the end
  while the EF curve is flat.
* **fp8 is self-limiting**: e4m3's mantissa makes the rounding error
  RELATIVE per value, so quiet coordinates keep proportional accuracy
  and no dead zone forms — fp8 without EF stays bounded near f32, and
  EF only adds dither (parity within ``FP8_EF_PARITY``). That is a
  finding, not a failure: the byte-identical fp8 tier buys accuracy
  headroom instead of needing state, while the half-byte s4 tier needs
  EF to be usable at all — the precision ladder's real trade.

Appends one provenance-stamped JSON line per cell (plus a summary) to
``results/round15_subint8_<platform>.jsonl`` (``--out`` overrides).

Run: ``JAX_PLATFORMS=cpu python benchmarks/ef_convergence_study.py``
(the contract assertions always run; ``--rounds``/``--out`` for local
iteration — there is no ``--smoke`` shrink because the s4 crossover is
a late-round phenomenon).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HERE = os.path.dirname(os.path.abspath(__file__))

#: s4 no-EF over with-EF final trajectory-distance floor (committed CPU
#: rows sit ~1.15 at 500 rounds and keep widening — no-EF is still
#: climbing when the run ends).
S4_EF_WIN_FLOOR = 1.05
#: fp8 with-EF must stay within this factor of the (already bounded)
#: no-EF distance — EF is optional at fp8, never catastrophic.
FP8_EF_PARITY = 2.0


def main() -> int:
    # no --smoke shrink here, deliberately: the s4 no-EF/EF crossover
    # is a LATE-round phenomenon (the ratchet has to outrun the EF
    # dither) and a shrunk cell sits before it — the model is tiny and
    # compiles dominate, so CI runs the full 500-round study and its
    # hard assertions as-is (--rounds exists for local iteration)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, help="JSONL sink override")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()

    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.parallel.mesh import node_mesh
    from byzpy_tpu.parallel.ps import (
        PSStepConfig,
        ShardedUpdateConfig,
        build_ps_train_step,
    )
    from byzpy_tpu.parallel.quantization import CommPrecision

    platform = jax.default_backend()
    rounds = args.rounds or 500
    d_in, d_out = 96, 16
    n = 8
    mesh = node_mesh(8)

    params0 = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out)) * 0.1
    }
    bundle = ModelBundle(
        apply_fn=lambda p, xb: xb @ p["w"],
        params=params0,
        loss_fn=lambda p, xb, yb: jnp.mean((xb @ p["w"] - yb) ** 2),
    )
    cfg = PSStepConfig(
        n_nodes=n, n_byzantine=0, learning_rate=0.01, momentum=0.0
    )
    w_true = jax.random.normal(jax.random.PRNGKey(1), (d_in, d_out)) * 0.3
    # outlier-dominated blocks: every 16th input feature is 8x hot, so
    # each 256-wide flat block (16 features x 16 outputs, the ravel of
    # w) has one feature whose gradient sets the block absmax and 15
    # quiet neighbors living on the resulting coarse grid
    feat_scales = np.ones(d_in, np.float32)
    feat_scales[::16] = 8.0
    xs = (
        jax.random.normal(jax.random.PRNGKey(2), (n, 32, d_in))
        * jnp.asarray(feat_scales)[None, None, :]
    )
    ys = xs @ w_true + 0.02 * jax.random.normal(
        jax.random.PRNGKey(3), (n, 32, d_out)
    )

    def run_cell(precision):
        su = ShardedUpdateConfig(mode="on", param_gather_precision=precision)
        step, o0 = build_ps_train_step(
            bundle, lambda m: jnp.mean(m, axis=0), cfg,
            mesh=mesh, comm_precision=precision, sharded_update=su,
        )
        jstep = jax.jit(step)
        p, o = bundle.params, o0
        traj, metrics = [], {}
        for r in range(rounds):
            p, o, metrics = jstep(p, o, xs, ys, jax.random.PRNGKey(100 + r))
            if r % 20 == 0 or r == rounds - 1:
                traj.append(np.asarray(p["w"]))
        return traj, metrics

    out_path = args.out or os.path.join(
        HERE, "results", f"round15_subint8_{platform}.jsonl"
    )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    provenance = {
        "platform": platform, "rounds": rounds,
        "d": d_in * d_out, "n": n, "regime": "outlier_blocks",
        "time_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

    f32_traj, f32_metrics = run_cell("off")
    f32_loss = float(f32_metrics["honest_loss"])
    rows, dists, losses = [], {}, {}
    for mode in ("fp8", "s4"):
        for ef in (False, True):
            traj, metrics = run_cell(
                CommPrecision(mode=mode, error_feedback=ef)
            )
            dist = [
                float(np.linalg.norm(t - ft))
                for t, ft in zip(traj, f32_traj, strict=True)
            ]
            dists[(mode, ef)] = dist
            losses[(mode, ef)] = float(metrics["honest_loss"])
            row = {
                "bench": "ef_convergence", "mode": mode,
                "error_feedback": ef,
                "traj_dist_final": round(dist[-1], 6),
                "traj_dist_mid": round(dist[len(dist) // 2], 6),
                "traj_dist_curve": [round(v, 5) for v in dist],
                "final_loss": round(losses[(mode, ef)], 6),
                "f32_loss": round(f32_loss, 6),
                "loss_excess_vs_f32": round(
                    losses[(mode, ef)] - f32_loss, 6
                ),
                "ef_resid_transpose": (
                    round(float(metrics["ef_transpose_norm"]), 6)
                    if "ef_transpose_norm" in metrics else None
                ),
                "ef_resid_gather": (
                    round(float(metrics["ef_gather_norm"]), 6)
                    if "ef_gather_norm" in metrics else None
                ),
                **provenance,
            }
            rows.append(row)
            print(json.dumps(row))

    def still_climbing(dist):
        return dist[-1] > dist[len(dist) // 2] * 1.02

    s4_ratio = dists[("s4", False)][-1] / max(dists[("s4", True)][-1], 1e-12)
    fp8_ratio = dists[("fp8", True)][-1] / max(
        dists[("fp8", False)][-1], 1e-12
    )
    summary = {
        "bench": "ef_convergence_summary",
        "s4_noef_over_ef_final_dist": round(s4_ratio, 3),
        "s4_noef_still_climbing": still_climbing(dists[("s4", False)]),
        "s4_ef_plateaued": not still_climbing(dists[("s4", True)]),
        "s4_ef_win_floor": S4_EF_WIN_FLOOR,
        "fp8_ef_over_noef_final_dist": round(fp8_ratio, 3),
        "fp8_parity_bound": FP8_EF_PARITY,
        "fp8_noef_bounded": not still_climbing(dists[("fp8", False)]),
        "loss_excess": {
            f"{m}_{'ef' if e else 'noef'}": round(
                losses[(m, e)] - f32_loss, 6
            )
            for (m, e) in losses
        },
        **provenance,
    }
    rows.append(summary)
    print(json.dumps(summary))
    with open(out_path, "a") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows -> {out_path}")

    ok = (
        s4_ratio >= S4_EF_WIN_FLOOR
        and summary["s4_noef_still_climbing"]
        and summary["s4_ef_plateaued"]
        and fp8_ratio <= FP8_EF_PARITY
    )
    if not ok:
        print(f"FAIL: EF contract not met: {summary}", file=sys.stderr)
        return 1
    print(
        "EF non-compounding: s4-with-EF tracks f32 where s4-without-EF "
        f"still climbs (ratio {s4_ratio:.2f}); fp8 self-limiting "
        f"(EF parity {fp8_ratio:.2f}) OK"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
