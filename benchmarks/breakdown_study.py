"""Breakdown study: accuracy as the byzantine fraction grows.

Robust aggregators have theoretical breakdown points (trimmed-mean/median
at f < n/2, Krum at f < (n-2)/2, ...); this study shows where they
actually stop rescuing training on real data: sign-flip colluders at
f = 0..3 of n = 8 nodes, final held-out accuracy per (aggregator, f).

Writes ``benchmarks/BREAKDOWN.md``. Reference analogue: the ByzFL sweeps
vary the byzantine count the same way (``benchmarks/byzfl/*_compare.py``).

Run: ``XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu python benchmarks/breakdown_study.py --write``
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rounds", type=int, default=200)
    parser.add_argument("--nodes", type=int, default=8)
    parser.add_argument("--max-byzantine", type=int, default=3)
    parser.add_argument("--attack", default="sign_flip")
    parser.add_argument(
        "--aggregators", default="mean,median,trimmed_mean,multi_krum"
    )
    parser.add_argument("--write", action="store_true")
    args = parser.parse_args()

    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    from functools import partial

    from byzpy_tpu.models.data import load_digits_dataset
    from byzpy_tpu.models.nets import digits_mlp
    from byzpy_tpu.utils.robust_study import StudyConfig, run_cell

    aggs = args.aggregators.split(",")
    data = load_digits_dataset(seed=0)
    rows = {}
    for f in range(0, args.max_byzantine + 1):
        cfg = StudyConfig(
            n_nodes=args.nodes,
            n_byzantine=f,
            rounds=args.rounds,
            eval_every=args.rounds,
        )
        for agg in aggs:
            cell = run_cell(
                partial(digits_mlp, seed=0), data, agg, args.attack, cfg
            )
            rows[(agg, f)] = cell.final_accuracy
            print(f"f={f} {agg:<14} acc={cell.final_accuracy:.3f}", flush=True)

    import jax

    lines = [
        "# Breakdown study: accuracy vs byzantine fraction",
        "",
        f"Device: `{jax.devices()[0]}`",
        "",
        f"Real digits, {args.nodes} nodes, colluding **{args.attack}**",
        f"attackers, {args.rounds} rounds; cells = final held-out accuracy",
        "(f = 0 is the clean baseline). Aggregators trim/select with the",
        "TRUE f — this measures the algorithm at its declared operating",
        "point, not mis-specification.",
        "",
        "| aggregator | " + " | ".join(f"f={f}" for f in range(args.max_byzantine + 1)) + " |",
        "|---" * (args.max_byzantine + 2) + "|",
    ]
    for agg in aggs:
        cells = " | ".join(
            f"{rows[(agg, f)]:.3f}" for f in range(args.max_byzantine + 1)
        )
        lines.append(f"| {agg} | {cells} |")
    lines += [
        "",
        "Reproduce: `python benchmarks/breakdown_study.py --write`;",
        "plot: `python benchmarks/plot_robust_learning.py` ->",
        "![breakdown](results/breakdown.png)",
        "",
    ]
    table = "\n".join(lines)
    print("\n" + table)
    if args.write:
        import json

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "BREAKDOWN.md"), "w") as fh:
            fh.write(table)
        os.makedirs(os.path.join(here, "results"), exist_ok=True)
        with open(os.path.join(here, "results", "breakdown.jsonl"), "a") as fh:
            for (agg, f), acc in sorted(rows.items()):
                fh.write(json.dumps({
                    "aggregator": agg, "n_byzantine": f,
                    "final_accuracy": round(acc, 4),
                    "attack": args.attack, "rounds": args.rounds,
                    "n_nodes": args.nodes, "device": str(jax.devices()[0]),
                }) + "\n")
        print("wrote BREAKDOWN.md + results/breakdown.jsonl")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
