"""Render the measured grid into comparison plots
(ref: ``byzpy/benchmarks/pytorch/generate_benchmark_plots.py``).

Reads ``benchmarks/results/grid.jsonl`` (written by ``full_grid.py``) and
produces:

* ``results/grid_latency.png`` — per-workload latency, byzpy_tpu vs the
  reference's best published number (log scale);
* ``results/grid_speedup.png`` — speedup bars vs the reference best.

Matplotlib only; no seaborn, no style deps.
"""

import os
import sys

from _plotting import RESULTS, load_jsonl, plt


def load_grid(path=None):
    path = path or os.path.join(RESULTS, "grid.jsonl")
    rows = [
        row for row in load_jsonl(path)
        if "ref_best_pool_ms" in row or "ref_direct_ms" in row
    ]
    return rows


def ref_best(row):
    """The reference's best published latency for this workload: its best
    pool unless its own pooling made it slower than direct."""
    candidates = [
        v for v in (row.get("ref_best_pool_ms"), row.get("ref_direct_ms"))
        if v is not None
    ]
    return min(candidates) if candidates else None


def main() -> None:
    rows = load_grid(sys.argv[1] if len(sys.argv) > 1 else None)
    rows = [r for r in rows if ref_best(r) is not None]
    rows.sort(key=lambda r: ref_best(r) / r["ms"], reverse=True)
    names = [r["workload"] for r in rows]
    ours = [r["ms"] for r in rows]
    refs = [ref_best(r) for r in rows]

    # latency comparison
    fig, ax = plt.subplots(figsize=(10, 0.42 * len(rows) + 1.5))
    y = range(len(rows))
    ax.barh([i + 0.2 for i in y], refs, height=0.38,
            label="reference (best published, CPU)", color="#b0b7c3")
    ax.barh([i - 0.2 for i in y], ours, height=0.38,
            label="byzpy_tpu (one v5e)", color="#3b6fd4")
    ax.set_yticks(list(y), names, fontsize=8)
    ax.set_xscale("log")
    ax.set_xlabel("latency, ms (log scale; lower is better)")
    ax.legend(loc="lower right", fontsize=8)
    ax.invert_yaxis()
    fig.tight_layout()
    fig.savefig(os.path.join(RESULTS, "grid_latency.png"), dpi=150)

    # speedups
    fig, ax = plt.subplots(figsize=(10, 0.42 * len(rows) + 1.5))
    speedups = [rf / ms for rf, ms in zip(refs, ours, strict=False)]
    colors = ["#2e9e59" if s >= 1 else "#c5483e" for s in speedups]
    ax.barh(list(y), speedups, color=colors, height=0.6)
    ax.axvline(1.0, color="black", linewidth=0.8)
    ax.set_yticks(list(y), names, fontsize=8)
    ax.set_xscale("log")
    ax.set_xlabel("speedup vs reference best (log scale; >1 = faster)")
    for i, s in enumerate(speedups):
        ax.text(s, i, f" {s:.1f}×", va="center", fontsize=7)
    ax.invert_yaxis()
    fig.tight_layout()
    fig.savefig(os.path.join(RESULTS, "grid_speedup.png"), dpi=150)
    print("wrote",
          os.path.join(RESULTS, "grid_latency.png"), "and",
          os.path.join(RESULTS, "grid_speedup.png"))


if __name__ == "__main__":
    main()
