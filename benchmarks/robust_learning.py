"""Accuracy-under-attack grid on real data (the robust-*learning* study).

Mirrors the reference's ByzFL accuracy sweeps
(``/root/reference/benchmarks/byzfl/*_compare.py``) and the MNIST example's
accuracy eval (``/root/reference/examples/ps/thread/mnist.py:114-119``):
every (aggregator x attack) cell is a full training run on the real
handwritten-digits dataset through the fused SPMD parameter-server step,
scored on held-out data.

Writes ``benchmarks/ROBUST_LEARNING.md`` (accuracy matrix + trajectories)
and appends one JSON row per cell to
``benchmarks/results/robust_learning.jsonl``.

Run on any backend; for the CPU mesh use::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/robust_learning.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_APPENDIX_MARKERS = ("\n## BF16 gradients", "\n## Decentralized (gossip)")


def _replace_section(md_path: str, marker: str, section_text: str) -> None:
    """Idempotently install ``marker``'s appendix section in the study
    doc: replace it in place if present (up to the next appendix marker
    or EOF), append otherwise."""
    existing = open(md_path).read() if os.path.exists(md_path) else ""
    starts = {m: existing.index(m) for m in _APPENDIX_MARKERS if m in existing}
    if marker in starts:
        s = starts[marker]
        later = [i for i in starts.values() if i > s]
        e = min(later) if later else len(existing)
        new = existing[:s] + section_text + existing[e:]
    else:
        new = existing + section_text
    with open(md_path, "w") as fh:
        fh.write(new)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--rounds", type=int, default=240,  # the committed grid/plot provenance
    )
    parser.add_argument("--nodes", type=int, default=8)
    parser.add_argument("--byzantine", type=int, default=2)
    parser.add_argument("--batch", type=int, default=32)
    parser.add_argument("--eval-every", type=int, default=50)
    parser.add_argument(
        "--aggregators",
        default="mean,median,trimmed_mean,multi_krum,nnm_trimmed_mean",
    )
    parser.add_argument("--attacks", default="none,sign_flip,little,empire")
    parser.add_argument(
        "--write", action="store_true", help="update ROBUST_LEARNING.md + jsonl"
    )
    parser.add_argument(
        "--grad-dtype", default=None, choices=[None, "bfloat16", "float32"],
        help="cast per-node gradients before attack+aggregation; "
             "bfloat16 halves robust-pipeline HBM traffic (params stay f32). "
             "With --write, a bfloat16 run appends the BF16 section to "
             "ROBUST_LEARNING.md instead of rewriting it.",
    )
    parser.add_argument(
        "--mode", default="ps", choices=["ps", "gossip"],
        help="training fabric per cell: fused SPMD parameter-server round "
             "or decentralized gossip (complete topology). With --write, "
             "a gossip run appends the Decentralized section to "
             "ROBUST_LEARNING.md instead of rewriting it.",
    )
    args = parser.parse_args()
    if args.mode == "gossip" and args.grad_dtype is not None:
        parser.error("--grad-dtype is a PS-mode knob (gossip exchanges "
                     "parameters, not gradients)")

    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax

    from byzpy_tpu.utils.robust_study import (
        StudyConfig,
        results_table,
        run_study,
    )

    cfg = StudyConfig(
        n_nodes=args.nodes,
        n_byzantine=args.byzantine,
        rounds=args.rounds,
        batch_size=args.batch,
        eval_every=args.eval_every,
        grad_dtype=args.grad_dtype,
    )
    results = run_study(
        aggregators=tuple(args.aggregators.split(",")),
        attacks=tuple(args.attacks.split(",")),
        cfg=cfg,
        mode=args.mode,
    )
    table = results_table(results)
    print(table)

    if args.write:
        here = os.path.dirname(os.path.abspath(__file__))
        os.makedirs(os.path.join(here, "results"), exist_ok=True)
        with open(os.path.join(here, "results", "robust_learning.jsonl"), "a") as fh:
            for r in results:
                row = r.row()
                row.update(
                    device=str(jax.devices()[0]),
                    rounds=cfg.rounds,
                    n_nodes=cfg.n_nodes,
                    n_byzantine=cfg.n_byzantine,
                    grad_dtype=cfg.grad_dtype or "float32",
                    mode=args.mode,
                )
                fh.write(json.dumps(row) + "\n")
        md_path = os.path.join(here, "ROBUST_LEARNING.md")
        if args.grad_dtype == "bfloat16":
            section = [
                "",
                "## BF16 gradients (robustness survives the cast)",
                "",
                "Same grid with per-node gradients cast to **bfloat16**",
                "before the attack + robust aggregation (the dtype the",
                "150k grads/sec headline kernel runs at; robust ops",
                "accumulate in f32, the aggregated update is applied to",
                "f32 params — the mixed-precision trainer shape).",
                f"{cfg.rounds} rounds, {cfg.n_nodes} nodes, "
                f"{cfg.n_byzantine} byzantine.",
                "",
                table,
                "",
                "Reproduce: `python benchmarks/robust_learning.py "
                "--grad-dtype bfloat16 --write`.",
            ]
            _replace_section(
                md_path, "\n## BF16 gradients", "\n".join(section) + "\n"
            )
            print("updated BF16 section in ROBUST_LEARNING.md")
            return 0
        if args.mode == "gossip":
            section = [
                "",
                "## Decentralized (gossip) cells",
                "",
                "Same grid trained by P2P gossip instead of the PS round:",
                "complete topology, every honest node half-steps on its",
                "shard and robust-aggregates its in-neighborhood; byzantine",
                "nodes broadcast the attack vector. Plain SGD by",
                "construction (parameters themselves gossip — no per-node",
                "momentum state), so absolute accuracies differ slightly",
                "from the PS table; the robust-vs-mean story is the same.",
                f"{cfg.rounds} rounds, {cfg.n_nodes} nodes, "
                f"{cfg.n_byzantine} byzantine. Accuracy is node 0's model.",
                "",
                table,
                "",
                "Reproduce: `python benchmarks/robust_learning.py "
                "--mode gossip --write`.",
            ]
            _replace_section(
                md_path, "\n## Decentralized (gossip)",
                "\n".join(section) + "\n",
            )
            print("updated Decentralized section in ROBUST_LEARNING.md")
            return 0
        md = [
            "# Robust learning on real data (accuracy under attack)",
            "",
            "Real handwritten digits (sklearn's bundled UCI set, 1348 train /",
            "449 held-out, 10 classes), MLP(64), fused SPMD PS round:",
            f"{cfg.n_nodes} nodes, {cfg.n_byzantine} byzantine, "
            f"{cfg.rounds} rounds, batch {cfg.batch_size}/node, "
            f"SGD lr={cfg.learning_rate} m={cfg.momentum}.",
            "Columns are attacks (colluding byzantine rows); cells are",
            "final held-out accuracy.",
            "",
            f"Device: `{jax.devices()[0]}`",
            "",
            table,
            "",
            "Reference analogue: torchvision-MNIST accuracy eval",
            "(`examples/ps/thread/mnist.py:114-119`) and the ByzFL",
            "aggregator-vs-attack sweeps (`benchmarks/byzfl/*_compare.py`).",
            "Reproduce: `python benchmarks/robust_learning.py --write`;",
            "plot: `python benchmarks/plot_robust_learning.py` ->",
            "![trajectories](results/robust_learning.png)",
            "",
            "## Trajectories (round, held-out accuracy)",
            "",
        ]
        for r in results:
            md.append(
                f"- **{r.aggregator}** vs **{r.attack}**: "
                + ", ".join(f"({n}, {a:.3f})" for n, a in r.history)
            )
        # the base (f32 PS) rewrite must not destroy appended variant
        # sections (each documented reproduce command is independent)
        appendix = ""
        if os.path.exists(md_path):
            existing = open(md_path).read()
            starts = [
                existing.index(m) for m in _APPENDIX_MARKERS if m in existing
            ]
            if starts:
                appendix = existing[min(starts):]
        with open(md_path, "w") as fh:
            fh.write("\n".join(md) + "\n" + appendix)
        print("wrote ROBUST_LEARNING.md")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
