"""Robust learning on REAL data: byzantine nodes vs robust aggregation.

The reference's flagship demo trains MNIST under attack and shows accuracy
rescued by a robust aggregator (ref: ``examples/ps/thread/mnist.py``).
This is the TPU-native equivalent on the real handwritten-digits dataset
bundled with the image: the whole Byzantine round — per-node grads,
colluding sign-flip rows, trimmed-mean aggregation, SGD — is ONE jitted
SPMD step (``byzpy_tpu.parallel.ps``). Compare the two runs it prints:
plain mean collapses to ~10% (random) accuracy; trimmed mean learns.

Run: ``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu
python examples/ps/real_data_robust.py`` (or on a TPU mesh as-is).

For full-size MNIST, point ``byzpy_tpu.models.data.load_mnist_idx`` at a
directory of IDX files and swap the loader + ``mnist_mlp`` below.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

from byzpy_tpu.utils.robust_study import StudyConfig, results_table, run_study

ROUNDS = int(os.environ.get("PS_ROUNDS", 200))


def main():
    cfg = StudyConfig(rounds=ROUNDS, eval_every=max(1, ROUNDS // 4))
    results = run_study(
        aggregators=("mean", "trimmed_mean"),
        attacks=("sign_flip",),
        cfg=cfg,
    )
    print()
    print(results_table(results))
    by_agg = {r.aggregator: r.final_accuracy for r in results}
    if ROUNDS >= 100:  # smoke runs with tiny ROUNDS can't reach the contract
        assert by_agg["mean"] < 0.5, "mean should be destroyed by the attack"
        assert by_agg["trimmed_mean"] > 0.8, "trimmed mean should rescue training"
    print(
        f"\nsign-flip attack: mean ends at {by_agg['mean']:.1%} (destroyed), "
        f"trimmed mean at {by_agg['trimmed_mean']:.1%} (rescued)"
    )


if __name__ == "__main__":
    main()
