"""HLO-derived 8→128-chip scaling projection for the fused PS round.

Runs on an 8-virtual-device CPU mesh, compiles the BASELINE config-#3
round (MNIST MLP, coordinate-wise trimmed mean, sign-flip attack) and
parses its per-device collective bytes out of the OPTIMIZED HLO
(:mod:`byzpy_tpu.parallel.comms`). The per-device payload of the round's
collectives follows the saturating ``(g-1)/g`` law, so the n=8
measurement extrapolates exactly to larger meshes; v5e ICI bandwidth and
the MLP's per-chip FLOPs then give the weak-scaling efficiency table.

Prints ONE JSON object (the ``ps_mnist_trimmed_mean_steps_per_sec``
projection). Runs on the CPU mesh: the platform pin below happens
before any backend touch.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

import jax
import jax.numpy as jnp

from byzpy_tpu.models import mnist_mlp
from byzpy_tpu.ops import attack_ops, robust
from byzpy_tpu.parallel.comms import (
    collective_traffic,
    measured_opt_state_bytes,
    opt_state_bytes,
)
from byzpy_tpu.parallel.mesh import node_mesh
from byzpy_tpu.parallel.ps import (
    PSStepConfig,
    ShardedUpdateConfig,
    build_ps_train_step,
)

N = 8
BATCH = 64

#: update-shard variants projected alongside the default round:
#: (label, sharded_update argument)
VARIANTS = (
    ("replicated", "off"),
    ("sharded_f32", "on"),
    ("sharded_bf16", ShardedUpdateConfig(mode="on", param_gather_precision="bf16")),
    ("sharded_int8", ShardedUpdateConfig(mode="on", param_gather_precision="int8")),
)


def main() -> None:
    assert len(jax.devices()) == N, jax.devices()
    mesh = node_mesh(N)
    bundle = mnist_mlp()  # 784-128-10, ~101k params — BASELINE config #3
    n_byz = 2
    cfg = PSStepConfig(n_nodes=N, n_byzantine=n_byz)
    xs = jnp.zeros((N, BATCH, 28, 28, 1), jnp.float32)
    ys = jnp.zeros((N, BATCH), jnp.int32)
    key = jax.random.PRNGKey(0)

    def build(sharded_update):
        return build_ps_train_step(
            bundle,
            lambda m: robust.trimmed_mean(m, f=n_byz),
            cfg,
            attack=lambda honest, key: attack_ops.sign_flip(
                jnp.mean(honest, axis=0)
            ),
            mesh=mesh,
            sharded_update=sharded_update,
        )

    d = sum(x.size for x in jax.tree_util.tree_leaves(bundle.params))
    ici = 4.5e10  # v5e: 45 GB/s per direction per link
    chips = (8, 16, 32, 64, 128)

    # Per-device collective payloads in this round all carry the
    # saturating (g-1)/g factor (gradient transpose all-to-all + params /
    # aggregated-gradient all-gather), so
    # bytes(n) = bytes(8) * ((n-1)/n) / (7/8). Per-chip opt-state HBM of
    # the sharded update FALLS as 1/n instead (each chip owns d/n of
    # every moment buffer), which is what lets the model size per chip
    # grow with the mesh.
    variants = {}
    for label, su in VARIANTS:
        step, opt0 = build(su)
        traffic = collective_traffic(step, bundle.params, opt0, xs, ys, key)
        w8 = float(traffic["wire_bytes_per_device"])
        variants[label] = {
            "hlo_wire_bytes_per_device_n8": w8,
            "per_opcode_bytes_n8": {
                k: float(v) for k, v in traffic["per_opcode_bytes"].items()
            },
            "opt_state_bytes_per_chip_n8": measured_opt_state_bytes(opt0),
            "opt_state_bytes_per_chip": {
                str(n): opt_state_bytes(
                    d, slots=1, update_sharded=label != "replicated",
                    n_shards=n,
                )
                for n in chips
            },
            "wire_bytes_per_device": {
                str(n): round(w8 * ((n - 1) / n) / ((N - 1) / N), 1)
                for n in chips
            },
        }

    # the default round (sharded_update="auto") resolves to the sharded
    # f32 program on this mesh — its already-measured variant carries the
    # top-level projection keys (no fifth compile)
    default = variants["sharded_f32"]
    wire8 = float(default["hlo_wire_bytes_per_device_n8"])

    def wire_fn(n: int) -> float:
        return wire8 * ((n - 1) / n) / ((N - 1) / N)

    out = {
        "config": "PS MNIST MLP (784-128-10) + trimmed-mean + sign-flip, "
                  f"n_nodes=n_chips, batch {BATCH}/node",
        "params": int(d),
        "hlo_wire_bytes_per_device_n8": wire8,
        "per_opcode_bytes_n8": dict(default["per_opcode_bytes_n8"]),
        "assumptions": "weak scaling (n_nodes grows with chips); "
                       "v5e ICI 45 GB/s/dir; no compute/comm overlap "
                       "(pessimistic); per-device collective bytes follow "
                       "the (g-1)/g law measured at n=8; default round = "
                       "feature-sharded weight update (auto), opt-state "
                       "HBM per chip falls 1/n",
        "wire_bytes_per_device": {str(n): round(wire_fn(n), 1) for n in chips},
        "comm_seconds_per_round": {
            str(n): wire_fn(n) / ici for n in chips
        },
        "opt_state_bytes_per_chip_n8": default["opt_state_bytes_per_chip_n8"],
        "update_shard_variants": variants,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
