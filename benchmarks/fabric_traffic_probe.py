"""Per-fabric HLO collective accounting at an arbitrary mesh size.

``python benchmarks/fabric_traffic_probe.py <fabric> <n>`` compiles one
round of the named fabric over an ``n``-virtual-device CPU mesh and
prints ONE JSON object with the per-device collective bytes parsed from
the optimized HLO (:mod:`byzpy_tpu.parallel.comms`).

Fabrics:

* ``ps`` — fused SPMD parameter-server round (trimmed mean, d=100k
  linear model). Dominant wire terms: gradient-transpose all-to-all +
  update all-gather, both carrying the saturating ``(g-1)/g`` factor.
* ``gossip`` — ring gossip round (``ppermute`` neighbor exchange);
  per-device bytes are CONSTANT in n (each chip talks to 2k neighbors
  regardless of ring size).
* ``ring_attention`` — sequence-parallel LM grad step; K/V blocks
  rotate via ``ppermute`` inside a ``fori_loop``, so the law is
  per-iteration bytes ~ block size (∝ 1/n) times (n-1) trips.

``tests/test_scaling_model.py`` runs this at n ∈ {8, 16, 32} and pins
the measured inventories against those closed-form laws — the evidence
behind ``docs/comm_model.md``'s 8→128 extrapolation.

Run in a SUBPROCESS: the CPU platform + device count are pinned below
before any jax backend touch.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    fabric = sys.argv[1]
    n = int(sys.argv[2])
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    from byzpy_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.ops import robust
    from byzpy_tpu.parallel.comms import collective_traffic
    from byzpy_tpu.parallel.mesh import node_mesh

    assert len(jax.devices()) == n, jax.devices()
    mesh = node_mesh(n)
    key = jax.random.PRNGKey(0)
    d = 100_000

    w0 = jnp.zeros((d,), jnp.float32)
    bundle = ModelBundle(
        apply_fn=lambda params, x: x @ params,
        params=w0,
        loss_fn=lambda params, x, y: jnp.mean((x @ params - y) ** 2),
    )

    if fabric == "ps":
        from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

        f = max(1, n // 4)
        cfg = PSStepConfig(n_nodes=n, n_byzantine=0)
        step, opt0 = build_ps_train_step(
            bundle, lambda m: robust.trimmed_mean(m, f=f), cfg, mesh=mesh
        )
        xs = jnp.zeros((n, 4, d), jnp.float32)
        ys = jnp.zeros((n, 4), jnp.float32)
        traffic = collective_traffic(step, bundle.params, opt0, xs, ys, key)
        extra = {"d": d, "dtype_bytes": 4}
    elif fabric == "gossip":
        from byzpy_tpu.parallel.gossip import (
            GossipStepConfig,
            build_ring_gossip_train_step,
        )

        cfg = GossipStepConfig(n_nodes=n, n_byzantine=0)
        gstep, ginit = build_ring_gossip_train_step(
            bundle, robust.coordinate_median, cfg, mesh, k=1
        )
        gx = jnp.zeros((n, 4, d), jnp.float32)
        gy = jnp.zeros((n, 4), jnp.float32)
        traffic = collective_traffic(gstep, ginit(), gx, gy, key)
        extra = {"d": d, "dtype_bytes": 4, "k": 1}
    elif fabric == "ring_attention":
        import optax
        from jax.sharding import PartitionSpec as P

        from byzpy_tpu.models.transformer import TransformerLM
        from byzpy_tpu.parallel.collectives import sharded_fn

        L, vocab, dim, heads = 8 * n, 16, 16, 2
        lm = TransformerLM(
            vocab_size=vocab, dim=dim, depth=1, num_heads=heads, max_len=L,
            attention="ring", ring_axis="nodes",
        )
        params = lm.init(jax.random.PRNGKey(2), jnp.zeros((1, 4), jnp.int32))
        tokens = jax.random.randint(jax.random.PRNGKey(3), (2, L), 0, vocab)

        def sp_loss(p, toks):
            def block_loss(tk):
                logits = lm.apply(p, tk[:, :-1])
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tk[:, 1:]
                )
                return jax.lax.pmean(ce.mean(), "nodes")

            return sharded_fn(
                mesh, "nodes", block_loss, in_spec=P(None, "nodes"),
                out_spec=P(),
            )(toks)

        grad_fn = jax.jit(jax.value_and_grad(sp_loss))
        traffic = collective_traffic(grad_fn, params, tokens)
        extra = {
            "seq_len": L, "dim": dim, "heads": heads, "batch": 2,
            "ring_trips": n - 1,
        }
    else:
        raise SystemExit(f"unknown fabric {fabric!r}")

    print(json.dumps({
        "fabric": fabric,
        "n": n,
        "wire_bytes_per_device": traffic["wire_bytes_per_device"],
        "loop_body_bytes_per_iteration": traffic[
            "loop_body_bytes_per_iteration"
        ],
        "per_opcode_bytes": {
            k: int(v) for k, v in traffic["per_opcode_bytes"].items()
        },
        **extra,
    }))


if __name__ == "__main__":
    main()
