"""Byzantine-robust LONG-CONTEXT LM training: ring attention + Multi-Krum.

The framework's two pillars in one loop (no reference equivalent — the
reference has no transformer/long-context code at all, SURVEY §5):

* **sequence parallelism**: the context is sharded over a mesh axis; each
  device holds an L/n block, K/V rotate over the ICI ring inside exact
  ring attention (`byzpy_tpu.parallel.ring_attention`), so per-device
  activation memory is O(L/n) and the context length scales with the mesh;
* **robust aggregation**: several nodes compute LM gradients on their own
  long sequences, a byzantine node flips its sign, Multi-Krum
  (`byzpy_tpu.ops.robust.multi_krum`) discards it.

Runs out of the box on the 8-virtual-device CPU mesh (set by default when
no TPU mesh is available); on a TPU slice the same code rides the ICI.

    python examples/long_context_lm.py          # 6 nodes, 1 byzantine
    N_NODES=8 N_BYZ=2 ROUNDS=30 python examples/long_context_lm.py
    # the other sequence-parallel scheme, and sparse FFNs:
    ATTENTION=ulysses python examples/long_context_lm.py
    MLP=moe python examples/long_context_lm.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from byzpy_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

if __name__ == "__main__":
    # virtual 8-device CPU mesh when this host has fewer than 8 devices
    # (set BYZPY_TPU_PLATFORM=cpu to skip probing an accelerator at all)
    import jax

    import jax.extend.backend as _backend

    if os.environ.get("BYZPY_TPU_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BYZPY_TPU_PLATFORM"])
    if len(jax.devices()) < 8:
        jax.config.update("jax_platforms", "cpu")
        _backend.clear_backends()
        jax.config.update("jax_num_cpu_devices", 8)
        _backend.clear_backends()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from byzpy_tpu.models.transformer import TransformerLM  # noqa: E402
from byzpy_tpu.ops import robust  # noqa: E402
from byzpy_tpu.parallel.collectives import sharded_fn  # noqa: E402
from byzpy_tpu.parallel.mesh import make_mesh  # noqa: E402
from byzpy_tpu.utils.trees import stack_gradients  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def main() -> None:
    n_nodes = int(os.environ.get("N_NODES", "6"))
    n_byz = int(os.environ.get("N_BYZ", "1"))
    rounds = int(os.environ.get("ROUNDS", "20"))
    L = int(os.environ.get("SEQ_LEN", "256"))  # long context, sharded /8
    # ATTENTION=ring|ulysses picks the sequence-parallel scheme; MLP=moe
    # swaps the block FFNs for routed mixtures (experts local per shard).
    # Invalid values would silently fall back to block-local attention
    # (no cross-shard mixing), so reject them loudly.
    attention = os.environ.get("ATTENTION", "ring")
    mlp = os.environ.get("MLP", "dense")
    if attention not in ("ring", "ulysses"):
        raise SystemExit(f"ATTENTION must be ring|ulysses (got {attention!r})")
    if mlp not in ("dense", "moe"):
        raise SystemExit(f"MLP must be dense|moe (got {mlp!r})")
    vocab, dim, depth, heads = 64, 64, 2, 8 if attention == "ulysses" else 4

    mesh = make_mesh([8], ("sp",))
    model = TransformerLM(
        vocab_size=vocab, dim=dim, depth=depth, num_heads=heads,
        max_len=L, attention=attention, ring_axis="sp",
        mlp=mlp, n_experts=4,
    )
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    flat0, unravel = stack_gradients([params])
    print(f"{attention} LM ({mlp} FFN) over L={L} (8 x {L // 8} per device), "
          f"{flat0.shape[1]} params, {n_nodes} honest + {n_byz} byzantine")

    # sequence-parallel loss: logits stay sequence-sharded; the per-block
    # cross-entropy reduces locally and psums over the ring
    def sp_loss(p, tokens):
        def block_loss(toks):
            logits = model.apply(p, toks[:, :-1])
            tgt = toks[:, 1:]
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            return jax.lax.pmean(ce.mean(), "sp")

        fn = sharded_fn(mesh, "sp", block_loss, in_spec=P(None, "sp"),
                        out_spec=P())
        return fn(tokens)

    grad_fn = jax.jit(jax.grad(sp_loss))
    loss_fn = jax.jit(sp_loss)

    # synthetic long-sequence corpus: each node learns the same repeating
    # pattern (so the robust mean is meaningful), different phases
    def batch_for(node: int, rnd: int) -> jnp.ndarray:
        base = (np.arange(L + 2) + node * 7 + rnd * 3) % vocab
        return jnp.asarray(
            np.stack([base[i : i + L] for i in range(2)]), jnp.int32
        )

    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    f = n_byz

    for rnd in range(rounds):
        grads = []
        for node in range(n_nodes):
            g = grad_fn(params, batch_for(node, rnd))
            grads.append(g)
        flat, unravel = stack_gradients(grads)
        byz_rows = -4.0 * flat[:n_byz]  # sign-flip attackers
        stacked = jnp.concatenate([flat, byz_rows], axis=0)
        agg = robust.multi_krum(stacked, f=f, q=max(1, n_nodes - f))
        update_tree = unravel(agg)
        updates, opt_state = opt.update(update_tree, opt_state, params)
        params = optax.apply_updates(params, updates)
        if rnd % 5 == 0 or rnd == rounds - 1:
            val = float(loss_fn(params, batch_for(0, 0)))
            print(f"round {rnd:3d}  loss {val:.4f}")

    if rounds >= 10:
        assert val < 3.0, f"loss failed to decrease: {val}"
    print("long-context robust training OK")


if __name__ == "__main__":
    main()
