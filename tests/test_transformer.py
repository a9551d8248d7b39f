"""Transformer family: full-attention training + sequence-parallel ring
forward equivalence.

The critical property: a ring-attention model over a sequence-sharded mesh
produces the SAME logits as the identical parameters in full-attention
mode on one device — sequence parallelism is an execution detail, not a
model change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.transformer import (
    TransformerLM,
    sequence_parallel_forward,
    tiny_classifier,
    tiny_lm,
)
from byzpy_tpu.parallel.mesh import make_mesh


def test_lm_trains_on_repeating_pattern():
    bundle = tiny_lm(seed=0, vocab_size=16, dim=32, depth=1, num_heads=2)
    pattern = jnp.asarray([[1, 2, 3, 4] * 8], jnp.int32)  # (1, 32)
    tokens = jnp.tile(pattern, (8, 1))

    opt = optax.adam(1e-2)
    state = opt.init(bundle.params)
    params = bundle.params
    loss_grad = jax.jit(jax.value_and_grad(bundle.loss_fn))
    first = None
    for _ in range(30):
        loss, grads = loss_grad(params, tokens)
        if first is None:
            first = float(loss)
        updates, state = opt.update(grads, state)
        params = optax.apply_updates(params, updates)
    assert float(loss) < first * 0.2, (first, float(loss))


def test_classifier_shapes():
    bundle = tiny_classifier(seed=0, num_classes=5, dim=32, depth=1, num_heads=2)
    tokens = jnp.zeros((4, 12), jnp.int32)
    logits = bundle.apply_fn(bundle.params, tokens)
    assert logits.shape == (4, 5)


def test_ring_lm_matches_full_lm(devices):
    """Same params, ring over 8 sequence shards == full attention."""
    vocab, dim, depth, heads, L = 32, 32, 2, 4, 64
    full = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, attention="full")
    ring = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, attention="ring", ring_axis="sp")
    params = full.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0, vocab)
    oracle = full.apply(params, tokens)

    mesh = make_mesh([8], ("sp",))
    out = sequence_parallel_forward(mesh, ring.apply, params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)
    # logits stay sequence-sharded
    assert out.sharding.spec[1] == "sp"


def test_ring_lm_init_and_apply_outside_shard_map():
    """Ring models must initialize (and run) on a single device with no
    mesh bound: the ring axis degrades to position 0 / full attention,
    which is exactly one-block ring semantics."""
    vocab, dim, depth, heads = 32, 32, 1, 4
    ring = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, attention="ring", ring_axis="sp")
    full = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, attention="full")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, vocab)
    params = ring.init(jax.random.PRNGKey(0), tokens)  # used to NameError
    out_ring = ring.apply(params, tokens)
    out_full = full.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_full), rtol=1e-5, atol=1e-5
    )


def test_ulysses_lm_matches_full_lm(devices):
    """Same params, ulysses all-to-all over 8 sequence shards == full
    attention (heads == axis size, the divisibility contract)."""
    vocab, dim, depth, heads, L = 32, 32, 2, 8, 64
    full = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, attention="full")
    uly = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                        num_heads=heads, attention="ulysses", ring_axis="sp")
    params = full.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0, vocab)
    oracle = full.apply(params, tokens)

    mesh = make_mesh([8], ("sp",))
    out = sequence_parallel_forward(mesh, uly.apply, params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)
    assert out.sharding.spec[1] == "sp"


def test_moe_lm_trains_single_device():
    """mlp='moe' LM: routed FFN end to end — loss must fall on the same
    repeating-pattern task the dense LM learns."""
    import optax

    vocab, L = 16, 32
    lm = TransformerLM(vocab_size=vocab, dim=32, depth=1, num_heads=4,
                       max_len=L, mlp="moe", n_experts=4)
    tokens = jnp.tile(jnp.arange(8, dtype=jnp.int32), (4, L // 8))
    params = lm.init(jax.random.PRNGKey(0), tokens)

    def loss_fn(p):
        logits = lm.apply(p, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]
        ).mean()

    opt = optax.adam(1e-2)
    state = opt.init(params)
    l0 = float(loss_fn(params))

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s)
        return optax.apply_updates(p, u), s, l

    for _ in range(60):
        params, state, l = step(params, state)
    assert float(l) < l0 * 0.5, (l0, float(l))


def test_moe_lm_combines_with_ulysses_sequence_parallel(devices):
    """Scheme composition: ulysses attention over 'sp' + MoE FFN in the
    same blocks (experts local per shard), forward parity vs the same
    params applied without the mesh is NOT expected (routing sees local
    token blocks) — the contract is: it runs, stays finite, and grads
    flow. Exact MoE parity is pinned separately in test_moe.py."""
    vocab, dim, heads, L = 16, 16, 8, 64
    lm = TransformerLM(vocab_size=vocab, dim=dim, depth=1, num_heads=heads,
                       max_len=L, attention="ulysses", ring_axis="sp",
                       mlp="moe", n_experts=4)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0, vocab)
    params = lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    mesh = make_mesh([8], ("sp",))
    out = sequence_parallel_forward(mesh, lm.apply, params, tokens)
    arr = np.asarray(out)
    assert arr.shape == (2, L, vocab)
    assert np.isfinite(arr).all()
