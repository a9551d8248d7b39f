"""What the language models of this package share: RMSNorm, the chain's
embedding link, next-token cross-entropy, and causal attention by blocks
of queries for a call the block-causal kernels do not serve
(:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`)."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    with jax.named_scope("model.norm"):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def token_embedding(dtype: Any):
    """A chain's first link: ``(p, tokens) -> p["embedding"][tokens]`` in
    ``dtype``."""

    def apply(p, tokens):
        with jax.named_scope("model.embed"):
            return p["embedding"][tokens].astype(dtype)

    return apply


def cross_entropy(logits: Array, targets: Array) -> Array:
    """``-log softmax(logits)[targets]`` a position, in float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def blocked_causal_attention(q: Array, k: Array, v: Array, query_block: int) -> Array:
    """Causal softmax attention of one sequence, ``query_block`` queries at a
    time: ``q (T, kv, per, head_dim)`` (``per`` query heads read key/value
    head ``kv``), ``k (T, kv, head_dim)``, ``v (T, kv, head_dim)``; returns
    ``(T, kv * per * head_dim)``. Each block is rematerialised in the
    backward pass, so the score matrix alive at once is
    ``(heads, query_block, T)``."""
    t, kv, per, hd = q.shape
    block = min(query_block, t)
    pad = -t % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, block, kv, per, hd)
    starts = jnp.arange(q.shape[0]) * block

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k).astype(jnp.float32) / math.sqrt(hd)
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs.astype(q.dtype), v)

    return jax.lax.map(one_block, (q, starts)).reshape(-1, kv * per * hd)[:t]


__all__ = ["blocked_causal_attention", "cross_entropy", "rms_norm", "token_embedding"]
