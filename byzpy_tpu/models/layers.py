"""What the language models of this package share: RMSNorm in its two
forms, rotary positions, the chain's embedding link, next-token
cross-entropy, the causal depthwise convolution of the state-space and
linear-attention mixers with its SiLU, and causal attention by blocks of
queries for a call the block-causal kernels do not serve
(:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`)."""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    with jax.named_scope("model.norm"):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rms_norm_one_plus(x: Array, weight: Array, eps: float) -> Array:
    """``x / rms(x) * (1 + weight)``: the form whose weight starts at zero
    (Qwen3-Next's norms, bar the gated one after its delta rule)."""
    with jax.named_scope("model.norm"):
        return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


def rotary(x: Array, theta: float) -> Array:
    """Rotary position embedding of ``x (T, ..., dim)``, position = index
    along the first axis: the pair (``x[..., i]``, ``x[..., i + dim / 2]``)
    turned by ``t * theta ** (-2 i / dim)``. Written as the 2 x 2 rotation
    of every pair (a product and a sum over an axis of two), with no slice
    of ``x``: a slice's cotangent is a zero-padded array, and the two
    halves' padded cotangents added up fed the weight-gradient product of
    the shared rotary key on the v5e's compiler in a form that lost it
    (PERF.md, PR 34)."""
    t, dim = x.shape[0], x.shape[-1]
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    # turn[t, out, in, i]: out = 0 reads (cos, -sin) of (a, b), out = 1 (sin, cos)
    turn = jnp.stack([jnp.stack([cos, -sin], axis=1), jnp.stack([sin, cos], axis=1)], axis=1)
    turn = turn.reshape(t, *(1,) * (x.ndim - 2), 2, 2, half).astype(x.dtype)
    pairs = x.reshape(*x.shape[:-1], 1, 2, half)
    return jnp.sum(turn * pairs, axis=-2).reshape(x.shape)


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


def _rows_moved(x: Array, by: int) -> Array:
    """``out[t] = x[t - by]`` along the first axis, zero where ``t - by``
    falls outside: one ``pad`` that adds ``by`` rows at one end and takes
    them off the other (no array longer than ``x``)."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype), ((by, -by, 0),) + ((0, 0, 0),) * (x.ndim - 1))


def causal_depthwise_conv(x: Array, w: Array, bias: Optional[Array] = None) -> Array:
    """``out[t] = bias + sum_j w[j] x[t - (K - 1) + j]``, zeros before the
    start; no ``bias``, no term."""
    k = w.shape[0]
    out = bias
    for j in range(k):
        tap = w[j] * _rows_moved(x, k - 1 - j)
        out = tap if out is None else out + tap
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv_silu(x: Array, w: Array, bias: Optional[Array], splits: Tuple[int, ...]
              ) -> Tuple[Array, ...]:
    """``silu(causal_depthwise_conv(x, w, bias))`` as its column blocks, cut
    at ``splits`` (handed out apart, each block is written once, in the
    layout its reader asks for; slices of one array are copied), with a
    backward of its own: the same ``K`` shifted multiply-adds run the
    other way. (Left to automatic differentiation each tap's transpose is
    a write into a fresh zero array of ``T + K - 1`` rows, the taps are
    added and the pad's transpose slices the sum.) Keeps ``x``, ``w`` and
    ``bias`` alone (``bias`` may be ``None``: a convolution without one)."""
    return tuple(jnp.split(jax.nn.silu(causal_depthwise_conv(x, w, bias)), splits, axis=1))


def _conv_silu_fwd(x, w, bias, splits):
    return conv_silu(x, w, bias, splits), (x, w, bias)


def _conv_silu_bwd(splits, kept, g):
    x, w, bias = kept
    k = w.shape[0]
    with jax.named_scope("model.ssm_gate"):
        g = jnp.concatenate(g, axis=1)
        pre = causal_depthwise_conv(x, w, bias)
        s = jax.nn.sigmoid(pre)
        gs = g * s * (1 + pre * (1 - s))  # through the SiLU
        # dx[t] = sum_j w[j] gs[t + (K - 1) - j], zero past the end
        dx = sum(w[j] * _rows_moved(gs, j + 1 - k) for j in range(k))
        dw = jnp.stack([jnp.sum(gs * _rows_moved(x, k - 1 - j), axis=0) for j in range(k)])
        return dx, dw, None if bias is None else jnp.sum(gs, axis=0)


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


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


__all__ = [
    "blocked_causal_attention",
    "causal_depthwise_conv",
    "conv_silu",
    "cross_entropy",
    "rms_norm",
    "rms_norm_one_plus",
    "rotary",
    "token_embedding",
]
