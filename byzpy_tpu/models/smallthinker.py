"""SmallThinker-21BA3B-Instruct (``model_name: smallthinker_21b_instruct``)
on the training path, as a chain of segments: blocks of grouped-query
attention that are windowed and turned by position, or global with no
positional term at all, by two published layouts; a router that reads the
block's normed input BEFORE attention; ReLU-gated experts, six of 64 a
token, no shared expert, no dense layer; an untied head.

One sequence ``x (T, hidden)``; ``rms(x; w) = x / sqrt(mean(x^2) + eps) *
w``, ``eps = rms_norm_eps``. Block ``l``, with ``W = sliding_window_size``:

* ``u = rms(x; g1)``.
* Router: ``r = softmax(u W_r)`` over all ``moe_num_primary_experts``
  (``moe_primary_router_apply_softmax``), the
  ``moe_num_active_primary_experts`` largest a token, their ``r`` over their
  sum (``norm_topk_prob``). It reads ``u``: what it picks is known before
  attention has run, and does not see attention's result.
* Attention: ``q = u W_q`` (heads x head_dim), ``k = u W_k``, ``v = u W_v``
  (key/value heads x head_dim), seven query heads a key/value head. Where
  ``rope_layout[l]`` is 1, ``q`` and ``k`` are turned by position over all
  ``head_dim`` dimensions, pairs ``(i, i + head_dim / 2)``, ``rope_theta``,
  no scaling; where it is 0 they are not (no positional term: the causal
  mask alone orders the sequence). Scores ``q_i k_j / sqrt(head_dim)``;
  where ``sliding_window_layout[l]`` is 1 query ``i`` reads the keys ``j``
  with ``0 <= i - j < W`` (its own position counted), where it is 0 every
  ``j <= i``. Both published layouts read ``0 1 1 1`` thirteen times: a
  global block without positions, then three windowed blocks with them.
  ``h = x + softmax_j(a) v W_o``. No bias, no query / key norm.
* Experts: ``m = rms(h; g2)``; ``y = h + sum_{e in top} r_e W_down,e
  (relu(W_gate,e m) * (W_up,e m))``.
* After the last block ``rms(x; w_final)``, logits ``h W_head`` (untied),
  cross-entropy on the next token.

Attention goes through the block-causal kernels where they serve
(:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`; a windowed
block through their ``window``: the ``window_attention_*`` kernels, which
leave out the block pairs wholly older than the window),
:func:`~byzpy_tpu.models.layers.blocked_causal_attention` with the same
``window`` elsewhere; the expert layer is :func:`~byzpy_tpu.parallel.moe.
held_experts_ffn` with the router's tensor and the gate's activation handed
over. Set here and not in the source's config: the router reads the NORMED
block input (the source's summary says "router placed before attention";
whether its code norms first is in no key); the window counts the query's
own position; the softmax over all experts then the top six renormalised
(equal to a softmax over the six chosen logits); no query / key norm and no
bias; rotary pairs ``(i, i + 64)``; the "secondary experts" of the family's
description stand under no key and are LEFT OUT.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_attention import causal_attention, causal_attention_serves
from ..parallel.moe import held_experts_ffn
from .bundle import ModelBundle, Segment
from .layers import (
    attention_proj,
    blocked_causal_attention,
    cross_entropy,
    rms_norm,
    rotary,
    token_embedding,
)

Array = jnp.ndarray

_PERIOD = (0, 1, 1, 1)


@dataclass(frozen=True)
class SmallThinkerConfig:
    """The published sizes of SmallThinker-21BA3B-Instruct (config.json)
    with the cut a chip holds: the two layouts (one entry a block KEPT, in
    the chain's order: published blocks 0-7, two periods), ``held_experts``
    (first, count) of ``moe_num_primary_experts`` and ``vocab_size`` (the
    slice of the vocabulary)."""

    hidden_size: int = 2560
    vocab_size: int = 18992
    rms_norm_eps: float = 1e-6
    # attention
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window_layout: Tuple[int, ...] = 2 * _PERIOD
    sliding_window_size: int = 4096
    rope_layout: Tuple[int, ...] = 2 * _PERIOD
    rope_theta: float = 1.5e6
    query_block: int = 512
    # experts
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    held_experts: Tuple[int, int] = (0, 8)

    @property
    def num_hidden_layers(self) -> int:
        return len(self.sliding_window_layout)

    def kind(self, block: int) -> Tuple[bool, bool]:
        """``(windowed, turned)`` of a block kept."""
        return bool(self.sliding_window_layout[block]), bool(self.rope_layout[block])


def attention(p: Dict[str, Array], u: Array, cfg: SmallThinkerConfig,
              kind: Tuple[bool, bool]) -> Array:
    """Softmax attention of one normed sequence ``u (T, hidden)``, of the
    block's ``kind = (windowed, turned)``: inside the window with rotary
    positions, or over the whole causal prefix with none. The core is the
    block-causal kernels where they serve, :func:`~byzpy_tpu.models.layers.
    blocked_causal_attention` elsewhere, each told the window."""
    with jax.named_scope("model.attention"):
        windowed, turned = kind
        t = u.shape[0]
        heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        window = cfg.sliding_window_size if windowed else None
        q = attention_proj(u, p["w_q"]).reshape(t, heads, hd)
        k = attention_proj(u, p["w_k"]).reshape(t, kv, hd)
        v = attention_proj(u, p["w_v"])
        if turned:
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        if causal_attention_serves(u, hd):
            out = causal_attention(q.reshape(t, heads * hd), k.reshape(t, kv * hd), v,
                                   kv_heads=kv, window=window)
        else:
            out = blocked_causal_attention(q.reshape(t, kv, heads // kv, hd), k,
                                           v.reshape(t, kv, hd), cfg.query_block, window)
        return attention_proj(out, p["w_o"])


def decoder_block(p: Dict[str, Array], h: Array, cfg: SmallThinkerConfig,
                  kind: Tuple[bool, bool]):
    """``h (B, T, hidden)`` through one block of ``kind``; returns ``(h,
    aux)``. The router's tensor is the attention's, the normed input: its
    picks hang on nothing attention computes (the expert layer's call stands
    after attention here because the experts read attention's result; the
    router's ops, under ``model.moe_route``, depend on ``u`` alone)."""
    u = rms_norm(h, p["attention_norm_scale"], cfg.rms_norm_eps)
    h = h + jax.vmap(lambda s: attention(p, s, cfg, kind))(u)
    m = rms_norm(h, p["ffn_norm_scale"], cfg.rms_norm_eps)
    # the expert layer is token by token: sequences are laid end to end; an
    # expert's round is held_experts_ffn's own, an eighth of the tokens
    out, aux = held_experts_ffn(
        m.reshape(-1, m.shape[-1]), p["router"], p["experts_up"], p["experts_down"],
        first_held=cfg.held_experts[0], n_experts=cfg.moe_num_primary_experts,
        top_k=cfg.moe_num_active_primary_experts, w_gate=p["experts_gate"],
        score=jax.nn.softmax, router_input=u.reshape(-1, u.shape[-1]),
        activation=jax.nn.relu)
    return h + out.reshape(h.shape), aux


def _block(cfg: SmallThinkerConfig, dtype: Any, kind: Tuple[bool, bool]):
    def apply(p, h):
        return decoder_block(p, h.astype(dtype), cfg, kind)

    return apply


def _head(cfg: SmallThinkerConfig, dtype: Any):
    def apply(p, h, targets):
        with jax.named_scope("model.head"):
            h = rms_norm(h.astype(dtype), p["norm_scale"], cfg.rms_norm_eps)
            return jnp.mean(cross_entropy(h @ p["w_head"].astype(dtype), targets))

    return apply


def segment_keys(cfg: SmallThinkerConfig) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_global`` / ``segNN_window`` ...,
    ``segNN_head``: sorted, they are in the chain's order."""
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{'window' if cfg.kind(i)[0] else 'global'}"
        for i in range(cfg.num_hidden_layers)]
    return tuple(names + [f"seg{len(names):02d}_head"])


def init_params(cfg: SmallThinkerConfig, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the embedding's input is
    one-hot: fan_in 1), norm scales 1."""
    hidden, f32 = cfg.hidden_size, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8 * (cfg.num_hidden_layers + 2)))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in or shape[-2])

    def ones(size):
        return jnp.ones((size,), f32)

    def block():
        q_width = cfg.num_attention_heads * cfg.head_dim
        kv_width = cfg.num_key_value_heads * cfg.head_dim
        held, width = cfg.held_experts[1], cfg.moe_ffn_hidden_size
        return dict(
            attention_norm_scale=ones(hidden), ffn_norm_scale=ones(hidden),
            w_q=matrix(hidden, q_width), w_k=matrix(hidden, kv_width),
            w_v=matrix(hidden, kv_width), w_o=matrix(q_width, hidden),
            router=matrix(hidden, cfg.moe_num_primary_experts),
            experts_gate=matrix(held, hidden, width), experts_up=matrix(held, hidden, width),
            experts_down=matrix(held, width, hidden))

    names = segment_keys(cfg)
    params = {names[0]: {"embedding": matrix(cfg.vocab_size, hidden, fan_in=1)}}
    for name in names[1:-1]:
        params[name] = block()
    params[names[-1]] = {"norm_scale": ones(hidden), "w_head": matrix(hidden, cfg.vocab_size)}
    return params


def smallthinker_bundle(cfg: SmallThinkerConfig, seed: int = 0,
                        dtype: Any = jnp.float32) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    layouts = (cfg.sliding_window_layout, cfg.rope_layout)
    if not layouts[0] or len(layouts[0]) != len(layouts[1]) or set(layouts[0] + layouts[1]) - {0, 1}:
        raise ValueError(f"smallthinker: two layouts of one entry (0 or 1) a block kept, at least "
                         f"one block, got {layouts}")
    names = segment_keys(cfg)
    segments = [Segment(names[0], token_embedding(dtype))]
    for i, name in enumerate(names[1:-1]):
        segments.append(Segment(name, _block(cfg, dtype, cfg.kind(i)), aux=True))
    segments.append(Segment(names[-1], _head(cfg, dtype)))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def smallthinker_21b_ep8(seed: int = 0, dtype: Any = jnp.float32,
                         **overrides: Any) -> ModelBundle:
    """What one chip of eight holds of SmallThinker-21BA3B-Instruct's first
    pipeline stage: blocks 0-7 of 52 (two periods ``global window window
    window``), experts 0-7 of 64, 18,992 of 151,936 rows of the embedding and
    of the head, every head, every width as published (d = 643.9M)."""
    for key in ("sliding_window_layout", "rope_layout", "held_experts"):  # JSON has no tuples
        if key in overrides:
            overrides[key] = tuple(overrides[key])
    return smallthinker_bundle(replace(SmallThinkerConfig(), **overrides), seed, dtype)


__all__ = [
    "SmallThinkerConfig",
    "attention",
    "decoder_block",
    "init_params",
    "segment_keys",
    "smallthinker_21b_ep8",
    "smallthinker_bundle",
]
