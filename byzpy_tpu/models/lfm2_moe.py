"""LFM2-24B-A2B (``model_type: lfm2_moe``) on the training path, as a chain
of segments: gated short-convolution blocks three to one with grouped-query
attention of 64-wide heads, a sigmoid top-4-of-64 expert layer with NO
shared expert after two leading dense layers, and ONE table that embeds the
tokens and, transposed, makes the logits.

One sequence ``x (T, hidden)``; ``rms(x; w) = x / sqrt(mean(x^2) + eps) *
w``, ``eps = norm_eps``:

* Block ``l``: ``u = x + Op_l(rms(x; w_op))``, ``y = u + FF_l(rms(u;
  w_ffn))``. ``Op_l`` is the short convolution where ``layer_types[l]`` is
  ``conv`` and attention where it is ``full_attention`` (published: ``conv
  conv full_attention conv`` ten times); ``FF_l`` is the dense gated MLP
  for ``l < num_dense_layers`` and the expert layer after.
* Short convolution: ``[B | C | X] = z W_in`` (hidden -> 3 x hidden, no
  bias, the three column blocks in this order); ``g = B * X``; ``c[t] =
  w[0] g[t - 2] + w[1] g[t - 1] + w[2] g[t]`` (depthwise, causal, ``w
  (conv_L_cache, hidden)``, zeros before the start, no bias, NO
  activation); ``out = (C * c) W_out``.
* Attention: ``q = z W_q`` (heads x 64), ``k = z W_k``, ``v = z W_v``
  (key/value heads x 64); every head of ``q`` and ``k`` through an RMS norm
  over its 64 values with a learned weight of 64; the rotary turn over all
  64 dimensions, pairs ``(i, i + 32)``, ``rope_theta`` 1e6, no scaling;
  causal ``softmax(q k^T / 8) v``, four query heads a key/value head;
  ``W_o``. No bias, no output gate, no window.
* Dense MLP: ``W_2 (silu(W_1 z) * W_3 z)``. Expert layer: ``s = sigmoid(z
  W_r)`` over all experts; the ``num_experts_per_tok`` largest a token
  (of ``s + expert_bias``; the bias is a buffer no gradient reaches, held
  at zero and left out here); their ``s`` over (their sum + 1e-6), times
  ``routed_scaling_factor``; an expert is the same gated MLP at
  ``moe_intermediate_size``; no shared expert.
* After the last block ``rms(x; w_final)`` (the family's
  ``embedding_norm``), logits ``h E^T`` with ``E`` THE EMBEDDING TABLE
  ITSELF, cross-entropy on the next token.

The table is one leaf of the FIRST segment; the head names that segment in
``Segment.reads`` and is handed the table with its other arguments, so the
table has one place in one row, one aggregate and one update a step, and a
worker's gradient through the head is added to that row (``models/
bundle.py``; nothing here, and nothing in the round, knows the model). The
gated convolution is :func:`~byzpy_tpu.models.layers.gated_short_conv`
(a backward of its own); attention goes through the block-causal kernels
where they serve (:func:`~byzpy_tpu.ops.pallas_attention.
causal_attention_serves`: two 64-wide heads a lane tile, nothing padded),
:func:`~byzpy_tpu.models.layers.blocked_causal_attention` elsewhere; the
expert layer is :func:`~byzpy_tpu.parallel.moe.held_experts_ffn` as it
stands, with the ``1e-6`` handed over. Set here and not in the source's
config: the table is tied (the LFM2 family's ``tie_embedding``); ``B | C |
X`` are whole column blocks in this order; rotary pairs are ``(i, i +
32)``; the router's bias is zero.
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
from .glm4_moe_lite import _gated_mlp  # the same SiLU-gated MLP, under model.mlp
from .layers import (
    attention_proj,
    blocked_causal_attention,
    cross_entropy,
    gated_short_conv,
    rms_norm,
    rotary,
    token_embedding,
)

Array = jnp.ndarray

_PERIOD = ("full_attention", "conv", "conv", "conv")


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published sizes of LFM2-24B-A2B (config.json) with the cut a
    chip holds: ``layer_types`` (one entry a block kept, in the chain's
    order: published layer 0, then layers 2-9, two periods ``full conv conv
    conv``), ``num_dense_layers`` (the leading blocks whose feed-forward is
    the dense MLP), ``held_experts`` (first, count) of ``num_experts`` and
    ``vocab_size`` (the slice of the vocabulary)."""

    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = ("conv",) + 2 * _PERIOD
    num_dense_layers: int = 1
    vocab_size: int = 8192
    norm_eps: float = 1e-5
    # the short convolution
    conv_L_cache: int = 3
    # grouped-query attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    query_block: int = 512
    # feed-forward
    intermediate_size: int = 11776
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    router_denominator_eps: float = 1e-6
    held_experts: Tuple[int, int] = (0, 8)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


def short_conv_operator(p: Dict[str, Array], x: Array, cfg: Lfm2MoeConfig) -> Array:
    """The gated short convolution of one sequence ``(T, hidden)``: ``(C *
    conv(B * X)) W_out`` with ``[B | C | X] = x W_in``."""
    del cfg
    with jax.named_scope("model.short_conv_proj"):
        bcx = x @ p["w_in"].astype(x.dtype)
    with jax.named_scope("model.short_conv"):
        y = gated_short_conv(bcx, p["conv_w"].astype(x.dtype))
    with jax.named_scope("model.short_conv_proj"):
        return y @ p["w_out"].astype(x.dtype)


def gqa_attention(p: Dict[str, Array], x: Array, cfg: Lfm2MoeConfig) -> Array:
    """Causal softmax attention of one sequence ``(T, hidden)``:
    ``num_attention_heads`` query heads of ``head_dim`` share
    ``num_key_value_heads`` key/value heads; every query and key head is
    normed (a weight of ``head_dim``) and then turned by position over all
    its dimensions. The core is the block-causal kernels where they serve
    (:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`: on a
    TPU the 64-wide heads lie two to a lane tile, as the projections leave
    them), :func:`~byzpy_tpu.models.layers.blocked_causal_attention`
    elsewhere."""
    with jax.named_scope("model.attention"):
        t = x.shape[0]
        heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

        def placed(a, scale):  # (T, n, head_dim): normed, then turned
            return rotary(rms_norm(a, scale, cfg.norm_eps), cfg.rope_theta)

        q = placed(attention_proj(x, p["w_q"]).reshape(t, heads, hd), p["q_norm_scale"])
        k = placed(attention_proj(x, p["w_k"]).reshape(t, kv, hd), p["k_norm_scale"])
        v = attention_proj(x, p["w_v"])
        if causal_attention_serves(x, hd):
            out = causal_attention(q.reshape(t, heads * hd), k.reshape(t, kv * hd), v,
                                   kv_heads=kv)
        else:
            out = blocked_causal_attention(q.reshape(t, kv, heads // kv, hd), k,
                                           v.reshape(t, kv, hd), cfg.query_block)
        return attention_proj(out, p["w_o"])


def _expert_ffn(p: Dict[str, Array], x: Array, cfg: Lfm2MoeConfig):
    # an expert's round is held_experts_ffn's own: an eighth of the tokens
    return held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"],
        first_held=cfg.held_experts[0], n_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        w_gate=p["experts_gate"], denominator_eps=cfg.router_denominator_eps)


def decoder_block(p: Dict[str, Array], h: Array, cfg: Lfm2MoeConfig, kind: str, dense: bool):
    """``h (B, T, hidden)`` through one block whose operator is ``kind``
    (``conv`` or ``full_attention``); an expert block returns ``(h,
    aux)``."""
    operator = gqa_attention if kind == "full_attention" else short_conv_operator
    h = h + jax.vmap(lambda s: operator(p, s, cfg))(
        rms_norm(h, p["operator_norm_scale"], cfg.norm_eps))
    normed = rms_norm(h, p["ffn_norm_scale"], cfg.norm_eps)
    if dense:
        return h + _gated_mlp(p, normed)
    # the expert layer is token by token: sequences are laid end to end
    out, aux = _expert_ffn(p, normed.reshape(-1, normed.shape[-1]), cfg)
    return h + out.reshape(h.shape), aux


def _block(cfg: Lfm2MoeConfig, dtype: Any, kind: str, dense: bool):
    def apply(p, h):
        return decoder_block(p, h.astype(dtype), cfg, kind, dense)

    return apply


def _head(cfg: Lfm2MoeConfig, dtype: Any, table_of: str):
    """The loss head: it owns the final norm's weight and READS the table
    of segment ``table_of`` (``Segment.reads``)."""

    def apply(p, h, targets, read):
        with jax.named_scope("model.head"):
            h = rms_norm(h.astype(dtype), p["norm_scale"], cfg.norm_eps)
            table = read[table_of]["embedding"].astype(dtype)
            # h E^T: the table's second axis contracted, no transposed copy asked for
            logits = jax.lax.dot_general(h, table, (((h.ndim - 1,), (1,)), ((), ())))
            return jnp.mean(cross_entropy(logits, targets))

    return apply


def _kinds(cfg: Lfm2MoeConfig):
    """``(operator, dense?)`` of every block kept."""
    return [(kind, i < cfg.num_dense_layers) for i, kind in enumerate(cfg.layer_types)]


def segment_keys(cfg: Lfm2MoeConfig) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_conv_dense`` / ``segNN_attn_moe`` ...,
    ``segNN_head``: sorted, they are in the chain's order."""
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{'attn' if kind == 'full_attention' else 'conv'}_"
        f"{'dense' if dense else 'moe'}" for i, (kind, dense) in enumerate(_kinds(cfg))]
    return tuple(names + [f"seg{len(names):02d}_head"])


def init_params(cfg: Lfm2MoeConfig, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the convolution's taps:
    ``conv_L_cache``; the table: ``hidden``, the fan_in of the head it also
    is, so that the logits start at unit variance), norm scales 1. No head
    matrix: the table is the head's."""
    hidden, f32 = cfg.hidden_size, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12 * (cfg.num_hidden_layers + 2)))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in or shape[-2])

    def ones(size):
        return jnp.ones((size,), f32)

    def block(kind, dense):
        p = dict(operator_norm_scale=ones(hidden), ffn_norm_scale=ones(hidden))
        if kind == "full_attention":
            kv_width = cfg.num_key_value_heads * cfg.head_dim
            p.update(w_q=matrix(hidden, hidden), w_k=matrix(hidden, kv_width),
                     w_v=matrix(hidden, kv_width), w_o=matrix(hidden, hidden),
                     q_norm_scale=ones(cfg.head_dim), k_norm_scale=ones(cfg.head_dim))
        else:
            p.update(w_in=matrix(hidden, 3 * hidden), conv_w=matrix(cfg.conv_L_cache, hidden),
                     w_out=matrix(hidden, hidden))
        if dense:
            width = cfg.intermediate_size
            p.update(w_gate=matrix(hidden, width), w_up=matrix(hidden, width),
                     w_down=matrix(width, hidden))
        else:
            held, width = cfg.held_experts[1], cfg.moe_intermediate_size
            p.update(router=matrix(hidden, cfg.num_experts),
                     experts_gate=matrix(held, hidden, width),
                     experts_up=matrix(held, hidden, width),
                     experts_down=matrix(held, width, hidden))
        return p

    names = segment_keys(cfg)
    params = {names[0]: {"embedding": matrix(cfg.vocab_size, hidden, fan_in=hidden)}}
    for name, (kind, dense) in zip(names[1:-1], _kinds(cfg)):
        params[name] = block(kind, dense)
    params[names[-1]] = {"norm_scale": ones(hidden)}
    return params


def lfm2_moe_bundle(cfg: Lfm2MoeConfig, seed: int = 0, dtype: Any = jnp.float32) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    kinds = _kinds(cfg)
    if not kinds or set(cfg.layer_types) - {"conv", "full_attention"}:
        raise ValueError(f"lfm2_moe: at least one block, each 'conv' or 'full_attention', "
                         f"got {cfg.layer_types}")
    names = segment_keys(cfg)
    segments = [Segment(names[0], token_embedding(dtype))]
    for name, (kind, dense) in zip(names[1:-1], kinds):
        segments.append(Segment(name, _block(cfg, dtype, kind, dense), aux=not dense))
    segments.append(Segment(names[-1], _head(cfg, dtype, names[0]), reads=(names[0],)))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def lfm2_24b_ep8(seed: int = 0, dtype: Any = jnp.float32, **overrides: Any) -> ModelBundle:
    """What one chip of eight holds of LFM2-24B-A2B's first pipeline stage:
    layer 0 (short convolution, dense MLP) and layers 2-9 (two periods
    ``full conv conv conv``, an expert layer in each), experts 0-7 of 64,
    8,192 of 65,536 rows of the tied table, every head and every
    convolution channel, every width as published (d = 832.7M)."""
    if "layer_types" in overrides:  # JSON has no tuples
        overrides["layer_types"] = tuple(overrides["layer_types"])
    return lfm2_moe_bundle(replace(Lfm2MoeConfig(), **overrides), seed, dtype)


__all__ = [
    "Lfm2MoeConfig",
    "decoder_block",
    "gqa_attention",
    "init_params",
    "lfm2_24b_ep8",
    "lfm2_moe_bundle",
    "segment_keys",
    "short_conv_operator",
]
