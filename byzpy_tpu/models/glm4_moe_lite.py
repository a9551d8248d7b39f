"""GLM-4.7-Flash (``model_type: glm4_moe_lite``) on the training path, as a
chain of segments.

Every block has two residual branches: ``h <- h + MLA(RMSNorm(h))``, then
``h <- h + FFN(RMSNorm(h))``. MLA is multi-head latent attention
(DeepSeek-V2/V3): queries and keys/values go through a low-rank latent
with an RMSNorm on each, and a head's query and key are a part without
position (``qk_nope_head_dim``) beside a rotary part (``qk_rope_head_dim``)
whose key is ONE vector shared by all heads. It is trained in this
uncompressed form (weight absorption is inference's). The first
``first_k_dense_replace`` blocks' FFN is a SiLU-gated MLP; the others' a
mixture of gated experts (sigmoid router, top-k, normalised and scaled,
one shared expert), of which this chip holds a share
(:func:`~byzpy_tpu.parallel.moe.held_experts_ffn`). A final RMSNorm, an
untied head, next-token cross-entropy.

The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437, section
2.2) predicts the token after next: ``u_t = W_eh [RMSNorm(h_t) ;
RMSNorm(e[x_{t+1}])]``, one more expert block with weights of its own, its
own RMSNorm, then the SAME head. It reads the embedded tokens and the head
a second time, and owns neither: the chain carries the embedded tokens
beside the stream (a boundary is a tree, :class:`~byzpy_tpu.models.bundle.
Segment`), and the head link applies its one head to both streams. So the
embedding's and the head's gradients hold both paths, and every parameter
still belongs to one segment. Loss = CE(next) + ``mtp_loss_weight`` x
CE(after next), each a mean over its positions.

Set here and not in the source's config: the rotary pairs are (i, i + half)
of the rotary part; the router's correction bias is a buffer held at zero,
so it is left out; ``eh_proj`` reads ``[h ; e]`` in that order. The
source's one ``kv_a_proj_with_mqa`` of ``kv_lora_rank + qk_rope_head_dim``
outputs is kept as its two column blocks, ``w_kva`` (the latent) and
``w_kr`` (the shared rotary key), each whole TPU tiles: the same numbers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.moe import held_experts_ffn
from .bundle import ModelBundle, Segment
from .layers import YarnScaling, cross_entropy, mla_attention, rms_norm, rotary, token_embedding

Array = jnp.ndarray


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """The published sizes of GLM-4.7-Flash (config.json), with the cut a
    chip holds: ``num_hidden_layers`` (the blocks kept, the first
    ``first_k_dense_replace`` of them dense), ``held_experts`` (first,
    count) of ``n_routed_experts`` and ``vocab_size`` (the slice of the
    vocabulary)."""

    hidden_size: int = 2048
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    vocab_size: int = 19360
    rms_norm_eps: float = 1e-5
    # multi-head latent attention
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rope_scaling: Optional[YarnScaling] = None  # the source's: null
    query_block: int = 512
    # feed-forward
    intermediate_size: int = 10240
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    held_experts: Tuple[int, int] = (0, 8)
    mtp_loss_weight: float = 0.3

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# --------------------------------------------------------------------------
# the chain
# --------------------------------------------------------------------------


def _gated_mlp(p: Dict[str, Array], x: Array) -> Array:
    cast = lambda name: p[name].astype(x.dtype)  # noqa: E731
    with jax.named_scope("model.mlp"):
        return (jax.nn.silu(x @ cast("w_gate")) * (x @ cast("w_up"))) @ cast("w_down")


def _expert_ffn(p: Dict[str, Array], x: Array, cfg: Glm4MoeLiteConfig):
    # an expert's round is held_experts_ffn's own: an eighth of the tokens
    return held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=cfg.held_experts[0], n_experts=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        w_gate=p["experts_gate"], shared_gate=p["shared_gate"])


def decoder_block(p: Dict[str, Array], h: Array, cfg: Glm4MoeLiteConfig, dense: bool):
    """``h (B, T, hidden)`` through one block; an expert block returns
    ``(h, aux)``."""
    h = h + jax.vmap(lambda s: mla_attention(p, s, cfg))(
        rms_norm(h, p["attn_norm_scale"], cfg.rms_norm_eps))
    normed = rms_norm(h, p["ffn_norm_scale"], cfg.rms_norm_eps)
    if dense:
        return h + _gated_mlp(p, normed)
    # the expert layer is token by token: sequences are laid end to end
    out, aux = _expert_ffn(p, normed.reshape(-1, normed.shape[-1]), cfg)
    return h + out.reshape(h.shape), aux


def _block(cfg: Glm4MoeLiteConfig, dtype: Any, dense: bool, first: bool):
    """A block as a link: the first is handed the embedded tokens alone and
    starts the pair (stream, embedded tokens) that the others hand on."""

    def apply(p, boundary):
        h, embedded = (boundary, boundary) if first else boundary
        out = decoder_block(p, h.astype(dtype), cfg, dense)
        return (out, embedded) if dense else ((out[0], embedded), out[1])

    return apply


def _mtp(cfg: Glm4MoeLiteConfig, dtype: Any):
    """``(stream, embedded tokens) -> (stream, the module's normed
    output)``: position t joins the stream at t with the embedding of token
    t + 1 (the embedded sequence moved up by one; the last position, which
    has no such token in the batch, reads the first's and is left out of
    the loss)."""

    def apply(p, boundary):
        with jax.named_scope("model.mtp"):
            h, embedded = boundary
            with jax.named_scope("model.mtp_join"):
                ahead = jnp.roll(embedded, -1, axis=1)
                joined = jnp.concatenate([
                    rms_norm(h.astype(dtype), p["h_norm_scale"], cfg.rms_norm_eps),
                    rms_norm(ahead.astype(dtype), p["e_norm_scale"], cfg.rms_norm_eps)], axis=-1)
                joined = joined @ p["w_eh"].astype(dtype)
            out, aux = decoder_block(p, joined, cfg, dense=False)
            return (h, rms_norm(out, p["head_norm_scale"], cfg.rms_norm_eps)), aux

    return apply


def _head(cfg: Glm4MoeLiteConfig, dtype: Any):
    """``targets[t]`` is token t + 1: the stream at t predicts it, the MTP
    stream at t predicts ``targets[t + 1]`` (positions 0 .. T - 2)."""

    def apply(p, boundary, targets):
        h, ahead = boundary
        with jax.named_scope("model.head"):
            w_head = p["w_head"].astype(dtype)
            h = rms_norm(h.astype(dtype), p["norm_scale"], cfg.rms_norm_eps)
            main = jnp.mean(cross_entropy(h @ w_head, targets))
            with jax.named_scope("model.mtp"):
                mtp = jnp.mean(cross_entropy(ahead[:, :-1] @ w_head, targets[:, 1:]))
            return main + cfg.mtp_loss_weight * mtp, {"main_loss": main, "mtp_loss": mtp}

    return apply


def segment_keys(cfg: Glm4MoeLiteConfig) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_dense`` / ``_moe`` ..., ``segNN_mtp``,
    ``segNN_head``: sorted, they are in the chain's order."""
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{'dense' if i < cfg.first_k_dense_replace else 'moe'}"
        for i in range(cfg.num_hidden_layers)]
    at = len(names)
    return tuple(names + [f"seg{at:02d}_mtp", f"seg{at + 1:02d}_head"])


def init_params(cfg: Glm4MoeLiteConfig, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the embedding's input is
    one-hot: fan_in 1); norm scales 1."""
    hidden, f32 = cfg.hidden_size, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 * (cfg.num_hidden_layers + 3)))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in or shape[-2])

    def ones(size):
        return jnp.ones((size,), f32)

    def block(dense):
        heads = cfg.num_attention_heads
        p = dict(
            attn_norm_scale=ones(hidden), ffn_norm_scale=ones(hidden),
            w_qa=matrix(hidden, cfg.q_lora_rank), q_norm_scale=ones(cfg.q_lora_rank),
            w_qb=matrix(cfg.q_lora_rank, heads * cfg.qk_head_dim),
            w_kva=matrix(hidden, cfg.kv_lora_rank), w_kr=matrix(hidden, cfg.qk_rope_head_dim),
            kv_norm_scale=ones(cfg.kv_lora_rank),
            w_kvb=matrix(cfg.kv_lora_rank, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            w_o=matrix(heads * cfg.v_head_dim, hidden))
        if dense:
            width = cfg.intermediate_size
            p.update(w_gate=matrix(hidden, width), w_up=matrix(hidden, width),
                     w_down=matrix(width, hidden))
        else:
            held, width = cfg.held_experts[1], cfg.moe_intermediate_size
            shared = cfg.n_shared_experts * width
            p.update(router=matrix(hidden, cfg.n_routed_experts),
                     experts_gate=matrix(held, hidden, width),
                     experts_up=matrix(held, hidden, width),
                     experts_down=matrix(held, width, hidden),
                     shared_gate=matrix(hidden, shared), shared_up=matrix(hidden, shared),
                     shared_down=matrix(shared, hidden))
        return p

    names = segment_keys(cfg)
    params = {names[0]: {"embedding": matrix(cfg.vocab_size, hidden, fan_in=1)}}
    for i, name in enumerate(names[1:-2]):
        params[name] = block(i < cfg.first_k_dense_replace)
    params[names[-2]] = dict(
        block(False), h_norm_scale=ones(hidden), e_norm_scale=ones(hidden),
        w_eh=matrix(2 * hidden, hidden), head_norm_scale=ones(hidden))
    params[names[-1]] = {"norm_scale": ones(hidden), "w_head": matrix(hidden, cfg.vocab_size)}
    return params


def glm4_moe_lite_bundle(cfg: Glm4MoeLiteConfig, seed: int = 0, dtype: Any = jnp.float32
                         ) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    if cfg.num_nextn_predict_layers != 1 or not 0 < cfg.first_k_dense_replace:
        raise ValueError("glm4_moe_lite: one MTP module and a leading dense block")
    names = segment_keys(cfg)
    segments = [Segment(names[0], token_embedding(dtype))]
    for i, name in enumerate(names[1:-2]):
        dense = i < cfg.first_k_dense_replace
        segments.append(Segment(name, _block(cfg, dtype, dense, first=i == 0), aux=not dense))
    segments.append(Segment(names[-2], _mtp(cfg, dtype), aux=True))
    segments.append(Segment(names[-1], _head(cfg, dtype), aux=True))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def glm47_flash_ep8(seed: int = 0, dtype: Any = jnp.float32, **overrides: Any) -> ModelBundle:
    """What one chip of eight holds of GLM-4.7-Flash's first pipeline
    stage: the dense block, four expert blocks and the MTP module, experts
    0-7 of 64, 19,360 of 154,880 vocabulary rows, every head, every width
    as published (d = 706.5M)."""
    return glm4_moe_lite_bundle(replace(Glm4MoeLiteConfig(), **overrides), seed, dtype)


__all__ = [
    "Glm4MoeLiteConfig",
    "decoder_block",
    "glm47_flash_ep8",
    "glm4_moe_lite_bundle",
    "init_params",
    "mla_attention",
    "rotary",
    "segment_keys",
]
