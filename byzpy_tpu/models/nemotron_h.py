"""Nemotron-H: the hybrid block of NVIDIA's Nemotron-3-Nano (``model_type:
nemotron_h``) as a chain of segments.

One mixer a block, by ``pattern``: ``M`` a Mamba-2 mixer (chunked
state-space scan), ``*`` grouped-query causal attention, ``E`` a mixture
of experts (sigmoid router, top-k, squared-ReLU experts, one shared
expert). ``x <- x + Mixer(RMSNorm(x))``, a final RMSNorm, an untied
head, next-token cross-entropy.

The bundle declares its segments (embedding, one a block, loss head), so
:func:`~byzpy_tpu.parallel.ps.build_ps_train_step` streams its round
segment by segment on one device; ``loss_fn`` is the chain of them for
everything else.

What a chip holds of a deployment is the configuration's to say: the
expert layer is told which of the ``n_routed_experts`` it holds
(:func:`~byzpy_tpu.parallel.moe.held_experts_ffn`: it routes over all of
them and computes its own experts' part, no token dropped), and the
vocabulary is the slice the token ids come from.

Not in the source's config and set here: no rotary or other positional
term in the attention layers (the Mamba-2 blocks carry position); the
router's correction bias is a buffer held at zero, so it is left out.
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
    causal_depthwise_conv,
    conv_silu,
    cross_entropy,
    rms_norm,
    token_embedding,
)

Array = jnp.ndarray


@dataclass(frozen=True)
class NemotronHConfig:
    """The published sizes of NVIDIA-Nemotron-3-Nano-30B-A3B (config.json),
    with the cut a chip holds: ``pattern`` (the blocks kept),
    ``held_experts`` (first, count) of ``n_routed_experts`` and
    ``vocab_size`` (the slice of the vocabulary)."""

    hidden_size: int = 2688
    pattern: str = "MEMEM*EME"
    vocab_size: int = 16384
    norm_eps: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    query_block: int = 512
    # mixture of experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    held_experts: Tuple[int, int] = (0, 8)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


# --------------------------------------------------------------------------
# Mamba-2
# --------------------------------------------------------------------------


def _segsum(a: Array) -> Array:
    """``out[..., i, j] = sum(a[..., j+1 : i+1])`` for ``i >= j``, ``-inf``
    above the diagonal, by a masked cumulative sum (no difference of two
    long sums)."""
    size = a.shape[-1]
    rows = jnp.arange(size)[:, None]
    cols = jnp.arange(size)[None, :]
    spread = jnp.where(rows > cols, a[..., :, None], 0.0)
    summed = jnp.cumsum(spread, axis=-2)
    return jnp.where(rows >= cols, summed, -jnp.inf)


def ssd_chunked(x: Array, dt: Array, a: Array, b: Array, c: Array, chunk: int) -> Array:
    """The Mamba-2 state-space recurrence of one sequence, in chunks.

    ``h_t = exp(dt_t a) h_{t-1} + dt_t b_t x_t^T`` and ``y_t = c_t h_t``
    for every head: ``x (T, H, P)``, ``dt (T, H)`` positive, ``a (H,)``
    negative, ``b``, ``c`` ``(T, G, N)`` with ``H / G`` heads a group.
    Inside a chunk of ``chunk`` positions the quadratic form (a masked
    decay matrix times ``c b^T``); across chunks the state each chunk
    leaves, carried by the chunks' total decays. Any ``T``: the tail is
    padded with ``dt = 0``, which neither decays nor adds. Differentiated
    by JAX as it stands. Returns ``(T, H, P)`` float32."""
    with jax.named_scope("model.ssm_scan"):
        t, heads, p = x.shape
        groups, n = b.shape[1:]
        per = heads // groups
        pad = -t % chunk
        if pad:
            x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                           for v in (x, dt, b, c))
        nc = (t + pad) // chunk
        x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
        xd = (x * dt[..., None]).reshape(nc, chunk, groups, per, p)
        b = b.reshape(nc, chunk, groups, n)
        c = c.reshape(nc, chunk, groups, n)
        da = (dt * a.astype(jnp.float32)).reshape(nc, chunk, heads)
        cum = jnp.cumsum(da, axis=1)  # (nc, L, H): decay from the chunk's start
        # inside a chunk: y[l] += sum_{s <= l} exp(cum[l] - cum[s]) (c_l . b_s) xd[s]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        cb = jnp.einsum("clgn,csgn->clsg", c, b)
        weights = cb[..., None] * decay.reshape(nc, chunk, chunk, groups, per)
        y = jnp.einsum("clsgr,csgrp->clgrp", weights, xd)
        # the state a chunk leaves, had it started from zero
        to_end = jnp.exp(cum[:, -1:, :] - cum).reshape(nc, chunk, groups, per)
        left = jnp.einsum("clgn,clgr,clgrp->cgrpn", b, to_end, xd)
        # the state a chunk starts from: every earlier chunk's, decayed by
        # the chunks between
        total = jnp.pad(cum[:, -1, :], ((1, 0), (0, 0)))  # (nc + 1, H)
        between = jnp.exp(_segsum(total.T))[:, :-1, 1:]  # (H, to chunk, from chunk)
        between = jnp.where(
            jnp.arange(nc)[:, None] > jnp.arange(nc)[None, :], between, 0.0
        ).reshape(groups, per, nc, nc)
        entering = jnp.einsum("grzc,cgrpn->zgrpn", between, left)
        from_start = jnp.exp(cum).reshape(nc, chunk, groups, per)
        y = y + jnp.einsum("clgn,cgrpn,clgr->clgrp", c, entering, from_start)
        return y.reshape(nc * chunk, heads, p)[:t]


def mamba2_mixer(p: Dict[str, Array], x: Array, cfg: NemotronHConfig) -> Array:
    """One sequence ``(T, hidden)`` through a Mamba-2 mixer."""
    t = x.shape[0]
    heads, hd, groups, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                            cfg.ssm_state_size)
    # model.ssm_proj: the four products; model.ssm_gate: what of the mixer is
    # neither a product nor the scan (which names itself inside it)
    with jax.named_scope("model.ssm_proj"):
        z = x @ p["w_z"].astype(x.dtype)
        xbc = x @ p["w_xbc"].astype(x.dtype)
        dt = (x @ p["w_dt"].astype(x.dtype)).astype(jnp.float32)
    with jax.named_scope("model.ssm_gate"):
        xs, b, c = conv_silu(xbc, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype),
                             (cfg.d_inner, cfg.d_inner + groups * n))
        xs, b, c = xs.reshape(t, heads, hd), b.reshape(t, groups, n), c.reshape(t, groups, n)
        delta = jax.nn.softplus(dt + p["dt_bias"])
        a = -jnp.exp(p["a_log"])
        y = ssd_chunked(xs, delta, a, b, c, cfg.chunk_size)
        y = y + p["d_skip"][:, None] * xs.astype(jnp.float32)
        y = (y.reshape(t, cfg.d_inner) * jax.nn.silu(z.astype(jnp.float32)))
        # RMSNorm over each of the n_groups groups of channels
        y = rms_norm(y.reshape(t, groups, -1), jnp.ones((), jnp.float32), cfg.norm_eps)
        y = (y.reshape(t, cfg.d_inner) * p["gate_norm_scale"]).astype(x.dtype)
    with jax.named_scope("model.ssm_proj"):
        return y @ p["w_out"].astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def gqa_attention(p: Dict[str, Array], x: Array, cfg: NemotronHConfig) -> Array:
    """Causal softmax attention of one sequence, ``num_attention_heads``
    query heads sharing ``num_key_value_heads`` key/value heads, no bias,
    no positional term. Where the block-causal kernels serve the call
    (:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`: a
    TPU, ``head_dim`` in whole lanes) no score leaves the chip's VMEM and
    no key above the diagonal is scored. Elsewhere queries go
    ``query_block`` at a time (each block rematerialised in the backward
    pass), so the score matrix alive at once is ``(heads, query_block, T)``."""
    with jax.named_scope("model.attention"):
        t = x.shape[0]
        heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        if causal_attention_serves(x, hd):
            out = causal_attention(
                attention_proj(x, p["w_q"]), attention_proj(x, p["w_k"]),
                attention_proj(x, p["w_v"]), kv_heads=kv)
            return attention_proj(out, p["w_o"])
        per = heads // kv
        q = attention_proj(x, p["w_q"]).reshape(t, kv, per, hd)
        k = attention_proj(x, p["w_k"]).reshape(t, kv, hd)
        v = attention_proj(x, p["w_v"]).reshape(t, kv, hd)
        out = blocked_causal_attention(q, k, v, cfg.query_block)
        return attention_proj(out, p["w_o"])


# --------------------------------------------------------------------------
# the chain
# --------------------------------------------------------------------------


def _moe_mixer(p: Dict[str, Array], x: Array, cfg: NemotronHConfig):
    first, _ = cfg.held_experts
    return held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=first, n_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor,
    )


def _block(kind: str, cfg: NemotronHConfig, dtype: Any):
    """``(subtree, h (B, T, hidden)) -> h`` of one block; an expert block
    returns ``(h, aux)``."""

    def apply(p, h):
        h = h.astype(dtype)
        normed = rms_norm(h, p["norm_scale"], cfg.norm_eps)
        if kind == "M":
            return h + jax.vmap(lambda s: mamba2_mixer(p, s, cfg))(normed)
        if kind == "*":
            return h + jax.vmap(lambda s: gqa_attention(p, s, cfg))(normed)
        # the expert layer is token by token: sequences are laid end to end
        out, aux = _moe_mixer(p, normed.reshape(-1, normed.shape[-1]), cfg)
        return h + out.reshape(h.shape), aux

    return apply


def _head(cfg: NemotronHConfig, dtype: Any):
    def apply(p, h, targets):
        with jax.named_scope("model.head"):
            h = rms_norm(h.astype(dtype), p["norm_scale"], cfg.norm_eps)
            return jnp.mean(cross_entropy(h @ p["w_head"].astype(dtype), targets))

    return apply


def segment_keys(cfg: NemotronHConfig) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_M`` ..., ``segNN_head``: sorted, they are in
    the chain's order."""
    kinds = {"M": "mamba", "*": "attn", "E": "moe"}
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{kinds[kind]}" for i, kind in enumerate(cfg.pattern)]
    return tuple(names + [f"seg{len(cfg.pattern) + 1:02d}_head"])


def init_params(cfg: NemotronHConfig, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the embedding's input is
    one-hot: fan_in 1); the Mamba-2 vectors in
    their published ranges (``A`` in [1, 16], ``dt`` log-uniform from
    ``time_step_min`` to ``time_step_max`` through the inverse softplus,
    ``D`` 1, the convolution's weights and bias uniform in +-1/sqrt(kernel));
    scales 1."""
    hidden, f32 = cfg.hidden_size, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16 * (len(cfg.pattern) + 2)))

    def matrix(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in)

    def block(kind):
        p = {"norm_scale": jnp.ones((hidden,), f32)}
        if kind == "M":
            heads = cfg.mamba_num_heads
            dt = jnp.exp(jax.random.uniform(
                next(keys), (heads,), f32, math.log(cfg.time_step_min),
                math.log(cfg.time_step_max)))
            dt = jnp.maximum(dt, cfg.time_step_floor)
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            p.update(
                w_z=matrix(hidden, cfg.d_inner), w_xbc=matrix(hidden, cfg.d_xbc),
                w_dt=matrix(hidden, heads),
                conv_w=jax.random.uniform(next(keys), (cfg.conv_kernel, cfg.d_xbc), f32,
                                          -bound, bound),
                conv_b=jax.random.uniform(next(keys), (cfg.d_xbc,), f32, -bound, bound),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.log(jax.random.uniform(next(keys), (heads,), f32, 1.0, 16.0)),
                d_skip=jnp.ones((heads,), f32),
                gate_norm_scale=jnp.ones((cfg.d_inner,), f32),
                w_out=matrix(cfg.d_inner, hidden),
            )
        elif kind == "*":
            q = cfg.num_attention_heads * cfg.head_dim
            kv = cfg.num_key_value_heads * cfg.head_dim
            p.update(w_q=matrix(hidden, q), w_k=matrix(hidden, kv), w_v=matrix(hidden, kv),
                     w_o=matrix(q, hidden))
        else:
            held, width = cfg.held_experts[1], cfg.moe_intermediate_size
            shared = cfg.moe_shared_expert_intermediate_size
            p.update(router=matrix(hidden, cfg.n_routed_experts),
                     experts_up=matrix(held, hidden, width),
                     experts_down=matrix(held, width, hidden),
                     shared_up=matrix(hidden, shared), shared_down=matrix(shared, hidden))
        return p

    keys_ = segment_keys(cfg)
    params = {keys_[0]: {"embedding": matrix(cfg.vocab_size, hidden, fan_in=1)}}
    for key, kind in zip(keys_[1:-1], cfg.pattern):
        params[key] = block(kind)
    params[keys_[-1]] = {"norm_scale": jnp.ones((hidden,), f32),
                         "w_head": matrix(hidden, cfg.vocab_size)}
    return params


def nemotron_h_bundle(cfg: NemotronHConfig, seed: int = 0, dtype: Any = jnp.float32
                      ) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    keys = segment_keys(cfg)
    segments = [Segment(keys[0], token_embedding(dtype))]
    for key, kind in zip(keys[1:-1], cfg.pattern):
        segments.append(Segment(key, _block(kind, cfg, dtype), aux=kind == "E"))
    segments.append(Segment(keys[-1], _head(cfg, dtype)))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def nemotron3_nano_ep16(seed: int = 0, dtype: Any = jnp.float32, **overrides: Any
                        ) -> ModelBundle:
    """What one chip of sixteen holds of a period of Nemotron-3-Nano: the
    first nine blocks, experts 0-7 of 128, 16,384 of 131,072 vocabulary
    rows, every head, every width as published (d = 667M)."""
    return nemotron_h_bundle(replace(NemotronHConfig(), **overrides), seed, dtype)


__all__ = [
    "NemotronHConfig",
    "causal_depthwise_conv",
    "conv_silu",
    "gqa_attention",
    "init_params",
    "mamba2_mixer",
    "nemotron3_nano_ep16",
    "nemotron_h_bundle",
    "rms_norm",
    "segment_keys",
    "ssd_chunked",
]
