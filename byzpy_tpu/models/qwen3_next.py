"""Qwen3-Next (``model_type: qwen3_next``) on the training path, as a chain
of segments.

Every block has two residual branches: ``h <- h + mixer(norm(h))``, then
``h <- h + experts(norm(h))``. The mixer is causal softmax attention in
every ``full_attention_interval``-th block and Gated DeltaNet, a linear
attention whose state is updated by a gated delta rule, in the others:
one period is ``L L L F``. Every norm bar one multiplies by ``1 + w``
(:func:`~byzpy_tpu.models.layers.rms_norm_one_plus`).

**Gated DeltaNet** (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464). Of
one sequence, per value head (a key head's q and k serve ``value heads /
key heads`` of them): q and k leave a causal depthwise convolution and a
SiLU, are L2-normalised, q divided by ``sqrt(key size)``; ``beta_t =
sigmoid(b_t)``, ``g_t = -exp(A_log) softplus(a_t + dt_bias)``; from ``S =
0 (key size x value size)``::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;  o_t = S^T q_t

then a norm over each head's outputs times ``w silu(z_t)`` and the output
projection. The state is multiplied by ``exp(g_t) (I - beta_t k_t k_t^T)``,
a matrix that depends on the token, so a scan of scalar decays (Mamba-2's)
cannot compute it: :func:`gated_delta_rule_chunked` runs it a chunk of
positions at a time through the chunk's unit lower-triangular system (the
WY / UT transform).

**Gated attention**: a norm on every query and key head, rotary positions
on the first ``partial_rotary_factor`` of a head's dimensions, grouped
causal softmax, and the result times ``sigmoid(gate)``, the gate a second
query-sized projection of the block's input.

**Experts**: a softmax over ALL the experts' router outputs, the
``num_experts_per_tok`` largest a token divided by their sum, SiLU-gated
experts of which this chip holds a share
(:func:`~byzpy_tpu.parallel.moe.held_experts_ffn`), and one shared expert
whose output is multiplied by ``sigmoid(x w_s)``.

Set here and not in the source's config: the source's ``in_proj_qkvz`` and
``q_proj`` lay their outputs out head by head; here they are column blocks
of whole tiles (``w_qkv`` | ``w_z``, ``w_q`` | ``w_q_gate``), which with
seeded weights is a fixed permutation of columns. The rotary pairs are (i,
i + half) of the rotary part. The multi-token-prediction module of the
published model is left out (the config gives it no size).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_attention import causal_attention, causal_attention_serves
from ..parallel.moe import held_experts_ffn
from .bundle import ModelBundle, Segment
from .layers import (
    attention_proj,
    blocked_causal_attention,
    conv_silu,
    cross_entropy,
    rms_norm_one_plus,
    rotary,
    token_embedding,
)

Array = jnp.ndarray

_L2_EPS = 1e-6
# sides of the diagonal blocks the chunk's triangular system is solved on
# row by row, before blocks are merged by products
_SOLVE_BASE = 16


@dataclass(frozen=True)
class Qwen3NextConfig:
    """The published sizes of Qwen3-Next-80B-A3B (config.json), with the cut
    a chip holds: ``num_hidden_layers`` (the blocks kept: whole periods of
    ``full_attention_interval``), ``held_experts`` (first, count) of
    ``num_experts`` and ``vocab_size`` (the slice of the vocabulary)."""

    hidden_size: int = 2048
    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    vocab_size: int = 18992
    rms_norm_eps: float = 1e-6
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk_size: int = 64
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    query_block: int = 512
    # mixture of experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    held_experts: Tuple[int, int] = (0, 32)

    def linear(self, layer: int) -> bool:
        """Is block ``layer``'s mixer Gated DeltaNet?"""
        return (layer + 1) % self.full_attention_interval != 0


# --------------------------------------------------------------------------
# Gated DeltaNet
# --------------------------------------------------------------------------


@jax.custom_vjp
def unit_lower_inverse(a: Array) -> Array:
    """``(I + a)^-1`` over the last two axes for a STRICTLY lower-triangular
    ``a (..., C, C)``, ``C`` a power of two: forward substitution, row by
    row, on the diagonal blocks of ``_SOLVE_BASE`` rows, then pairs of
    blocks merged (``[[X, 0], [-Y a21 X, Y]]``) until one is left; nothing
    is divided, nothing can overflow. Its backward is the inverse's own:
    ``d a = -(T^T d T T^T)``, strictly lower. The products are at the
    backend's default precision like the rule's others: on the v5e, at the
    published sizes, taking the system's at full float32 left the rule's
    error against the recurrence where it was (0.002811 against 0.002810 of
    the output's norm; with every product at full float32 4e-6) and cost a
    fifth of its time (PERF.md, PR 39)."""
    size = a.shape[-1]
    base = min(_SOLVE_BASE, size)

    def blocks(side, down):
        """The ``side`` x ``side`` blocks on the diagonal of ``a`` (``down`` 0)
        or those under every other one of them (``down`` 1: a pair's a21),
        stacked on a new third-last axis."""
        step = side * (1 + down)
        return jnp.stack([a[..., at + side * down: at + side * down + side, at: at + side]
                          for at in range(0, size, step)], axis=-3)

    diagonal = blocks(base, 0)  # (..., n, base, base)
    # X = (I + a)^-1 - I of a block: X[i] = -a[i] - sum_{j < i} a[i, j] X[j]
    rows = [-diagonal[..., 0, :]]
    for i in range(1, base):
        known = jnp.stack(rows, axis=-2)  # (..., i, base)
        rows.append(-diagonal[..., i, :]
                    - jnp.sum(diagonal[..., i, :i, None] * known, axis=-2))
    inverse = jnp.stack(rows, axis=-2) + jnp.eye(base, dtype=a.dtype)
    side = base
    while side < size:
        upper, lower = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        corner = -(lower @ blocks(side, 1) @ upper)
        inverse = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([corner, lower], axis=-1)], axis=-2)  # (..., n / 2, 2 side, 2 side)
        side *= 2
    return inverse[..., 0, :, :]


def _unit_lower_inverse_fwd(a):
    inverse = unit_lower_inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    turned = jnp.swapaxes(inverse, -1, -2)
    d_a = -(turned @ d_inverse @ turned)
    size = inverse.shape[-1]
    return (jnp.where(jnp.arange(size)[:, None] > jnp.arange(size)[None, :], d_a, 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def gated_delta_rule_chunked(q: Array, k: Array, v: Array, g: Array, beta: Array, chunk: int
                             ) -> Array:
    """The gated delta rule of one sequence, ``chunk`` positions at a time.

    ``q``, ``k`` ``(T, Hk, K)`` as the rule reads them (normalised, q
    scaled), ``v (T, Hv, V)``, ``g (T, Hv)`` the log of the decay (<= 0),
    ``beta (T, Hv)``; key head ``h`` serves value heads ``h r .. h r + r -
    1``, ``r = Hv / Hk``. Returns ``o (T, Hv, V)`` float32.

    Inside a chunk, with ``G`` the running sum of ``g`` from its start and
    ``S0`` the state it starts from, the rule's ``u`` solve ``(I + A) U =
    beta V - (beta exp(G) K) S0`` with ``A[t, s] = beta_t exp(G_t - G_s)
    (k_t . k_s)`` for ``s < t``: one inverse a chunk a head
    (:func:`unit_lower_inverse`) serves both right-hand sides, the one
    that needs no state ahead of the scan over chunks. Any ``T``: the tail
    is padded with ``beta = 0`` and ``g = 0``, which neither writes nor
    decays. No exponential of a positive number is taken."""
    with jax.named_scope("model.delta_rule"):
        t, hk, dk = q.shape
        hv, dv = v.shape[1:]
        r = hv // hk
        pad = -t % chunk
        if pad:
            q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                                for a in (q, k, v, g, beta))
        nc = (t + pad) // chunk
        f32 = jnp.float32
        # (nc, Hk, C, K); (nc, Hk, r, C, V); (nc, Hk, r, C)
        q, k = (a.astype(f32).reshape(nc, chunk, hk, dk).transpose(0, 2, 1, 3) for a in (q, k))
        v = v.astype(f32).reshape(nc, chunk, hk, r, dv).transpose(0, 2, 3, 1, 4)
        g, beta = (a.astype(f32).reshape(nc, chunk, hk, r).transpose(0, 2, 3, 1)
                   for a in (g, beta))
        run = jnp.cumsum(g, axis=-1)  # G
        at = jnp.arange(chunk)
        earlier, seen = at[:, None] > at[None, :], at[:, None] >= at[None, :]
        decay = jnp.exp(jnp.where(seen, run[..., :, None] - run[..., None, :], -jnp.inf))
        kk = jnp.einsum("chid,chjd->chij", k, k)
        system = jnp.where(earlier, beta[..., :, None] * kk[:, :, None] * decay, 0.0)
        solve = unit_lower_inverse(system)  # (nc, Hk, r, C, C)
        own = solve @ (v * beta[..., None])  # U had every chunk started from zero
        carried = solve @ (k[:, :, None] * (beta * jnp.exp(run))[..., None])  # what S0 takes off
        inside = jnp.where(seen, jnp.einsum("chid,chjd->chij", q, k)[:, :, None] * decay, 0.0)
        q_in = q[:, :, None] * jnp.exp(run)[..., None]
        k_out = k[:, :, None] * jnp.exp(run[..., -1:] - run)[..., None]
        total = jnp.exp(run[..., -1])[..., None, None]

        def one_chunk(state, c):  # state (Hk, r, K, V)
            own_c, carried_c, inside_c, q_c, k_c, total_c = c
            u = own_c - carried_c @ state
            out = q_c @ state + inside_c @ u
            return state * total_c + jnp.swapaxes(k_c, -1, -2) @ u, out

        _, out = lax.scan(one_chunk, jnp.zeros((hk, r, dk, dv), f32),
                          (own, carried, inside, q_in, k_out, total))
        # (nc, Hk, r, C, V) -> (T, Hv, V)
        return out.transpose(0, 3, 1, 2, 4).reshape(nc * chunk, hv, dv)[:t]


def gated_delta_net(p: Dict[str, Array], x: Array, cfg: Qwen3NextConfig) -> Array:
    """One sequence ``(T, hidden)`` through a Gated DeltaNet mixer."""
    t = x.shape[0]
    hk, hv, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                      cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    f32 = jnp.float32
    # model.ssm_proj: the products; model.ssm_gate: what of the mixer is
    # neither a product nor the rule (which names itself inside it)
    with jax.named_scope("model.ssm_proj"):
        qkv = x @ p["w_qkv"].astype(x.dtype)
        z = x @ p["w_z"].astype(x.dtype)
        ba = (x @ p["w_ba"].astype(x.dtype)).astype(f32)
    with jax.named_scope("model.ssm_gate"):
        q, k, v = conv_silu(qkv, p["conv_w"].astype(x.dtype), None, (hk * dk, 2 * hk * dk))
        q, k = (a.reshape(t, hk, dk).astype(f32) for a in (q, k))
        q = q * lax.rsqrt(jnp.sum(jnp.square(q), axis=-1, keepdims=True) + _L2_EPS)
        k = k * lax.rsqrt(jnp.sum(jnp.square(k), axis=-1, keepdims=True) + _L2_EPS)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
        o = gated_delta_rule_chunked(q / math.sqrt(dk), k, v.reshape(t, hv, dv), g, beta,
                                     cfg.chunk_size)
        # the gated norm: over each head's outputs, a plain scale, times silu(z)
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps)
        o = o * p["gate_norm_scale"] * jax.nn.silu(z.reshape(t, hv, dv).astype(f32))
        o = o.reshape(t, hv * dv).astype(x.dtype)
    with jax.named_scope("model.ssm_proj"):
        return o @ p["w_out"].astype(x.dtype)


# --------------------------------------------------------------------------
# gated attention
# --------------------------------------------------------------------------


def gated_attention(p: Dict[str, Array], x: Array, cfg: Qwen3NextConfig) -> Array:
    """Causal softmax attention of one sequence ``(T, hidden)``:
    ``num_attention_heads`` query heads share ``num_key_value_heads``
    key/value heads; every query and key head is normed, its first
    ``partial_rotary_factor`` dimensions turned by position; the result is
    multiplied by ``sigmoid`` of a second query-sized projection. The core
    is the block-causal kernels where they serve
    (:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`),
    :func:`~byzpy_tpu.models.layers.blocked_causal_attention` elsewhere."""
    with jax.named_scope("model.attention"):
        t = x.shape[0]
        heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        turned = int(hd * cfg.partial_rotary_factor)

        def placed(a, weight):  # (T, n, head_dim): normed, then the rotary part turned
            a = rms_norm_one_plus(a, weight, cfg.rms_norm_eps)
            return jnp.concatenate(
                [rotary(a[..., :turned], cfg.rope_theta), a[..., turned:]], axis=-1)

        q = placed(attention_proj(x, p["w_q"]).reshape(t, heads, hd), p["q_norm_weight"])
        k = placed(attention_proj(x, p["w_k"]).reshape(t, kv, hd), p["k_norm_weight"])
        v = attention_proj(x, p["w_v"])
        if causal_attention_serves(x, hd):
            out = causal_attention(q.reshape(t, heads * hd), k.reshape(t, kv * hd), v,
                                   kv_heads=kv)
        else:
            out = blocked_causal_attention(q.reshape(t, kv, heads // kv, hd), k,
                                           v.reshape(t, kv, hd), cfg.query_block)
        out = out * jax.nn.sigmoid(attention_proj(x, p["w_q_gate"]))
        return attention_proj(out, p["w_o"])


# --------------------------------------------------------------------------
# the chain
# --------------------------------------------------------------------------


def round_rows(cfg: Qwen3NextConfig, tokens: int) -> int:
    """The size of a round of the held experts: four times an expert's
    mean load of ``tokens`` (``held_experts_ffn``'s own eighth of the tokens
    would be 6.6 times it), in whole sublanes:
    320 rows for 4096 tokens, top-10 of 512."""
    mean = tokens * cfg.num_experts_per_tok / cfg.num_experts
    return max(8, -(-math.ceil(4 * mean) // 8) * 8)


def _expert_ffn(p: Dict[str, Array], x: Array, cfg: Qwen3NextConfig):
    return held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=cfg.held_experts[0], n_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_tok, round_rows=round_rows(cfg, x.shape[0]),
        w_gate=p["experts_gate"], shared_gate=p["shared_gate"], score=jax.nn.softmax,
        shared_weight=p["shared_weight"])


def decoder_block(p: Dict[str, Array], h: Array, cfg: Qwen3NextConfig, linear: bool):
    """``h (B, T, hidden)`` through one block; returns ``(h, aux)``."""
    mixer = gated_delta_net if linear else gated_attention
    h = h + jax.vmap(lambda s: mixer(p, s, cfg))(
        rms_norm_one_plus(h, p["mixer_norm_weight"], cfg.rms_norm_eps))
    normed = rms_norm_one_plus(h, p["ffn_norm_weight"], cfg.rms_norm_eps)
    # the expert layer is token by token: sequences are laid end to end
    out, aux = _expert_ffn(p, normed.reshape(-1, normed.shape[-1]), cfg)
    return h + out.reshape(h.shape), aux


def _block(cfg: Qwen3NextConfig, dtype: Any, linear: bool):
    def apply(p, h):
        return decoder_block(p, h.astype(dtype), cfg, linear)

    return apply


def _head(cfg: Qwen3NextConfig, dtype: Any):
    def apply(p, h, targets):
        with jax.named_scope("model.head"):
            h = rms_norm_one_plus(h.astype(dtype), p["norm_weight"], cfg.rms_norm_eps)
            return jnp.mean(cross_entropy(h @ p["w_head"].astype(dtype), targets))

    return apply


def segment_keys(cfg: Qwen3NextConfig) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_delta`` / ``_attn`` ..., ``segNN_head``:
    sorted, they are in the chain's order."""
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{'delta' if cfg.linear(i) else 'attn'}"
        for i in range(cfg.num_hidden_layers)]
    return tuple(names + [f"seg{len(names):02d}_head"])


def init_params(cfg: Qwen3NextConfig, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the embedding's input is
    one-hot: fan_in 1); the ``1 + w`` norms' weights 0, the gated norm's
    scale 1; the delta rule's vectors as the Gated DeltaNet paper's code
    draws them (``A`` in [1, 16], ``dt`` log-uniform in [0.001, 0.1]
    through the inverse softplus); the convolution's weights uniform in
    +-1/sqrt(kernel)."""
    hidden, f32 = cfg.hidden_size, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 24 * (cfg.num_hidden_layers + 2)))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in or shape[-2])

    def block(linear):
        held, width = cfg.held_experts[1], cfg.moe_intermediate_size
        shared = cfg.shared_expert_intermediate_size
        p = dict(
            mixer_norm_weight=jnp.zeros((hidden,), f32), ffn_norm_weight=jnp.zeros((hidden,), f32),
            router=matrix(hidden, cfg.num_experts),
            experts_gate=matrix(held, hidden, width), experts_up=matrix(held, hidden, width),
            experts_down=matrix(held, width, hidden),
            shared_gate=matrix(hidden, shared), shared_up=matrix(hidden, shared),
            shared_down=matrix(shared, hidden), shared_weight=matrix(hidden, 1))
        if linear:
            hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
            keyed, valued = hk * cfg.linear_key_head_dim, hv * cfg.linear_value_head_dim
            dt = jnp.exp(jax.random.uniform(next(keys), (hv,), f32, math.log(1e-3), math.log(0.1)))
            bound = 1.0 / math.sqrt(cfg.linear_conv_kernel_dim)
            p.update(
                w_qkv=matrix(hidden, 2 * keyed + valued), w_z=matrix(hidden, valued),
                w_ba=matrix(hidden, 2 * hv),
                conv_w=jax.random.uniform(
                    next(keys), (cfg.linear_conv_kernel_dim, 2 * keyed + valued), f32,
                    -bound, bound),
                a_log=jnp.log(jax.random.uniform(next(keys), (hv,), f32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                gate_norm_scale=jnp.ones((cfg.linear_value_head_dim,), f32),
                w_out=matrix(valued, hidden))
        else:
            q = cfg.num_attention_heads * cfg.head_dim
            kv = cfg.num_key_value_heads * cfg.head_dim
            p.update(
                w_q=matrix(hidden, q), w_q_gate=matrix(hidden, q), w_k=matrix(hidden, kv),
                w_v=matrix(hidden, kv), w_o=matrix(q, hidden),
                q_norm_weight=jnp.zeros((cfg.head_dim,), f32),
                k_norm_weight=jnp.zeros((cfg.head_dim,), f32))
        return p

    names = segment_keys(cfg)
    params = {names[0]: {"embedding": matrix(cfg.vocab_size, hidden, fan_in=1)}}
    for i, name in enumerate(names[1:-1]):
        params[name] = block(cfg.linear(i))
    params[names[-1]] = {"norm_weight": jnp.zeros((hidden,), f32),
                         "w_head": matrix(hidden, cfg.vocab_size)}
    return params


def qwen3_next_bundle(cfg: Qwen3NextConfig, seed: int = 0, dtype: Any = jnp.float32
                      ) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    if cfg.num_hidden_layers % cfg.full_attention_interval:
        raise ValueError("qwen3_next: whole periods of full_attention_interval blocks")
    names = segment_keys(cfg)
    segments = [Segment(names[0], token_embedding(dtype))]
    for i, name in enumerate(names[1:-1]):
        segments.append(Segment(name, _block(cfg, dtype, cfg.linear(i)), aux=True))
    segments.append(Segment(names[-1], _head(cfg, dtype)))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def qwen3_next_ep16(seed: int = 0, dtype: Any = jnp.float32, **overrides: Any) -> ModelBundle:
    """What one chip of sixteen holds of Qwen3-Next-80B-A3B's first period:
    three Gated DeltaNet blocks and one gated-attention block, experts 0-31
    of 512 in each, 18,992 of 151,936 vocabulary rows, every head, every
    width as published (d = 625.7M)."""
    return qwen3_next_bundle(replace(Qwen3NextConfig(), **overrides), seed, dtype)


__all__ = [
    "Qwen3NextConfig",
    "decoder_block",
    "gated_attention",
    "gated_delta_net",
    "gated_delta_rule_chunked",
    "init_params",
    "qwen3_next_bundle",
    "qwen3_next_ep16",
    "round_rows",
    "segment_keys",
    "unit_lower_inverse",
]
