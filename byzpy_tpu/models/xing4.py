"""Xing4.0-29B-A4B (``model_type: xing4_0``) on the training path, as a
chain of segments: DeepSeek-V3's block (latent attention, a sigmoid top-k
expert layer with one shared expert) on a CHANGED RESIDUAL PATH,
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606).

The residual is ``n = hc_mult`` streams a position, ``X (n, hidden)``. A
sublayer ``F`` (two a block: latent attention, then the dense MLP or the
expert layer) has three mappings of its own, made anew at every position
from the streams themselves:

    x^ = RMSNorm(vec(X))                          over all n * hidden values
    [Hp~ | Ho~ | Hr~] = alpha * (x^ Phi) + b      Phi (n * hidden, n + n + n * n)
    H_pre  = sigmoid(Hp~)                         (n,)
    H_post = 2 sigmoid(Ho~)                       (n,)
    H_res  = SK(clip(Hr~, lo, hi))                (n, n), doubly stochastic

with one ``alpha`` for each of the three column blocks and ``SK`` the
Sinkhorn projection: ``M = exp(.)``, then ``hc_sinkhorn_iters`` times every
column over (its sum + ``hc_eps``), then every row over (its sum +
``hc_eps``). The sublayer reads ``u = H_pre X`` (one ``hidden``-vector),
computes ``y = F(RMSNorm(u))`` and writes ``X' = H_res X + H_post^T y``
(stream ``i`` gets ``H_post[i] y``). No other residual add exists in a
block. The streams start as ``n`` copies of the embedded token and end as
their sum (Hyper-Connections' entry and exit), made in the first and the
last block's segment: the embedding's and the head's boundaries stay
``(B, T, hidden)``, the blocks' between are ``(B, T, n, hidden)``.

The mappings are float32 at full precision whatever the activations' type:
a position's 4 x 4 matrix costs nothing, and a bf16-rounded operand would
move every stream. Here a position's streams lie side by side in ONE row of
``n * hidden`` (stream ``i`` its columns ``i * hidden ..``: whole lanes, no
array whose second-minor axis is ``n``), the mappings' position axis lies
along the lanes through the Sinkhorn iterations, the norm's factor is
applied to the 24 projected values (``x^ Phi = rsqrt(mean X^2) (X Phi)``:
one read of the streams less), and the two mixes are functions with a
backward of their own (:func:`pre_mix`, :func:`write_back`): a column
block's cotangent is written once into its place, where automatic
differentiation of a slice pads every block to the whole row and adds the
padded rows up.

Latent attention is :func:`~byzpy_tpu.models.layers.mla_attention`, shared
with GLM-4.7-Flash, at 32 heads of 128 + 64 / 128 under YaRN
(:class:`~byzpy_tpu.models.layers.YarnScaling`); the expert layer is
:func:`~byzpy_tpu.parallel.moe.held_experts_ffn` as it stands. Set here and
not in the source's config: the streams' entry and exit; the Sinkhorn's
order (columns first) and where ``hc_eps`` stands; the hyper-connection's
norm has no learned scale; rotary pairs are (i, i + half); the router's
correction bias is a buffer held at zero, so it is left out. The
multi-token-prediction module is not written: how it reads ``n`` streams
is not in the source's config (``num_nextn_predict_layers`` must be 0).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.moe import held_experts_ffn
from .bundle import ModelBundle, Segment
from .glm4_moe_lite import _gated_mlp  # the same SiLU-gated MLP, under model.mlp
from .layers import YarnScaling, cross_entropy, mla_attention, rms_norm, token_embedding

Array = jnp.ndarray


@dataclass(frozen=True)
class Xing4Config:
    """The published sizes of Xing4.0-29B-A4B (config.json), with the cut a
    chip holds: ``num_hidden_layers`` (the blocks kept, the first
    ``first_k_dense_replace`` of them dense), ``held_experts`` (first,
    count) of ``n_routed_experts`` and ``vocab_size`` (the slice of the
    vocabulary)."""

    hidden_size: int = 3584
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 0
    vocab_size: int = 16384
    rms_norm_eps: float = 1e-6
    # hyper-connections
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # multi-head latent attention
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rope_scaling: Optional[YarnScaling] = YarnScaling(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=1.0)
    query_block: int = 512
    # feed-forward
    intermediate_size: int = 9216
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    held_experts: Tuple[int, int] = (0, 8)

    @property
    def hc_maps_width(self) -> int:
        """Columns of a hyper-connection's ``Phi``: pre, post, residual."""
        return self.hc_mult * (2 + self.hc_mult)


# --------------------------------------------------------------------------
# hyper-connections
# --------------------------------------------------------------------------


def sinkhorn(logits: Array, iters: int, eps: float) -> Array:
    """``logits (n, n, ...)`` -> ``exp`` of them, then ``iters`` times:
    every column over (its sum + ``eps``), then every row over (its sum +
    ``eps``); entry ``[i, j]`` is row ``i``, column ``j``, and the axes
    behind are positions. The sums are written out term by term, so the
    whole projection (and its derivative) is elementwise in the positions."""
    n = logits.shape[0]
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (sum(m[i] for i in range(n)) + eps)[None]
        m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
    return m


def hc_maps(p: Dict[str, Array], x: Array, cfg: Xing4Config) -> Tuple[Array, Array, Array]:
    """The three mappings of one hyper-connection at every position:
    ``x (N, n * hidden)`` streams -> ``H_pre (N, n)``, ``H_post (N, n)``,
    ``H_res (N, n, n)``, float32. ``p``: ``phi (n * hidden, n (2 + n))``,
    ``b (n (2 + n),)``, ``alpha (3,)`` (pre, post, residual)."""
    with jax.named_scope("model.hc_maps"):
        n = cfg.hc_mult
        x32 = x.astype(jnp.float32)
        inv_rms = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1) + cfg.rms_norm_eps)
        # float32 at full precision: a position's 4 x 4 matrix moves every stream
        projected = jnp.dot(x32, p["phi"].astype(jnp.float32), precision=lax.Precision.HIGHEST)
        alpha = jnp.repeat(p["alpha"].astype(jnp.float32), np.asarray([n, n, n * n]))
        # positions along the lanes from here on: (n (2 + n), N)
        raw = (alpha * projected * inv_rms[:, None] + p["b"].astype(jnp.float32)).T
        pre = jax.nn.sigmoid(raw[:n])
        post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
        res = sinkhorn(
            jnp.clip(raw[2 * n:], cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max).reshape(
                n, n, -1), cfg.hc_sinkhorn_iters, cfg.hc_eps)
        return pre.T, post.T, jnp.moveaxis(res, -1, 0)


def _streams(x: Array, n: int):
    """The ``n`` column blocks of ``x (N, n * hidden)``, in float32."""
    d = x.shape[1] // n
    return [x[:, i * d:(i + 1) * d].astype(jnp.float32) for i in range(n)]


def _rowsum(a: Array, b: Array) -> Array:
    return jnp.sum(a * b, axis=1)


@jax.custom_vjp
def pre_mix(x: Array, pre: Array) -> Array:
    """``u = H_pre X`` a position: ``x (N, n * hidden)``, ``pre (N, n)``
    float32 -> ``(N, hidden)`` in ``x``'s dtype."""
    n = pre.shape[1]
    return sum(pre[:, i, None] * xi for i, xi in enumerate(_streams(x, n))).astype(x.dtype)


def _pre_mix_fwd(x, pre):
    return pre_mix(x, pre), (x, pre)


def _pre_mix_bwd(kept, du):
    # the backward rule is traced outside the scope the forward stood in
    with jax.named_scope("model.hc_mix"):
        x, pre = kept
        n = pre.shape[1]
        du = du.astype(jnp.float32)
        dx = jnp.concatenate([pre[:, i, None] * du for i in range(n)], axis=1).astype(x.dtype)
        return dx, jnp.stack([_rowsum(du, xi) for xi in _streams(x, n)], axis=1)


pre_mix.defvjp(_pre_mix_fwd, _pre_mix_bwd)


@jax.custom_vjp
def write_back(x: Array, y: Array, res: Array, post: Array) -> Array:
    """``X' = H_res X + H_post^T y`` a position: ``x (N, n * hidden)``,
    ``y (N, hidden)``, ``res (N, n, n)``, ``post (N, n)`` -> ``(N, n *
    hidden)`` in ``x``'s dtype."""
    n = post.shape[1]
    xs, y32 = _streams(x, n), y.astype(jnp.float32)
    return jnp.concatenate(
        [sum(res[:, i, j, None] * xs[j] for j in range(n)) + post[:, i, None] * y32
         for i in range(n)], axis=1).astype(x.dtype)


def _write_back_fwd(x, y, res, post):
    return write_back(x, y, res, post), (x, y, res, post)


def _write_back_bwd(kept, d_out):
    with jax.named_scope("model.hc_mix"):
        x, y, res, post = kept
        n = post.shape[1]
        xs, ds, y32 = _streams(x, n), _streams(d_out, n), y.astype(jnp.float32)
        dx = jnp.concatenate(
            [sum(res[:, i, j, None] * ds[i] for i in range(n)) for j in range(n)], axis=1)
        dy = sum(post[:, i, None] * ds[i] for i in range(n))
        d_res = jnp.stack(
            [jnp.stack([_rowsum(ds[i], xs[j]) for j in range(n)], axis=1) for i in range(n)],
            axis=1)
        d_post = jnp.stack([_rowsum(ds[i], y32) for i in range(n)], axis=1)
        return dx.astype(x.dtype), dy.astype(y.dtype), d_res, d_post


write_back.defvjp(_write_back_fwd, _write_back_bwd)


def hyper_connected(p: Dict[str, Array], x: Array, norm_scale: Array,
                    sublayer: Callable[[Array], Tuple[Array, Any]], cfg: Xing4Config):
    """One hyper-connected sublayer on ``x (N, n * hidden)``: ``X' = H_res
    X + H_post^T F(RMSNorm(H_pre X))`` with ``p`` the connection's ``phi``,
    ``b``, ``alpha``. ``sublayer`` returns ``(y, aux)`` (``aux`` ``None``
    where it has nothing to report); so does this."""
    pre, post, res = hc_maps(p, x, cfg)
    with jax.named_scope("model.hc_mix"):
        u = pre_mix(x, pre)
    y, aux = sublayer(rms_norm(u, norm_scale, cfg.rms_norm_eps))
    with jax.named_scope("model.hc_mix"):
        return write_back(x, y, res, post), aux


# --------------------------------------------------------------------------
# the chain
# --------------------------------------------------------------------------


def _expert_ffn(p: Dict[str, Array], x: Array, cfg: Xing4Config):
    # an expert's round is a quarter of the tokens, four times its mean load at
    # the published 4 picks of 64: held_experts_ffn's own eighth is twice the
    # mean, under this router's fullest expert (2.2 times), and a second round
    # reads the experts' matrices again, which products this short wait for
    return held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=cfg.held_experts[0], n_experts=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        w_gate=p["experts_gate"], shared_gate=p["shared_gate"],
        round_rows=-(-max(x.shape[0] // 4, 1) // 8) * 8)


def _connection(p: Dict[str, Array], which: str) -> Dict[str, Array]:
    return {name: p[f"{which}_hc_{name}"] for name in ("phi", "b", "alpha")}


def decoder_block(p: Dict[str, Array], x: Array, cfg: Xing4Config, dense: bool):
    """The streams ``x (B, T, n, hidden)`` through one block; returns ``(x,
    aux)``, ``aux`` the expert layer's or ``None`` (a dense block)."""
    batch, t, n, hidden = x.shape
    with jax.named_scope("model.hc_mix"):
        rows = x.reshape(batch * t, n * hidden)  # sequences end to end, streams side by side

    def attend(z):
        with jax.named_scope("model.attention"):  # the sequences apart again, and back
            return jax.vmap(lambda s: mla_attention(p, s, cfg))(
                z.reshape(batch, t, hidden)).reshape(batch * t, hidden), None

    rows, _ = hyper_connected(_connection(p, "attn"), rows, p["attn_norm_scale"], attend, cfg)
    feed = (lambda z: (_gated_mlp(p, z), None)) if dense else (lambda z: _expert_ffn(p, z, cfg))
    out, aux = hyper_connected(_connection(p, "ffn"), rows, p["ffn_norm_scale"], feed, cfg)
    with jax.named_scope("model.hc_mix"):
        return out.reshape(x.shape), aux


def _block(cfg: Xing4Config, dtype: Any, dense: bool, first: bool, last: bool):
    """A block as a link: the first is handed the embedded tokens and copies
    them into the ``n`` streams, the last hands on the streams' sum."""
    n = cfg.hc_mult

    def apply(p, boundary):
        boundary = boundary.astype(dtype)
        if first:
            with jax.named_scope("model.hc_mix"):
                boundary = jnp.stack([boundary] * n, axis=2)
        out, aux = decoder_block(p, boundary, cfg, dense)
        if last:
            with jax.named_scope("model.hc_mix"):
                batch, t = out.shape[:2]
                out = pre_mix(out.reshape(batch * t, -1), jnp.ones((batch * t, n), jnp.float32)
                              ).reshape(batch, t, -1)
        return out if dense else (out, aux)

    return apply


def _head(cfg: Xing4Config, dtype: Any):
    def apply(p, h, targets):
        with jax.named_scope("model.head"):
            h = rms_norm(h.astype(dtype), p["norm_scale"], cfg.rms_norm_eps)
            return jnp.mean(cross_entropy(h @ p["w_head"].astype(dtype), targets))

    return apply


def segment_keys(cfg: Xing4Config) -> Tuple[str, ...]:
    """``seg00_embed``, ``seg01_dense`` / ``_moe`` ..., ``segNN_head``:
    sorted, they are in the chain's order."""
    names = ["seg00_embed"] + [
        f"seg{i + 1:02d}_{'dense' if i < cfg.first_k_dense_replace else 'moe'}"
        for i in range(cfg.num_hidden_layers)]
    return tuple(names + [f"seg{len(names):02d}_head"])


def init_params(cfg: Xing4Config, seed: int = 0) -> Dict[str, Dict[str, Array]]:
    """Matrices normal with variance 1 / fan_in (the embedding's input is
    one-hot: fan_in 1); norm scales 1; a hyper-connection starts near the
    plain residual (mHC's initialisation): ``alpha`` 0.01, ``H_pre`` 1 / n,
    ``H_post`` 1, ``H_res`` the identity but for ``exp(-8)``."""
    hidden, n, f32 = cfg.hidden_size, cfg.hc_mult, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 20 * (cfg.num_hidden_layers + 2)))

    def matrix(*shape, fan_in=None):
        return jax.random.normal(next(keys), shape, f32) / math.sqrt(fan_in or shape[-2])

    def ones(size):
        return jnp.ones((size,), f32)

    def connection(which):
        b = jnp.concatenate([
            jnp.full((n,), -math.log(n - 1.0) if n > 1 else 0.0, f32), jnp.zeros((n,), f32),
            (8.0 * (jnp.eye(n, dtype=f32) - 1.0)).reshape(-1)])
        return {f"{which}_hc_phi": matrix(n * hidden, cfg.hc_maps_width),
                f"{which}_hc_b": b, f"{which}_hc_alpha": jnp.full((3,), 0.01, f32)}

    def block(dense):
        heads = cfg.num_attention_heads
        p = dict(
            connection("attn"), **connection("ffn"),
            attn_norm_scale=ones(hidden), ffn_norm_scale=ones(hidden),
            w_qa=matrix(hidden, cfg.q_lora_rank), q_norm_scale=ones(cfg.q_lora_rank),
            w_qb=matrix(cfg.q_lora_rank, heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
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
    for i, name in enumerate(names[1:-1]):
        params[name] = block(i < cfg.first_k_dense_replace)
    params[names[-1]] = {"norm_scale": ones(hidden), "w_head": matrix(hidden, cfg.vocab_size)}
    return params


def xing4_bundle(cfg: Xing4Config, seed: int = 0, dtype: Any = jnp.float32) -> ModelBundle:
    """The segmented bundle: batches are ``x, y: (B, T)`` token ids and
    next tokens. ``dtype`` is the type activations are computed in."""
    if cfg.num_nextn_predict_layers or cfg.num_hidden_layers < 1:
        raise ValueError("xing4: at least one block, and no MTP module (how it reads the "
                         "streams is not in the source's config)")
    names = segment_keys(cfg)
    blocks = names[1:-1]
    segments = [Segment(names[0], token_embedding(dtype))]
    for i, name in enumerate(blocks):
        dense = i < cfg.first_k_dense_replace
        segments.append(Segment(
            name, _block(cfg, dtype, dense, first=i == 0, last=i == len(blocks) - 1),
            aux=not dense))
    segments.append(Segment(names[-1], _head(cfg, dtype)))
    return ModelBundle(apply_fn=None, params=init_params(cfg, seed), segments=tuple(segments))


def xing4_29b_ep8(seed: int = 0, dtype: Any = jnp.float32, **overrides: Any) -> ModelBundle:
    """What one chip of eight holds of Xing4.0-29B-A4B's first pipeline
    stage: the dense block and four expert blocks on four hyper-connected
    streams, experts 0-7 of 64, 16,384 of 131,072 vocabulary rows, every
    head, every width as published (d = 759.3M)."""
    return xing4_bundle(replace(Xing4Config(), **overrides), seed, dtype)


__all__ = [
    "Xing4Config",
    "decoder_block",
    "hc_maps",
    "hyper_connected",
    "init_params",
    "pre_mix",
    "segment_keys",
    "sinkhorn",
    "write_back",
    "xing4_29b_ep8",
    "xing4_bundle",
]
