"""What the language models of this package share: RMSNorm in its two
forms, rotary positions (plain, or with YaRN's blended frequencies), the
chain's embedding link, next-token cross-entropy, the causal depthwise
convolution of the state-space and linear-attention mixers with its SiLU,
the gated short convolution (two elementwise gates around that convolution,
no activation), multi-head latent attention, and causal attention by blocks of queries for
a call the block-causal kernels do not serve
(:func:`~byzpy_tpu.ops.pallas_attention.causal_attention_serves`)."""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas_attention import causal_attention, causal_attention_serves

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


@dataclass(frozen=True)
class YarnScaling:
    """A configuration's ``rope_scaling`` of ``type: yarn`` (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V2 / V3 read it): the rotary pairs that
    turn fast keep their frequency, the slow ones' is divided by
    ``factor``, and those between are blended."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def blend_range(self, dim: int, theta: float) -> Tuple[int, int]:
        """``(low, high)``: pair ``i <= low`` keeps its frequency, pair
        ``i >= high`` is interpolated. A pair that makes ``beta`` turns over
        the original context is pair ``dim ln(original / (2 pi beta)) /
        (2 ln theta)``."""
        def pair_of(turns: float) -> float:
            return dim * math.log(self.original_max_position_embeddings / (2 * math.pi * turns)) \
                / (2 * math.log(theta))

        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), dim - 1)
        return low, high

    def frequencies(self, dim: int, theta: float) -> Array:
        """Pair ``i``'s frequency, ``(dim / 2,)`` float32: ``theta_i m_i +
        (theta_i / factor)(1 - m_i)`` with ``theta_i = theta ** (-2 i /
        dim)`` and ``m_i = 1 - clip((i - low) / (high - low), 0, 1)``."""
        pair = jnp.arange(dim // 2, dtype=jnp.float32)
        plain = theta ** (-pair * 2.0 / dim)
        low, high = self.blend_range(dim, theta)
        keep = 1.0 - jnp.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
        return plain * keep + plain / self.factor * (1.0 - keep)

    @staticmethod
    def _mscale(factor: float, by: float) -> float:
        return 0.1 * by * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def rotary_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return self._mscale(self.factor, self.mscale) / self._mscale(self.factor,
                                                                     self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """What the scores' ``head_dim ** -0.5`` is multiplied by:
        ``mscale(factor, mscale_all_dim) ** 2`` (DeepSeek-V2 / V3's reading
        of ``mscale_all_dim``)."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


def _turn_cos_sin(t: int, dim: int, theta: float, scaling: Optional[YarnScaling]
                  ) -> Tuple[Array, Array]:
    """Cosine and sine ``(t, dim / 2)`` float32 of position times pair
    frequency (``theta ** (-2 i / dim)``, or ``scaling``'s blend), times
    ``scaling``'s ``rotary_scale``."""
    if scaling is None:
        freq = theta ** (-jnp.arange(dim // 2, dtype=jnp.float32) * 2.0 / dim)
    else:
        freq = scaling.frequencies(dim, theta)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if scaling is not None and scaling.rotary_scale != 1.0:
        cos, sin = cos * scaling.rotary_scale, sin * scaling.rotary_scale
    return cos, sin


def rotary(x: Array, theta: float, scaling: Optional[YarnScaling] = None) -> Array:
    """Rotary position embedding of ``x (T, ..., dim)``, position = index
    along the first axis: the pair (``x[..., i]``, ``x[..., i + dim / 2]``)
    turned by ``t * theta ** (-2 i / dim)``, or by ``t`` times ``scaling``'s
    blended frequency, cos and sin times its ``rotary_scale``. Written as the
    2 x 2 rotation
    of every pair (a product and a sum over an axis of two), with no slice
    of ``x``: a slice's cotangent is a zero-padded array, and the two
    halves' padded cotangents added up fed the weight-gradient product of
    the shared rotary key on the v5e's compiler in a form that lost it
    (PERF.md, PR 34)."""
    with jax.named_scope("model.rotary"):
        t, dim = x.shape[0], x.shape[-1]
        half = dim // 2
        cos, sin = _turn_cos_sin(t, dim, theta, scaling)
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


@jax.custom_vjp
def gated_short_conv(bcx: Array, w: Array) -> Array:
    """The gated short convolution of one sequence (LFM2's operator between
    its two projections): ``bcx (T, 3 * hidden)`` holds the column blocks
    ``[B | C | X]``, ``w (K, hidden)`` the depthwise taps; returns ``C *
    causal_depthwise_conv(B * X, w)``, ``(T, hidden)``: no bias, NO
    activation. With a backward of its own: it keeps ``bcx`` and ``w``
    alone (the gated product and the convolution are made again: two
    elementwise passes, against two more arrays of ``(T, hidden)`` kept a
    block), runs the same ``K`` shifted multiply-adds the other way, and
    writes the three blocks' cotangents ONCE, side by side, as the
    projection's transpose reads them (left to automatic differentiation
    each block's slice is padded to the whole row and the padded rows are
    added up; each tap's transpose is a write into a fresh zero array)."""
    b, c, x = jnp.split(bcx, 3, axis=1)
    return c * causal_depthwise_conv(b * x, w)


def _gated_short_conv_fwd(bcx, w):
    return gated_short_conv(bcx, w), (bcx, w)


def _gated_short_conv_bwd(kept, d_out):
    # the backward rule is traced outside the scope the forward stood in
    with jax.named_scope("model.short_conv"):
        bcx, w = kept
        k = w.shape[0]
        b, c, x = jnp.split(bcx, 3, axis=1)
        g = b * x
        d_conv = d_out * c
        # dg[t] = sum_j w[j] d_conv[t + (K - 1) - j], zero past the end
        dg = sum(w[j] * _rows_moved(d_conv, j + 1 - k) for j in range(k))
        dw = jnp.stack([jnp.sum(d_conv * _rows_moved(g, k - 1 - j), axis=0) for j in range(k)])
        d_bcx = jnp.concatenate([dg * x, d_out * causal_depthwise_conv(g, w), dg * b], axis=1)
        return d_bcx, dw


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def blocked_causal_attention(q: Array, k: Array, v: Array, query_block: int,
                             window: Optional[int] = None) -> Array:
    """Causal softmax attention of one sequence, ``query_block`` queries at a
    time: ``q (T, kv, per, head_dim)`` (``per`` query heads read key/value
    head ``kv``), ``k (T, kv, head_dim)``, ``v (T, kv, head_dim)``; returns
    ``(T, kv * per * head_dim)``. With ``window = W`` query ``i`` reads keys
    ``j`` with ``0 <= i - j < W`` (the kernels' ``window``); ``None`` adds
    no op. Each block is rematerialised in the backward pass, so the score
    matrix alive at once is ``(heads, query_block, T)``. Its ops stand under
    ``model.attention_core``, the label the kernels' call enters."""
    t, kv, per, hd = q.shape
    block = min(query_block, t)
    pad = -t % block

    @jax.checkpoint
    def one_block(args):
        qb, start = args
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k).astype(jnp.float32) / math.sqrt(hd)
        position, key = (start + jnp.arange(block))[:, None], jnp.arange(t)[None, :]
        seen = position >= key
        if window is not None:
            seen = seen & (position - key < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", probs.astype(q.dtype), v)

    with jax.named_scope("model.attention_core"):
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(-1, block, kv, per, hd)
        starts = jnp.arange(q.shape[0]) * block
        return jax.lax.map(one_block, (q, starts)).reshape(-1, kv * per * hd)[:t]


def attention_proj(x: Array, w: Array) -> Array:
    """``x @ w`` in ``x``'s dtype under ``model.attention_proj``: attention's
    products with ``w_q``, ``w_k``, ``w_v`` and ``w_o`` (Qwen3-Next's
    ``w_q_gate`` too) in every model of this package, so that one label says
    what of ``model.attention`` is projections. Latent attention's own down-
    and up-projections stay ``model.mla_latent``."""
    with jax.named_scope("model.attention_proj"):
        return x @ w.astype(x.dtype)


def mla_attention(p: Dict[str, Array], x: Array, cfg: Any) -> Array:
    """Multi-head latent attention (DeepSeek-V2 / V3) of one sequence
    ``(T, hidden)``, causal, in its uncompressed training form: ONE function
    for every configuration that has it (``models.glm4_moe_lite``: 20 heads
    of 192 + 64 / 256; ``models.xing4``: 32 of 128 + 64 / 128 under YaRN),
    everything read from ``cfg`` (``num_attention_heads``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rope_theta``, ``rope_scaling``: ``None`` or a :class:`YarnScaling`,
    ``rms_norm_eps``, ``query_block``). A head's query and key are ``[no
    position | rotary]``; the rotary key is one vector a position, shared
    by every head; the scores' scale is ``(nope + rope) ** -0.5`` times the
    scaling's ``softmax_scale``. The core is ``num_attention_heads``
    key/value heads with one query head each: the block-causal kernels
    where they serve (:func:`~byzpy_tpu.ops.pallas_attention.
    causal_attention_serves`: a TPU; the queries and keys go in at whole
    lanes, zero columns behind the rotary part where ``nope + rope`` is not
    a multiple of 128, and the values at their own width, never padded),
    :func:`blocked_causal_attention` elsewhere (the CPU's route; it takes
    one head size, so there the narrower side is padded).

    The kernels read ``q, k (T, heads * width)`` and ``v (T, heads *
    v_head_dim)``, rows of whole heads side by side. Two forms make them,
    the same numbers, and :func:`_rows_route_pays` picks one from the
    shapes alone (``T``, the two latents' ranks, the zero lanes a head):

    * BORN IN ROWS (:func:`_latent_qkv_rows`) where the heads are whole
      lanes and the sequence is three times as long as the latents have
      rows: every head's cut, zero pad and rotary swap is made on the
      weights' columns, and no activation takes a third axis.
    * CUT ON THREE AXES (the lines below) elsewhere: the up-projections'
      results reshaped to ``(T, heads, width)``, sliced, turned, joined and
      reshaped back. The TPU's compiler lays such an array out with the
      positions on the lanes, so each slice, join and reshape is a pass over
      it, and each one's way back a pad, a select and an ``add_any``.

    What decides (PERF.md, PR 54: ``jax.vjp`` of this function under
    ``vmap`` over one sequence on a v5e, forward and backward, the kernels'
    5.5 ms among them): at GLM-4.7-Flash's cell (4096 positions, latents of
    768 + 512 rows, 20 heads of 256 / 256) the three axes read 12.27 ms and
    the rows 10.77; at 2048 positions 4.47 and 4.67; at Xing4.0's cell
    (1024 positions, the same latents, 32 heads of 192 padded to 256 / 128)
    2.76 and 3.54, and with those heads at 4096 positions 16.93 and 16.61:
    a sequence barely longer than the latents spares little, and 64 zero
    lanes a head add a third to the queries' products and double the
    keys'."""
    with jax.named_scope("model.attention"):
        t = x.shape[0]
        heads, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                                 cfg.qk_rope_head_dim, cfg.v_head_dim)
        scaling = cfg.rope_scaling
        stretch = 1.0 if scaling is None else scaling.softmax_scale
        lanes = -(nope + rope) % 128  # zero columns up to whole lanes
        serves = causal_attention_serves(x, nope + rope + lanes, vd)
        if serves and _rows_route_pays(t, p["w_qb"].shape[0], p["w_kvb"].shape[0], lanes):
            q, k, v = _latent_qkv_rows(p, x, cfg, lanes)
            out = causal_attention(q, k, v, kv_heads=heads, scale=stretch / math.sqrt(nope + rope))
            return attention_proj(out, p["w_o"])
        with jax.named_scope("model.mla_latent"):
            w = {name: p[name].astype(x.dtype)
                 for name in ("w_qa", "w_qb", "w_kva", "w_kr", "w_kvb")}
            c_q = rms_norm(x @ w["w_qa"], p["q_norm_scale"], cfg.rms_norm_eps)
            q = (c_q @ w["w_qb"]).reshape(t, heads, nope + rope)
            c_kv = rms_norm(x @ w["w_kva"], p["kv_norm_scale"], cfg.rms_norm_eps)
            kv = (c_kv @ w["w_kvb"]).reshape(t, heads, nope + vd)
            blank = [jnp.zeros((t, heads, lanes), x.dtype)] if serves and lanes else []
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[..., nope:], cfg.rope_theta, scaling)] + blank, axis=-1)
            k_rope = rotary(x @ w["w_kr"], cfg.rope_theta, scaling)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :], (t, heads, rope))] + blank,
                axis=-1)
            v = kv[..., nope:]
        if serves:
            out = causal_attention(
                q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, heads * vd), kv_heads=heads,
                scale=stretch / math.sqrt(nope + rope))
        else:
            # blocked_causal_attention takes one head size and scales by it:
            # the narrower side is padded with zeros, the scale put right
            width = max(nope + rope, vd)
            q = q * (math.sqrt(width / (nope + rope)) * stretch)
            q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, width - a.shape[-1]))) for a in (q, k, v))
            out = blocked_causal_attention(q[:, :, None, :], k, v, cfg.query_block)
            out = out.reshape(t, heads, width)[..., :vd].reshape(t, heads * vd)
        return attention_proj(out, p["w_o"])


def _rows_route_pays(t: int, q_rank: int, kv_rank: int, lanes: int) -> bool:
    """Does :func:`mla_attention` hand the kernels q, k and v born in rows
    (:func:`_latent_qkv_rows`)? That form spares passes over the ``t`` rows
    of the activations and pays with passes over the ``q_rank + kv_rank``
    rows of the up-projections' weights and a second up-projection of the
    queries: it wants a sequence three times as long as the latents have
    rows, and heads of whole lanes, because ``lanes`` zero columns a head
    would ride on five of its products (:func:`mla_attention` has the
    readings both ways)."""
    return not lanes and t >= 3 * (q_rank + kv_rank)


_GROUP_LANES = 1024  # the widest block of heads one product of q or k writes


def _latent_qkv_rows(p: Dict[str, Array], x: Array, cfg: Any, lanes: int
                     ) -> Tuple[Array, Array, Array]:
    """Latent attention's ``q, k (T, heads * width)`` and ``v (T, heads *
    v_head_dim)`` in the rows :func:`~byzpy_tpu.ops.pallas_attention.
    causal_attention` reads, ``width = nope + rope + lanes``: no activation
    takes a third axis on the way. What the three-axis form cuts, pads and
    joins a position is cut, padded and joined here on the WEIGHTS' columns,
    a head at a time (768 or 512 rows, once a call; the gradients come back
    through the same small cuts):

    * ``v = c_kv @ w_v``, ``w_v`` the value columns of ``w_kvb``;
    * ``k = c_kv @ w_k + k_r``: ``w_k`` the no-position columns of
      ``w_kvb`` with zero columns in every head's rotary and blank places,
      ``k_r`` the turned shared key at one head's width, zero elsewhere,
      side by side for the heads (adding an exact zero is exact);
    * ``q = (c_q @ w_q) * C + (c_q @ w_q') * S``: ``w_q'`` is ``w_q`` with
      each rotary pair's halves swapped and the other columns zero; ``C`` is
      1 on the no-position columns and the cosine on the rotary ones, ``S``
      0, minus the sine on a pair's first half and the sine on its second:
      :func:`rotary`'s 2 x 2 turn, the same two products and one sum an
      element in float32, written on two axes.

    q and k are made a GROUP of heads a product (as many whole heads as
    ``_GROUP_LANES`` hold: four of 256), each group's block written where
    the kernels read it. ``C``, ``S`` and ``k_r`` are ``(T, width)``; a
    product reads them laid side by side for its heads, and the compiler
    writes that out in full: over all twenty heads at once three chains of
    twenty updates of ``(T, 5120)`` a forward (17.8 ms of the GLM step) and
    84 MB of table beside each query product's operands; a head at a time
    nothing to lay out, and sixty products a call to trace and compile.
    The layer's forward and backward alone on a v5e at GLM's sizes (PERF.md,
    PR 54): 11.13 ms a head at a time, 10.91 two, 10.77 four, 12.22 ten,
    11.50 all twenty (the three-axis form 12.27).

    The tables and the cut weights pass an ``optimization_barrier``: the
    tables so that the cosines are computed once a call and not again
    inside every product that reads them, the weights so that the compiler
    does not pull the heads' axis back through the products (under ``vmap``
    it did, and transposed four cotangents of ``T`` rows to do so)."""
    t = x.shape[0]
    heads, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
    half, width = rope // 2, nope + rope + lanes
    per = max(n for n in range(1, heads + 1)
              if heads % n == 0 and (n == 1 or n * width <= _GROUP_LANES))

    def grouped(w, before, behind):  # (rank, heads, n) -> (groups, rank, per * (before + n + behind))
        w = jnp.pad(w, ((0, 0), (0, 0), (before, behind)))
        return jnp.swapaxes(w.reshape(w.shape[0], heads // per, -1), 0, 1)

    def swapped(w):  # a rotary pair's two halves change places, along the last axis
        return jnp.concatenate([w[..., half:], w[..., :half]], axis=-1)

    def side_by_side(blocks):  # whole-lane blocks of T rows, written in place
        return jnp.concatenate(blocks, axis=1)

    with jax.named_scope("model.mla_latent"):
        w = {name: p[name].astype(x.dtype)
             for name in ("w_qa", "w_qb", "w_kva", "w_kr", "w_kvb")}
        with jax.named_scope("model.rotary"):
            cos, sin = _turn_cos_sin(t, rope, cfg.rope_theta, cfg.rope_scaling)
            around = ((0, 0), (nope, lanes))
            tables = (jnp.pad(jnp.concatenate([cos, cos], axis=1), around, constant_values=1.0),
                      jnp.pad(jnp.concatenate([-sin, sin], axis=1), around))
            cos, sin = jax.lax.optimization_barrier(tuple(a.astype(x.dtype) for a in tables))
        w_qb = w["w_qb"].reshape(-1, heads, nope + rope)
        w_kvb = w["w_kvb"].reshape(-1, heads, nope + vd)
        w_kr = jnp.pad(w["w_kr"], ((0, 0), (nope, lanes)))
        w_q, w_q_swapped, w_k, w_v, w_kr, w_kr_swapped = jax.lax.optimization_barrier((
            grouped(w_qb, 0, lanes), grouped(swapped(w_qb[..., nope:]), nope, lanes),
            grouped(w_kvb[..., :nope], 0, rope + lanes),
            w_kvb[..., nope:].reshape(w_kvb.shape[0], -1),
            w_kr, jnp.pad(swapped(w["w_kr"]), ((0, 0), (nope, lanes)))))
        c_q = rms_norm(x @ w["w_qa"], p["q_norm_scale"], cfg.rms_norm_eps)
        c_kv = rms_norm(x @ w["w_kva"], p["kv_norm_scale"], cfg.rms_norm_eps)
        with jax.named_scope("model.rotary"):
            k_rope = side_by_side([(x @ w_kr) * cos + (x @ w_kr_swapped) * sin] * per)
            cos, sin = side_by_side([cos] * per), side_by_side([sin] * per)
            q = side_by_side([(c_q @ w_q[g]) * cos + (c_q @ w_q_swapped[g]) * sin
                              for g in range(heads // per)])
        k = side_by_side([c_kv @ w_k[g] + k_rope for g in range(heads // per)])
        v = c_kv @ w_v
    return q, k, v


__all__ = [
    "attention_proj",
    "YarnScaling",
    "blocked_causal_attention",
    "causal_depthwise_conv",
    "conv_silu",
    "cross_entropy",
    "gated_short_conv",
    "mla_attention",
    "rms_norm",
    "rms_norm_one_plus",
    "rotary",
    "token_embedding",
]
