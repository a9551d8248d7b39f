"""Plain reference of the Xing4.0-29B-A4B configuration (``model_type:
xing4_0``): its forward pass, loss and gradients, and the robust rounds
followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the equations of
the mHC paper (arXiv:2512.24880, manifold-constrained hyper-connections, on
Hyper-Connections, arXiv:2409.19606), the DeepSeek-V3 report
(arXiv:2412.19437: multi-head latent attention in section 2.1.1, the
sigmoid router with normalised top-k in 2.1.2) and YaRN (arXiv:2309.00071),
with Xing4.0's ``config.json`` for every size. Nothing here imports the
program (``byzpy_tpu``); weights come from ``chipbench.seeded_xing4``.

Deliberately NOT the forms the program computes in: a hyper-connection is
written position by position on the ``(n, hidden)`` streams of ONE position
(the norm of the flattened streams, then the projection; the Sinkhorn's 20
iterations an explicit loop of matrix row and column sums; ``H_res X`` and
``H_post^T y`` as a matrix product and an outer product) and mapped over
the positions, differentiated by ``jax.grad`` with no rule of its own;
attention is a head's full ``(T, T)`` score matrix, one head at a time, at
its published widths (192 for queries and keys, 128 for values: nothing is
padded); YaRN's frequencies are computed from the definition in float64 and
applied as a complex rotation; every held expert multiplies every token
under a dense mask (``chipbench.reference_glm4_moe_lite.moe_dense_mask``:
the same DeepSeek-V3 expert layer). The same share as the configuration:
the router scores all ``n_routed_experts``, a token's routed part sums those
of its top-k that are among ``held_experts``, the vocabulary is the slice.

Departures from the published description, each the configuration's
``assumed``: the streams enter as ``n`` copies of the embedded token and
leave as their sum (Hyper-Connections'; the source's config does not say);
the Sinkhorn normalises columns first, then rows, with ``hc_eps`` added to
each sum; the hyper-connection's norm has no learned scale; ``mscale_all_dim``
multiplies the softmax scale by ``(0.1 mscale_all_dim ln factor + 1)^2``
(DeepSeek-V2 / V3's reading); rotary pairs are ``(i, i + 32)``; the router's
correction bias is zero; the multi-token-prediction module is left out.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block, the head). ``dtype`` is the type
activations and weights are computed in (the configuration's float32; lower
for the reading a limit is set from); the hyper-connections' mappings are
float32 whatever it is, as the configuration states.
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import sgd_momentum
from chipbench.reference_glm4_moe_lite import moe_dense_mask, rms_norm

# --------------------------------------------------------------------------
# hyper-connections, one position at a time
# --------------------------------------------------------------------------


def sinkhorn_loop(logits, iters: int, eps: float):
    """``(n, n)`` logits of one position -> the matrix after ``iters``
    rounds of (every column over its sum + ``eps``, then every row over its
    sum + ``eps``), from ``exp`` of the logits."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)  # a column's sum runs over the rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def mappings_of_position(p: Dict[str, Any], which: str, streams, arch: Dict[str, Any]):
    """``streams (n, hidden)`` of one position -> ``H_pre (n,)``, ``H_post
    (n,)``, ``H_res (n, n)`` of the ``which`` hyper-connection, float32."""
    n = streams.shape[0]
    flat = streams.reshape(-1).astype(jnp.float32)
    normed = flat / jnp.sqrt(jnp.mean(flat * flat) + float(arch["rms_norm_eps"]))
    projected = normed @ p[f"{which}_hc_phi"]
    alpha, b = p[f"{which}_hc_alpha"], p[f"{which}_hc_b"]
    pre = alpha[0] * projected[:n] + b[:n]
    post = alpha[1] * projected[n:2 * n] + b[n:2 * n]
    res = (alpha[2] * projected[2 * n:] + b[2 * n:]).reshape(n, n)
    res = jnp.clip(res, float(arch["mhc_h_res_clamp_min"]), float(arch["mhc_h_res_clamp_max"]))
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn_loop(res, int(arch["hc_sinkhorn_iters"]), float(arch["hc_eps"])))


def hyper_connected(p: Dict[str, Any], which: str, x, sublayer: Callable, arch: Dict[str, Any]):
    """``x (T, n, hidden)`` through the ``which`` sublayer of a block:
    ``X' = H_res X + H_post^T F(RMSNorm(H_pre X))`` at every position.
    ``sublayer`` maps ``(T, hidden) -> (T, hidden)`` or ``-> (out, aux)``."""
    maps = jax.vmap(lambda streams: mappings_of_position(p, which, streams, arch))
    pre, post, res = maps(x)
    u = jax.vmap(lambda h_pre, streams: h_pre @ streams.astype(jnp.float32))(pre, x)
    y = sublayer(rms_norm(u.astype(x.dtype), p[f"{which}_norm_scale"],
                          float(arch["rms_norm_eps"])))
    y, aux = y if isinstance(y, tuple) else (y, None)
    out = jax.vmap(lambda h_res, h_post, streams, y_t: (
        h_res @ streams.astype(jnp.float32) + jnp.outer(h_post, y_t.astype(jnp.float32))))(
            res, post, x, y)
    return out.astype(x.dtype), aux


# --------------------------------------------------------------------------
# latent attention under YaRN
# --------------------------------------------------------------------------


def yarn_frequencies(dim: int, theta: float, scaling: Dict[str, Any]) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN, float64, from the
    definition: pair ``i`` turns by ``theta_i = theta^(-2 i / dim)`` a
    position; ``r(beta) = dim ln(original / (2 pi beta)) / (2 ln theta)`` is
    the pair that makes ``beta`` turns over the original context; ``low =
    floor(r(beta_fast))``, ``high = ceil(r(beta_slow))``; pair ``i`` keeps
    ``m_i = 1 - clip((i - low) / (high - low), 0, 1)`` of its own frequency
    and takes ``1 - m_i`` of ``theta_i / factor``."""
    pairs = np.arange(dim // 2, dtype=np.float64)
    plain = float(theta) ** (-2.0 * pairs / dim)
    original = float(scaling["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(original / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(scaling["beta_slow"]))), dim - 1)
    keep = 1.0 - np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * keep + plain / float(scaling["factor"]) * (1.0 - keep)


def _mscale(factor: float, by: float) -> float:
    return 0.1 * by * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotate(x, frequencies: np.ndarray, magnitude: float):
    """Rotary positions of ``x (T, ..., dim)``: the pair (``x[..., i]``,
    ``x[..., i + dim / 2]``) is a complex number, multiplied by ``magnitude
    exp(j t frequencies[i])`` at position ``t``."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = np.arange(t, dtype=np.float64)[:, None] * frequencies[None, :]
    turn = jnp.asarray((magnitude * np.exp(1j * angle)).astype(np.complex64))
    turn = turn.reshape(t, *(1,) * (x.ndim - 2), half)
    z = jax.lax.complex(x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32))
    z = z * turn
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1).astype(x.dtype)


def mla_full(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """Multi-head latent attention of one sequence ``(T, hidden)`` by a
    head's full causal score matrix, one head at a time, positions under
    YaRN: the scores are scaled by ``(nope + rope)^-1/2`` times ``(0.1
    mscale_all_dim ln factor + 1)^2``."""
    t = x.shape[0]
    heads, nope, rope, vd = (int(arch[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps, scaling = float(arch["rms_norm_eps"]), arch["rope_scaling"]
    factor = float(scaling["factor"])
    frequencies = yarn_frequencies(rope, float(arch["rope_theta"]), scaling)
    magnitude = _mscale(factor, float(scaling["mscale"])) / _mscale(
        factor, float(scaling["mscale_all_dim"]))
    scale = _mscale(factor, float(scaling["mscale_all_dim"])) ** 2 / math.sqrt(nope + rope)
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    c_q = rms_norm(x @ cast(p["w_qa"]), p["q_norm_scale"], eps)
    q = (c_q @ cast(p["w_qb"])).reshape(t, heads, nope + rope)
    # kv_a_proj_with_mqa as its two column blocks: the latent | the rotary key
    c_kv = rms_norm(x @ cast(p["w_kva"]), p["kv_norm_scale"], eps)
    k_rope = rotate(x @ cast(p["w_kr"]), frequencies, magnitude)  # (T, rope): every head's
    up = (c_kv @ cast(p["w_kvb"])).reshape(t, heads, nope + vd)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], frequencies, magnitude)
    k_nope, v = up[..., :nope], up[..., nope:]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        qn, qr, kn, v_h = args  # (T, nope), (T, rope), (T, nope), (T, vd)
        scores = (qn @ kn.T + qr @ k_rope.T).astype(jnp.float32) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights.astype(x.dtype) @ v_h

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    out = jax.lax.map(head, (by_head(q_nope), by_head(q_rope), by_head(k_nope), by_head(v)))
    return by_head(out).reshape(t, heads * vd) @ cast(p["w_o"])


# --------------------------------------------------------------------------
# the chain
# --------------------------------------------------------------------------


def block(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """The streams of one sequence ``x (T, n, hidden)`` through one block:
    the hyper-connected latent attention, then the hyper-connected dense MLP
    or expert layer; returns ``(x, held experts' counts or None)``."""
    x, _ = jax.checkpoint(lambda p_, x_: hyper_connected(
        p_, "attn", x_, partial(mla_full, p_, arch=arch), arch))(p, x)
    if "router" in p:
        feed = lambda p_: partial(moe_dense_mask, p_, arch=arch)  # noqa: E731
    else:
        def feed(p_):
            def mlp(z):
                gate = z @ p_["w_gate"].astype(z.dtype)
                return ((gate * jax.nn.sigmoid(gate)) * (z @ p_["w_up"].astype(z.dtype))
                        ) @ p_["w_down"].astype(z.dtype)  # down(silu(gate x) * up x)

            return mlp
    return jax.checkpoint(lambda p_, x_: hyper_connected(p_, "ffn", x_, feed(p_), arch))(p, x)


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """Next-token cross-entropy (mean over positions) of ``tokens, targets:
    (B, T)`` and, per expert layer, the tokens each held expert got (summed
    over the batch's sequences, which the expert layer sees end to end)."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        n = int(arch["hc_mult"])
        embedded = params[segments[0]]["embedding"][tokens].astype(dtype)  # (B, T, hidden)
        logits, counts = [], []
        for sequence in embedded:
            x = jnp.stack([sequence] * n, axis=1)  # entry: a copy in every stream
            got_of_sequence = []
            for segment in segments[1:-1]:
                x, got = block(params[segment], x, arch)
                if got is not None:
                    got_of_sequence.append(got)
            counts.append(jnp.stack(got_of_sequence))
            head = params[segments[-1]]
            h = jnp.sum(x.astype(jnp.float32), axis=1).astype(dtype)  # exit: the streams' sum
            logits.append(rms_norm(h, head["norm_scale"], float(arch["rms_norm_eps"]))
                          @ head["w_head"].astype(dtype))
        logits = jnp.stack(logits).astype(jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - picked)
    return loss, sum(counts)


# --------------------------------------------------------------------------
# the rounds
# --------------------------------------------------------------------------


def follow_rounds(
    arch: Dict[str, Any],
    params0: Dict[str, Dict[str, Any]],
    batches: Sequence[Tuple[Any, Any]],
    *,
    n_nodes: int,
    n_byzantine: int,
    aggregate: Callable,
    attack: Callable,
    lr: float,
    momentum: float,
    dtype=jnp.float32,
    precision: str = "highest",
    report: Callable[..., None] = lambda **facts: None,
) -> Dict[str, Any]:
    """Robust parameter-server rounds from ``params0``, one per entry of
    ``batches`` (``xs, ys: (n, B, T)``), as ``chipbench.
    reference_nemotron_h.follow_rounds`` runs them: every honest worker's
    loss and whole gradient (``jax.grad``), one worker at a time, its rows
    kept on the HOST; then, leaf by leaf, the h honest rows of that leaf go
    back to the device, the byzantine rows are made from them, the (n,
    leaf) matrix is aggregated, and SGD with momentum updates the leaf.

    ``params0`` is consumed. Returns each round's honest-mean loss, the
    tokens every held expert got from every honest worker (``(rounds, h,
    expert layers, held)``), the norm of every leaf of the first round's
    aggregate, and the parameters after the last round (on the device);
    no ``loss_terms``: the loss has one."""
    h = n_nodes - n_byzantine
    worker = jax.jit(jax.value_and_grad(
        partial(loss_and_counts, arch=arch, dtype=dtype, precision=precision), has_aux=True))

    @partial(jax.jit, donate_argnums=(0, 1))
    def leaf_round(leaf, trace, rows):
        honest = jnp.stack(rows).astype(jnp.float32)
        matrix = honest
        if n_byzantine:
            matrix = jnp.concatenate([honest, attack(honest, n_byzantine)], axis=0)
        agg = aggregate(matrix)
        flat, trace = sgd_momentum(leaf.reshape(-1), trace, agg, lr=lr, momentum=momentum)
        return flat.reshape(leaf.shape), trace, jnp.sqrt(jnp.sum(jnp.square(agg)))

    leaves, treedef = jax.tree_util.tree_flatten(params0)
    del params0
    traces = [jnp.zeros((leaf.size,), jnp.float32) for leaf in leaves]
    losses: List[float] = []
    counts: List[Any] = []
    first_norms: List[float] = []
    for xs, ys in batches:
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        rows, round_losses, round_counts = [], [], []
        t0, t_grad, t_host = time.perf_counter(), 0.0, 0.0
        for i in range(h):
            t1 = time.perf_counter()
            (value, got), grads = worker(params, xs[i], ys[i])
            grads = jax.tree_util.tree_leaves(grads)
            round_losses.append(float(value))
            t2 = time.perf_counter()
            for leaf in grads:
                leaf.copy_to_host_async()
            rows.append([np.asarray(leaf).reshape(-1) for leaf in grads])
            del grads
            round_counts.append(np.asarray(got))
            t_grad, t_host = t_grad + (t2 - t1), t_host + (time.perf_counter() - t2)
        del params
        t_rows = time.perf_counter()
        norms = []

        def to_device(j):  # a leaf's h rows, each on its own way to the device
            return [jax.device_put(rows[i][j]) for i in range(h)]

        coming = to_device(0)
        for j in range(len(leaves)):
            here, coming = coming, (to_device(j + 1) if j + 1 < len(leaves) else None)
            leaves[j], traces[j], norm_j = leaf_round(leaves[j], traces[j], here)
            norms.append(norm_j)
        del here
        del rows
        jax.block_until_ready(leaves)
        report(reference_round_s=time.perf_counter() - t0, workers_gradients_s=t_grad,
               rows_to_host_s=t_host, leaf_rounds_s=time.perf_counter() - t_rows)
        if not first_norms:
            first_norms = [float(v) for v in norms]
        losses.append(sum(round_losses) / h)
        counts.append(np.stack(round_counts))
    return {
        "losses": losses,
        "held_expert_tokens": np.stack(counts),
        "first_aggregate_leaf_norms": first_norms,
        "params": jax.tree_util.tree_unflatten(treedef, leaves),
    }
