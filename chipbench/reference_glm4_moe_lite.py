"""Plain reference of the GLM-4.7-Flash configuration (``model_type:
glm4_moe_lite``): its forward pass, its two-term loss and gradients, and
the robust rounds followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the equations of
the DeepSeek-V3 report (arXiv:2412.19437: multi-head latent attention in
section 2.1.1, the sigmoid router with normalised top-k in 2.1.2,
multi-token prediction in 2.2), which the GLM-4.5 report follows, with
GLM-4.7-Flash's ``config.json`` for every size. Nothing here imports the
program (``byzpy_tpu``); weights come from ``chipbench.
seeded_glm4_moe_lite``.

Deliberately NOT the forms the program computes in: attention is a head's
full ``(T, T)`` score matrix, one head at a time; rotary positions are a
complex rotation, from the definition; every held expert multiplies every
token under a dense mask; the MTP module runs on the T - 1 positions that
have a target, from the shifted sequence itself. The same share as the
configuration: the router scores all ``n_routed_experts``, a token's routed
part sums those of its top-k that are among ``held_experts``, the
vocabulary is the slice.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block, ``segNN_mtp``, the head).
``dtype`` is the type activations and weights are computed in (the
configuration's float32; lower for the reading a limit is set from).
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


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rotate(x, theta: float):
    """Rotary positions of ``x (T, ..., dim)`` from the definition: the pair
    (``x[..., i]``, ``x[..., i + dim / 2]``) is a complex number, multiplied
    by ``exp(j t theta^(-2 i / dim))`` at position ``t``."""
    t, dim = x.shape[0], x.shape[-1]
    half = dim // 2
    i = np.arange(half, dtype=np.float64)
    angle = np.arange(t, dtype=np.float64)[:, None] * theta ** (-2.0 * i / dim)[None, :]
    turn = jnp.asarray(np.exp(1j * angle).astype(np.complex64))  # (T, half)
    turn = turn.reshape(t, *(1,) * (x.ndim - 2), half)
    z = jax.lax.complex(x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32))
    z = z * turn
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1).astype(x.dtype)


def mla_full(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """Multi-head latent attention of one sequence ``(T, hidden)`` by a
    head's full causal score matrix, one head at a time."""
    t = x.shape[0]
    heads, nope, rope, vd = (int(arch[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    eps, theta = float(arch["rms_norm_eps"]), float(arch["rope_theta"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    c_q = rms_norm(x @ cast(p["w_qa"]), p["q_norm_scale"], eps)
    q = (c_q @ cast(p["w_qb"])).reshape(t, heads, nope + rope)
    # kv_a_proj_with_mqa as its two column blocks: the latent | the rotary key
    c_kv = rms_norm(x @ cast(p["w_kva"]), p["kv_norm_scale"], eps)
    k_rope = rotate(x @ cast(p["w_kr"]), theta)  # (T, rope): one a position, every head's
    up = (c_kv @ cast(p["w_kvb"])).reshape(t, heads, nope + vd)
    q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], theta)
    k_nope, v = up[..., :nope], up[..., nope:]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def head(args):
        qn, qr, kn, v_h = args  # (T, nope), (T, rope), (T, nope), (T, vd)
        scores = (qn @ kn.T + qr @ k_rope.T).astype(jnp.float32) / math.sqrt(nope + rope)
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights.astype(x.dtype) @ v_h

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    out = jax.lax.map(head, (by_head(q_nope), by_head(q_rope), by_head(k_nope), by_head(v)))
    return by_head(out).reshape(t, heads * vd) @ cast(p["w_o"])


def _gated(x, gate, up, down):
    g = x @ gate
    return ((g * jax.nn.sigmoid(g)) * (x @ up)) @ down  # down(silu(gate x) * up x)


def moe_dense_mask(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """``(out, tokens each held expert got)`` for tokens ``x (T, hidden)``:
    sigmoid scores over all experts, the top-k a token, their scores
    normalised to sum 1 and scaled; every held expert runs on every token
    and a 0/1 mask keeps the tokens that chose it; the shared expert runs
    on every token."""
    first, held = (int(v) for v in arch["held_experts"])
    top_k, scale = int(arch["num_experts_per_tok"]), float(arch["routed_scaling_factor"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    scores = jax.nn.sigmoid((x @ cast(p["router"])).astype(jnp.float32))  # (T, E)
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    chosen = scores >= kth  # (T, E): the top-k (scores are distinct floats)
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    out = _gated(x, cast(p["shared_gate"]), cast(p["shared_up"]), cast(p["shared_down"]))
    counts = []
    for e in range(held):
        expert = _gated(x, cast(p["experts_gate"][e]), cast(p["experts_up"][e]),
                        cast(p["experts_down"][e]))
        out = out + weights[:, first + e, None].astype(x.dtype) * expert
        counts.append(jnp.sum(chosen[:, first + e]))
    return out, jnp.stack(counts)


def block(p: Dict[str, Any], h, arch: Dict[str, Any]):
    """``h (B, T, hidden)`` through one block: ``h + MLA(rms h)``, then
    ``h + FFN(rms h)``; returns ``(h, held experts' counts or None)``."""
    eps = float(arch["rms_norm_eps"])
    attend = jax.checkpoint(partial(mla_full, arch=arch))
    h = h + jnp.stack([attend(p, seq) for seq in rms_norm(h, p["attn_norm_scale"], eps)])
    normed = rms_norm(h, p["ffn_norm_scale"], eps)
    if "router" not in p:
        cast = lambda w: w.astype(h.dtype)  # noqa: E731
        return h + _gated(normed, cast(p["w_gate"]), cast(p["w_up"]), cast(p["w_down"])), None
    out, got = jax.checkpoint(partial(moe_dense_mask, arch=arch))(
        p, normed.reshape(-1, normed.shape[-1]))
    return h + out.reshape(h.shape), got


def _cross_entropy(logits, targets):
    logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
    return jnp.mean(lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """``CE(next token) + mtp_loss_weight x CE(the token after next)`` of
    ``tokens, targets: (B, T)`` (``targets[t]`` is token ``t + 1``), each a
    mean over its positions, and beside it ``(per expert layer the tokens
    each held expert got, the two terms)``."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        eps = float(arch["rms_norm_eps"])
        table = params[segments[0]]["embedding"]
        h = table[tokens].astype(dtype)  # (B, T, hidden)
        counts = []
        for segment in segments[1:-2]:
            h, got = block(params[segment], h, arch)
            if got is not None:
                counts.append(got)
        head = params[segments[-1]]
        w_head = head["w_head"].astype(dtype)
        main = _cross_entropy(rms_norm(h, head["norm_scale"], eps) @ w_head, targets)
        # multi-token prediction: position t (0 .. T - 2) joins the stream at
        # t with the embedding of token t + 1, which is targets[t], and
        # predicts token t + 2, which is targets[t + 1]
        mtp = params[segments[-2]]
        joined = jnp.concatenate([
            rms_norm(h[:, :-1], mtp["h_norm_scale"], eps),
            rms_norm(table[targets[:, :-1]].astype(dtype), mtp["e_norm_scale"], eps)], axis=-1)
        ahead, got = block(mtp, joined @ mtp["w_eh"].astype(dtype), arch)
        counts.append(got)
        second = _cross_entropy(
            rms_norm(ahead, mtp["head_norm_scale"], eps) @ w_head, targets[:, 1:])
        loss = main + float(arch["mtp_loss_weight"]) * second
    return loss, (jnp.stack(counts), jnp.stack([main, second]))


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
    loss and whole gradient, one worker at a time, its rows kept on the
    HOST; then, leaf by leaf, the h honest rows of that leaf go back to the
    device, the byzantine rows are made from them, the (n, leaf) matrix is
    aggregated, and SGD with momentum updates the leaf.

    ``params0`` is consumed. Returns each round's honest-mean loss and its
    two terms (``loss_terms``: ``(rounds, 2)``, next token and the token
    after), the tokens every held expert got from every honest worker
    (``(rounds, h, expert layers, held)``, the MTP module's last), the norm
    of every leaf of the first round's aggregate, and the parameters after
    the last round (on the device)."""
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
    terms: List[Any] = []
    counts: List[Any] = []
    first_norms: List[float] = []
    for xs, ys in batches:
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        rows, round_losses, round_terms, round_counts = [], [], [], []
        t0, t_grad, t_host = time.perf_counter(), 0.0, 0.0
        for i in range(h):
            t1 = time.perf_counter()
            (value, (got, two)), grads = worker(params, xs[i], ys[i])
            grads = jax.tree_util.tree_leaves(grads)
            round_losses.append(float(value))
            t2 = time.perf_counter()
            for leaf in grads:
                leaf.copy_to_host_async()
            rows.append([np.asarray(leaf).reshape(-1) for leaf in grads])
            del grads
            round_counts.append(np.asarray(got))
            round_terms.append(np.asarray(two, np.float64))
            t_grad, t_host = t_grad + (t2 - t1), t_host + (time.perf_counter() - t2)
        del params
        t_rows = time.perf_counter()
        norms = []

        def to_device(j):  # a leaf's h rows, each on its own way to the device
            return [jax.device_put(rows[i][j]) for i in range(h)]

        coming = to_device(0)
        for j in range(len(leaves)):
            here, coming = coming, (to_device(j + 1) if j + 1 < len(leaves) else None)
            leaves[j], traces[j], norm = leaf_round(leaves[j], traces[j], here)
            norms.append(norm)
        del here
        del rows
        jax.block_until_ready(leaves)
        report(reference_round_s=time.perf_counter() - t0, workers_gradients_s=t_grad,
               rows_to_host_s=t_host, leaf_rounds_s=time.perf_counter() - t_rows)
        if not first_norms:
            first_norms = [float(v) for v in norms]
        losses.append(sum(round_losses) / h)
        terms.append(np.mean(round_terms, axis=0))
        counts.append(np.stack(round_counts))
    return {
        "losses": losses,
        "loss_terms": np.stack(terms),
        "held_expert_tokens": np.stack(counts),
        "first_aggregate_leaf_norms": first_norms,
        "params": jax.tree_util.tree_unflatten(treedef, leaves),
    }
