"""Plain reference of the Nemotron-H configuration: its forward pass, loss
and gradients, and the robust rounds followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the equations
of NVIDIA-Nemotron-3-Nano's ``config.json`` (``model_type: nemotron_h``)
and the Mamba-2 paper (Dao & Gu 2024, the recurrence of section 2).
Nothing here imports the program (``byzpy_tpu``); weights and data come
from ``chipbench.seeded_nemotron_h``.

Deliberately NOT the forms the program computes in: Mamba-2 is the
recurrence step by step (``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``,
``y_t = C_t h_t + D x_t``), not the chunked form; attention is the full
score matrix of a key/value group; every held expert multiplies every
token under a dense mask. The same share as the configuration: the router
scores all ``n_routed_experts``, a token's routed part sums those of its
top-k that are among ``held_experts``, the vocabulary is the slice.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block of ``pattern``, the head).
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


def mamba2_recurrent(p: Dict[str, Any], x, arch: Dict[str, Any], *, inner: int = 64):
    """One sequence ``(T, hidden)`` through a Mamba-2 mixer, the state
    carried position by position. Positions go ``inner`` at a time and
    each such stretch is rematerialised in the backward pass: kept whole,
    the ``T`` states of ``heads x head_dim x state`` would be 8.6 GB a
    block at the published sizes."""
    t = x.shape[0]
    heads, hd = int(arch["mamba_num_heads"]), int(arch["mamba_head_dim"])
    groups, n, k = int(arch["n_groups"]), int(arch["ssm_state_size"]), int(arch["conv_kernel"])
    d_inner = heads * hd
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    z, xbc, dt = x @ cast(p["w_z"]), x @ cast(p["w_xbc"]), x @ cast(p["w_dt"])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    conv = cast(p["conv_b"])[None, :] + sum(
        cast(p["conv_w"])[j][None, :] * padded[j: j + t] for j in range(k))
    xbc = conv * jax.nn.sigmoid(conv)  # SiLU
    xs = xbc[:, :d_inner].reshape(t, heads, hd).astype(jnp.float32)
    b = xbc[:, d_inner: d_inner + groups * n].reshape(t, groups, n).astype(jnp.float32)
    c = xbc[:, d_inner + groups * n:].reshape(t, groups, n).astype(jnp.float32)
    b = jnp.repeat(b, heads // groups, axis=1)  # a group's B and C serve its heads
    c = jnp.repeat(c, heads // groups, axis=1)
    delta = jnp.logaddexp(dt.astype(jnp.float32) + p["dt_bias"], 0.0)  # softplus
    a = -jnp.exp(p["a_log"])

    def position(state, at):
        x_t, b_t, c_t, d_t = at
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + d_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def stretch(state, at):
        return jax.lax.scan(position, state, at)

    pad = -t % inner
    seq = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
        (t + pad) // inner, inner, *v.shape[1:]) for v in (xs, b, c, delta)]
    _, y = jax.lax.scan(stretch, jnp.zeros((heads, hd, n), jnp.float32), tuple(seq))
    y = y.reshape(t + pad, heads, hd)[:t] + p["d_skip"][:, None] * xs
    y = y.reshape(t, d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(t, groups, d_inner // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + float(arch["norm_eps"]))
    y = (y.reshape(t, d_inner) * p["gate_norm_scale"]).astype(x.dtype)
    return y @ cast(p["w_out"])


def attention_full(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """Causal softmax attention by the full ``(T, T)`` score matrix, one
    key/value group (its query heads together) at a time."""
    t = x.shape[0]
    heads, kv, hd = (int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]),
                     int(arch["head_dim"]))
    per = heads // kv
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    q = (x @ cast(p["w_q"])).reshape(t, kv, per, hd)
    k = (x @ cast(p["w_k"])).reshape(t, kv, hd)
    v = (x @ cast(p["w_v"])).reshape(t, kv, hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def group(q_g, k_g, v_g):  # (T, per, hd), (T, hd), (T, hd)
        scores = jnp.einsum("qrd,kd->rqk", q_g, k_g).astype(jnp.float32) / math.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return jnp.einsum("rqk,kd->qrd", weights.astype(x.dtype), v_g)

    out = jnp.stack([group(q[:, g], k[:, g], v[:, g]) for g in range(kv)], axis=1)
    return out.reshape(t, heads * hd) @ cast(p["w_o"])


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

    def relu2(v):
        return jnp.square(jnp.maximum(v, 0))

    out = relu2(x @ cast(p["shared_up"])) @ cast(p["shared_down"])
    counts = []
    for e in range(held):
        expert = relu2(x @ cast(p["experts_up"][e])) @ cast(p["experts_down"][e])
        out = out + weights[:, first + e, None].astype(x.dtype) * expert
        counts.append(jnp.sum(chosen[:, first + e]))
    return out, jnp.stack(counts)


_MIXERS = {"M": mamba2_recurrent, "*": attention_full}


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """Next-token cross-entropy (mean over positions) of ``tokens, targets:
    (B, T)`` and, per expert block, the tokens each held expert got."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        eps = float(arch["norm_eps"])
        h = params[segments[0]]["embedding"][tokens].astype(dtype)  # (B, T, hidden)
        counts = []
        for segment, kind in zip(segments[1:-1], arch["pattern"]):
            p = params[segment]
            normed = rms_norm(h, p["norm_scale"], eps)
            block = jax.checkpoint(partial(_MIXERS[kind], arch=arch)) if kind in _MIXERS else None
            if block is not None:
                h = h + jnp.stack([block(p, seq) for seq in normed])
            else:
                flat = normed.reshape(-1, normed.shape[-1])
                out, got = jax.checkpoint(partial(moe_dense_mask, arch=arch))(p, flat)
                h = h + out.reshape(h.shape)
                counts.append(got)
        head = params[segments[-1]]
        logits = (rms_norm(h, head["norm_scale"], eps) @ head["w_head"].astype(dtype))
        logits = logits.astype(jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - picked)
    return loss, (jnp.stack(counts) if counts else jnp.zeros((0, 0), jnp.int32))


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
    ``batches`` (``xs, ys: (n, B, T)``). A round: every honest worker's
    loss and whole gradient, one worker at a time, its rows kept on the
    HOST (h rows of d floats do not fit beside the parameters); then,
    leaf by leaf (a block of columns: the aggregate and the attack here
    treat every column alone), the h honest rows of that leaf go back to
    the device, the byzantine rows are made from them, the (n, leaf)
    matrix is aggregated, and SGD with momentum updates the leaf.

    ``params0`` is consumed (its buffers are donated leaf by leaf).
    Returns each round's honest-mean loss, the tokens every held expert
    got from every honest worker (``(rounds, h, expert blocks, held)``),
    the norm of every leaf of the first round's aggregate, and the
    parameters after the last round (on the device). ``report`` is told
    where each round's seconds went."""
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
        counts.append(np.stack(round_counts))
    return {
        "losses": losses,
        "held_expert_tokens": np.stack(counts),
        "first_aggregate_leaf_norms": first_norms,
        "params": jax.tree_util.tree_unflatten(treedef, leaves),
    }
