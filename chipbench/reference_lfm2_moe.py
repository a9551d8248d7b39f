"""Plain reference of the LFM2-24B-A2B configuration (``model_type:
lfm2_moe``): its forward pass, loss and gradients, and the robust rounds
followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the equations of
the configuration (``chipbench/configs/lfm2-24b-ep8-ps.json``, from the
source's ``config.json`` and the LFM2 family's modelling code). Nothing
here imports the program (``byzpy_tpu``); weights come from
``chipbench.seeded_lfm2_moe``.

Deliberately NOT the forms the program computes in: the short convolution
is written from the definition, position by position (``c[t] = w[0] g[t -
2] + w[1] g[t - 1] + w[2] g[t]`` as three shifted products summed, the
shifted arrays made by concatenating zero rows), differentiated by
``jax.grad`` with no rule of its own; attention is a head's full ``(T,
T)`` score matrix, one head at a time, at its published width 64 (nothing
is padded, nothing shares a tile); the head norms are written from the
definition and the rotary turn is a complex rotation; every held expert
multiplies every token under a dense mask, with the ``1e-6`` in the
weights' denominator; the tied table is ONE array that ``jax.grad``
differentiates through both its uses (the gather and the product with its
transpose). The same share as the configuration: the router scores all
``num_experts``, a token's routed part sums those of its top-k that are
among ``held_experts``, the vocabulary is the slice.

Departures from the published description, each the configuration's
``assumed``: the table is tied; ``[B | C | X]`` are the projection's three
column blocks in this order; rotary pairs are ``(i, i + 32)``; the router's
bias is zero; the ``1e-6``.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block, the head, which holds the
final norm's weight alone). ``dtype`` is the type activations and weights
are computed in (the configuration's float32; lower for the reading a
limit is set from).
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
    t, half = x.shape[0], x.shape[-1] // 2
    frequencies = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    angle = np.arange(t, dtype=np.float64)[:, None] * frequencies[None, :]
    turn = jnp.asarray(np.exp(1j * angle).astype(np.complex64))
    turn = turn.reshape(t, *(1,) * (x.ndim - 2), half)
    z = jax.lax.complex(x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32))
    z = z * turn
    return jnp.concatenate([jnp.real(z), jnp.imag(z)], axis=-1).astype(x.dtype)


def short_conv(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """The gated short convolution of one sequence ``(T, hidden)``."""
    del arch
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    hidden = x.shape[1]
    projected = x @ cast(p["w_in"])
    b, c, xs = (projected[:, i * hidden:(i + 1) * hidden] for i in range(3))
    g = b * xs
    taps = cast(p["conv_w"])
    k = taps.shape[0]
    conv = jnp.zeros_like(g)
    for j in range(k):  # tap j reads position t - (k - 1) + j
        back = k - 1 - j
        moved = jnp.concatenate([jnp.zeros((back, hidden), g.dtype), g], axis=0)[:g.shape[0]]
        conv = conv + taps[j] * moved
    return (c * conv) @ cast(p["w_out"])


def attention_full(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """Grouped-query attention of one sequence ``(T, hidden)`` by a head's
    full causal score matrix, one head at a time: every query and key head
    normed over its own values, then turned by position."""
    t = x.shape[0]
    heads, kv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd, eps = x.shape[1] // heads, float(arch["norm_eps"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    q = (x @ cast(p["w_q"])).reshape(t, heads, hd)
    k = (x @ cast(p["w_k"])).reshape(t, kv, hd)
    v = (x @ cast(p["w_v"])).reshape(t, kv, hd)
    q = rotate(rms_norm(q, p["q_norm_scale"], eps), float(arch["rope_theta"]))
    k = rotate(rms_norm(k, p["k_norm_scale"], eps), float(arch["rope_theta"]))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    per = heads // kv

    @jax.checkpoint
    def head(args):
        q_h, k_h, v_h = args  # (T, hd) each
        scores = (q_h @ k_h.T).astype(jnp.float32) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights.astype(x.dtype) @ v_h

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    # query head h reads key/value head h // per
    out = jax.lax.map(head, (by_head(q), jnp.repeat(by_head(k), per, axis=0),
                             jnp.repeat(by_head(v), per, axis=0)))
    return by_head(out).reshape(t, heads * hd) @ cast(p["w_o"])


def _gated(x, gate, up, down):
    g = x @ gate
    return ((g * jax.nn.sigmoid(g)) * (x @ up)) @ down  # down(silu(gate x) * up x)


def moe_dense_mask(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """``(out, tokens each held expert got)`` for tokens ``x (T, hidden)``:
    sigmoid scores over all experts, the top-k a token, their scores over
    (their sum + ``router_denominator_eps``) and scaled; every held expert
    runs on every token and a 0/1 mask keeps the tokens that chose it. No
    shared expert."""
    first, held = (int(v) for v in arch["held_experts"])
    top_k, scale = int(arch["num_experts_per_tok"]), float(arch["routed_scaling_factor"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    scores = jax.nn.sigmoid((x @ cast(p["router"])).astype(jnp.float32))  # (T, E)
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    chosen = scores >= kth  # (T, E): the top-k (scores are distinct floats)
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                         + float(arch["router_denominator_eps"])) * scale
    out, counts = jnp.zeros_like(x), []
    for e in range(held):
        expert = _gated(x, cast(p["experts_gate"][e]), cast(p["experts_up"][e]),
                        cast(p["experts_down"][e]))
        out = out + weights[:, first + e, None].astype(x.dtype) * expert
        counts.append(jnp.sum(chosen[:, first + e]))
    return out, jnp.stack(counts)


def block(p: Dict[str, Any], h, arch: Dict[str, Any]):
    """``h (B, T, hidden)`` through one block: ``h + Op(rms h)``, then ``h +
    FF(rms h)``; which operator and which feed-forward is read off the
    block's leaves. Returns ``(h, held experts' counts or None)``."""
    eps = float(arch["norm_eps"])
    operator = attention_full if "w_q" in p else short_conv
    mix = jax.checkpoint(partial(operator, arch=arch))
    h = h + jnp.stack([mix(p, seq) for seq in rms_norm(h, p["operator_norm_scale"], eps)])
    normed = rms_norm(h, p["ffn_norm_scale"], eps)
    if "router" not in p:
        cast = lambda w: w.astype(h.dtype)  # noqa: E731
        return h + _gated(normed, cast(p["w_gate"]), cast(p["w_up"]), cast(p["w_down"])), None
    out, got = jax.checkpoint(partial(moe_dense_mask, arch=arch))(
        p, normed.reshape(-1, normed.shape[-1]))
    return h + out.reshape(h.shape), got


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """Next-token cross-entropy (mean over positions) of ``tokens, targets:
    (B, T)`` and, per expert layer, the tokens each held expert got. The
    table is read twice: rows of it embed the tokens, and its transpose
    makes the logits."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        table = params[segments[0]]["embedding"]  # the one array of both uses
        h = table[tokens].astype(dtype)  # (B, T, hidden)
        counts = []
        for segment in segments[1:-1]:
            h, got = block(params[segment], h, arch)
            if got is not None:
                counts.append(got)
        h = rms_norm(h, params[segments[-1]]["norm_scale"], float(arch["norm_eps"]))
        logits = (h @ table.astype(dtype).T).astype(jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - picked)
    return loss, jnp.stack(counts)


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
    loss and whole gradient (``jax.grad``; the table's holds both its
    uses), one worker at a time, its rows kept on the HOST; then, leaf by
    leaf, the h honest rows of that leaf go back to the device, the
    byzantine rows are made from them, the (n, leaf) matrix is aggregated,
    and SGD with momentum updates the leaf.

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
