"""Plain reference of the SmallThinker-21BA3B-Instruct configuration
(``model_name: smallthinker_21b_instruct``): its forward pass, loss and
gradients, and the robust rounds followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the equations of
the configuration (``chipbench/configs/smallthinker-21b-ep8-ps.json``, from
the source's ``config.json``). Nothing here imports the program
(``byzpy_tpu``); weights come from ``chipbench.seeded_smallthinker``.

Deliberately NOT the forms the program computes in: attention is a head's
score matrix from the definition, one head at a time, ``query_rows`` queries
against ALL keys at a time (so that 8192 positions fit), the two masks
written as inequalities on ``i - j`` (``0 <= i - j`` for a global block, ``0
<= i - j < window`` for a windowed one) over the whole row of keys, no block
pair left out; the rotary turn is a complex rotation, made on the blocks
whose ``rope_layout`` entry is 1 and on no other; the router is a softmax
over all experts of the NORMED BLOCK INPUT ``u`` (what attention reads, not
what the experts read), the top six by a sort, their weights over their
sum; every held expert multiplies every token under a dense mask, with
``relu`` on the gate. The same share as the configuration: the router scores
all ``moe_num_primary_experts``, a token's routed part sums those of its top
six that are among ``held_experts``, the vocabulary is the slice.

Departures from the published description, each the configuration's
``assumed``: the router reads the normed input; the window counts the
query's own position; no query / key norm, no bias; rotary pairs ``(i, i +
head_dim / 2)``; the family's "secondary experts" are left out.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block, the head with the final norm's
weight and ``w_head``). Block ``b`` of the chain (0-based) is published
layer ``layers_held[b]``, whose entries of the two published layouts say
which attention it has. ``dtype`` is the type activations and weights are
computed in (the configuration's float32; lower for the reading a limit is
set from).
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

QUERY_ROWS = 1024  # queries of one head scored against all keys at a time


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


def attention_by_rows(p: Dict[str, Any], u, arch: Dict[str, Any], windowed: bool, turned: bool):
    """Grouped-query attention of one normed sequence ``u (T, hidden)``, one
    head at a time, ``QUERY_ROWS`` of its queries against every key: query
    ``i`` reads key ``j`` where ``0 <= i - j`` and, in a windowed block, ``i
    - j < sliding_window_size``."""
    t = u.shape[0]
    heads, kv, hd = (int(arch[key]) for key in
                     ("num_attention_heads", "num_key_value_heads", "head_dim"))
    window = int(arch["sliding_window_size"])
    cast = lambda w: w.astype(u.dtype)  # noqa: E731
    q = (u @ cast(p["w_q"])).reshape(t, heads, hd)
    k = (u @ cast(p["w_k"])).reshape(t, kv, hd)
    v = (u @ cast(p["w_v"])).reshape(t, kv, hd)
    if turned:
        q, k = rotate(q, float(arch["rope_theta"])), rotate(k, float(arch["rope_theta"]))
    rows = min(QUERY_ROWS, t)
    pad = -t % rows
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    per = heads // kv

    @jax.checkpoint
    def some_rows(q_rows, first, k_h, v_h):  # (rows, hd), the first row's position
        scores = (q_rows @ k_h.T).astype(jnp.float32) / math.sqrt(hd)
        behind = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None, :]  # i - j
        seen = (behind >= 0) & (behind < window) if windowed else behind >= 0
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        weights = jnp.exp(scores)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights.astype(u.dtype) @ v_h

    def head(args):
        q_h, k_h, v_h = args  # (T + pad, hd), (T, hd), (T, hd)
        return jnp.concatenate([some_rows(q_h[first:first + rows], first, k_h, v_h)
                                for first in range(0, t + pad, rows)], axis=0)

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    # query head h reads key/value head h // per
    out = jax.lax.map(head, (by_head(q), jnp.repeat(by_head(k), per, axis=0),
                             jnp.repeat(by_head(v), per, axis=0)))
    return by_head(out)[:t].reshape(t, heads * hd) @ cast(p["w_o"])


def moe_dense_mask(p: Dict[str, Any], m, u, arch: Dict[str, Any]):
    """``(out, tokens each held expert got)`` for tokens ``m (T, hidden)``
    routed on ``u (T, hidden)``: softmax scores of ``u`` over all experts,
    the top six a token, their scores over their sum; every held expert
    runs on every token of ``m`` and a 0/1 mask keeps the tokens that chose
    it. ``relu`` on the gate; no shared expert."""
    first, held = (int(v) for v in arch["held_experts"])
    top_k = int(arch["moe_num_active_primary_experts"])
    cast = lambda w: w.astype(m.dtype)  # noqa: E731
    logits = (u @ cast(p["router"])).astype(jnp.float32)  # (T, E)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    scores = jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1, keepdims=True)
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    chosen = scores >= kth  # (T, E): the top six (scores are distinct floats)
    weights = jnp.where(chosen, scores, 0.0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out, counts = jnp.zeros_like(m), []
    for e in range(held):
        gate = jnp.maximum(m @ cast(p["experts_gate"][e]), 0.0)
        expert = (gate * (m @ cast(p["experts_up"][e]))) @ cast(p["experts_down"][e])
        out = out + weights[:, first + e, None].astype(m.dtype) * expert
        counts.append(jnp.sum(chosen[:, first + e]))
    return out, jnp.stack(counts)


def block(p: Dict[str, Any], h, arch: Dict[str, Any], windowed: bool, turned: bool):
    """``h (B, T, hidden)`` through one block: ``u = rms h``; ``h +
    attention(u)``; ``+ experts(rms of that), routed on u``. Returns ``(h,
    held experts' counts)``."""
    eps = float(arch["rms_norm_eps"])
    u = rms_norm(h, p["attention_norm_scale"], eps)
    mix = jax.checkpoint(partial(attention_by_rows, arch=arch, windowed=windowed, turned=turned))
    h = h + jnp.stack([mix(p, seq) for seq in u])
    m = rms_norm(h, p["ffn_norm_scale"], eps)
    out, got = jax.checkpoint(partial(moe_dense_mask, arch=arch))(
        p, m.reshape(-1, m.shape[-1]), u.reshape(-1, u.shape[-1]))
    return h + out.reshape(h.shape), got


def block_kinds(arch: Dict[str, Any]) -> List[Tuple[bool, bool]]:
    """``(windowed, turned)`` of every block held: the two published
    layouts read at the published layers ``layers_held``."""
    return [(bool(arch["sliding_window_layout"][layer]), bool(arch["rope_layout"][layer]))
            for layer in arch["layers_held"]]


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """Next-token cross-entropy (mean over positions) of ``tokens, targets:
    (B, T)`` and, per block, the tokens each held expert got."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        h = params[segments[0]]["embedding"][tokens].astype(dtype)  # (B, T, hidden)
        counts = []
        for segment, (windowed, turned) in zip(segments[1:-1], block_kinds(arch), strict=True):
            h, got = block(params[segment], h, arch, windowed, turned)
            counts.append(got)
        last = params[segments[-1]]
        h = rms_norm(h, last["norm_scale"], float(arch["rms_norm_eps"]))
        logits = (h @ last["w_head"].astype(dtype)).astype(jnp.float32)
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
    loss and whole gradient (``jax.grad``), one worker at a time, its rows
    kept on the HOST; then, leaf by leaf, the h honest rows of that leaf go
    back to the device, the
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
