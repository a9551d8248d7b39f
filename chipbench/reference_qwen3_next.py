"""Plain reference of the Qwen3-Next configuration (``model_type:
qwen3_next``): its forward pass, loss and gradients, and the robust rounds
followed one worker at a time.

Straight ``jax.numpy`` in float32 with every contraction at
``jax.default_matmul_precision("highest")``, written from the layer
equations of Qwen3-Next-80B-A3B's ``config.json`` and the Gated DeltaNet
paper (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464, the recurrence of
its equation 10). Nothing here imports the program (``byzpy_tpu``);
weights come from ``chipbench.seeded_qwen3_next``.

Deliberately NOT the forms the program computes in: the gated delta rule
is the recurrence position by position (``S <- exp(g_t) S; u_t = beta_t
(v_t - S^T k_t); S <- S + k_t u_t^T; o_t = S^T q_t``, products written as
sums), not the chunked form and no triangular system; attention is the
full score matrix of a key/value group, rotary positions a complex
rotation from the definition; every held expert multiplies every token
under a dense mask. The same share as the configuration: the router's
softmax is over all ``num_experts``, a token's routed part sums those of
its top-k that are among ``held_experts``, the vocabulary is the slice.
The multi-token-prediction module of the published model is left out, as
the configuration's file says.

Parameter trees are ``{segment: {leaf: array}}``; segments sort into the
chain's order (``seg00_embed``, one a block, the head); a block whose
leaves hold ``w_qkv`` is a Gated DeltaNet block, the others attention.
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


def norm(x, weight, eps: float):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + weight)).astype(x.dtype)


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


def delta_rule_recurrent(q, k, v, g, beta, *, inner: int = 64):
    """The gated delta rule of one sequence, the state carried position by
    position: ``q``, ``k`` ``(T, H, K)``, ``v (T, H, V)``, ``g``, ``beta``
    ``(T, H)``, all float32, one q and k a value head. Positions go
    ``inner`` at a time and each such stretch is rematerialised in the
    backward pass: kept whole, the ``T`` states of ``H x K x V`` would be
    8.6 GB a block at the published sizes. Returns ``o (T, H, V)``."""
    t, heads, dk = q.shape
    dv = v.shape[-1]

    def position(state, at):  # state (H, K, V)
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    @jax.checkpoint
    def stretch(state, at):
        return jax.lax.scan(position, state, at)

    pad = -t % inner
    seq = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (t + pad) // inner, inner, *a.shape[1:]) for a in (q, k, v, g, beta)]
    _, o = jax.lax.scan(stretch, jnp.zeros((heads, dk, dv), jnp.float32), tuple(seq))
    return o.reshape(t + pad, heads, dv)[:t]


def delta_net(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """One sequence ``(T, hidden)`` through a Gated DeltaNet mixer."""
    t = x.shape[0]
    hk, hv = int(arch["linear_num_key_heads"]), int(arch["linear_num_value_heads"])
    dk, dv = int(arch["linear_key_head_dim"]), int(arch["linear_value_head_dim"])
    taps = int(arch["linear_conv_kernel_dim"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    qkv, z, ba = x @ cast(p["w_qkv"]), x @ cast(p["w_z"]), x @ cast(p["w_ba"])
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), qkv.dtype), qkv], axis=0)
    conv = sum(cast(p["conv_w"])[j][None, :] * padded[j: j + t] for j in range(taps))
    qkv = (conv * jax.nn.sigmoid(conv)).astype(jnp.float32)  # SiLU
    q = qkv[:, : hk * dk].reshape(t, hk, dk)
    k = qkv[:, hk * dk: 2 * hk * dk].reshape(t, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, hv // hk, axis=1)  # a key head's q and k serve its value heads
    k = jnp.repeat(k, hv // hk, axis=1)
    ba = ba.astype(jnp.float32)
    beta = 1.0 / (1.0 + jnp.exp(-ba[:, :hv]))
    g = -jnp.exp(p["a_log"]) * jnp.logaddexp(ba[:, hv:] + p["dt_bias"], 0.0)  # softplus
    o = delta_rule_recurrent(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + float(arch["rms_norm_eps"]))
    gate = z.reshape(t, hv, dv).astype(jnp.float32)
    o = o * p["gate_norm_scale"] * (gate * jax.nn.sigmoid(gate))
    return o.reshape(t, hv * dv).astype(x.dtype) @ cast(p["w_out"])


def attention_full(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """Gated causal softmax attention by the full ``(T, T)`` score matrix,
    one key/value group (its query heads together) at a time."""
    t = x.shape[0]
    heads, kv, hd = (int(arch["num_attention_heads"]), int(arch["num_key_value_heads"]),
                     int(arch["head_dim"]))
    per, eps, theta = heads // kv, float(arch["rms_norm_eps"]), float(arch["rope_theta"])
    turned = int(hd * float(arch["partial_rotary_factor"]))
    cast = lambda w: w.astype(x.dtype)  # noqa: E731

    def placed(a, weight):
        a = norm(a, weight, eps)
        return jnp.concatenate([rotate(a[..., :turned], theta), a[..., turned:]], axis=-1)

    q = placed((x @ cast(p["w_q"])).reshape(t, heads, hd), p["q_norm_weight"])
    q = q.reshape(t, kv, per, hd)
    k = placed((x @ cast(p["w_k"])).reshape(t, kv, hd), p["k_norm_weight"])
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
    out = out.reshape(t, heads * hd) * jax.nn.sigmoid(x @ cast(p["w_q_gate"]))
    return out @ cast(p["w_o"])


def _gated(x, gate, up, down):
    g = x @ gate
    return ((g * jax.nn.sigmoid(g)) * (x @ up)) @ down  # down(silu(gate x) * up x)


def moe_dense_mask(p: Dict[str, Any], x, arch: Dict[str, Any]):
    """``(out, tokens each held expert got)`` for tokens ``x (T, hidden)``:
    a softmax over all experts, the top-k a token divided by their sum;
    every held expert runs on every token and a 0/1 mask keeps the tokens
    that chose it; the shared expert runs on every token, times
    ``sigmoid(x w_s)``."""
    first, held = (int(v) for v in arch["held_experts"])
    top_k = int(arch["num_experts_per_tok"])
    cast = lambda w: w.astype(x.dtype)  # noqa: E731
    logits = (x @ cast(p["router"])).astype(jnp.float32)  # (T, E)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1, keepdims=True)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    chosen = probs >= kth  # (T, E): the top-k (probabilities are distinct floats)
    weights = jnp.where(chosen, probs, 0.0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = _gated(x, cast(p["shared_gate"]), cast(p["shared_up"]), cast(p["shared_down"]))
    out = out * jax.nn.sigmoid(x @ cast(p["shared_weight"]))

    def one_expert(total, expert):  # one held expert on EVERY token, then its mask
        gate, up, down, weight, picked = expert
        return (total + weight[:, None].astype(x.dtype) * _gated(x, gate, up, down),
                jnp.sum(picked))

    cut = slice(first, first + held)
    return jax.lax.scan(one_expert, out, (
        cast(p["experts_gate"]), cast(p["experts_up"]), cast(p["experts_down"]),
        weights[:, cut].T, chosen[:, cut].T))


def block(p: Dict[str, Any], h, arch: Dict[str, Any]):
    """``h (B, T, hidden)`` through one block: ``h + mixer(norm h)``, then
    ``h + experts(norm h)``; returns ``(h, held experts' counts)``."""
    eps = float(arch["rms_norm_eps"])
    mixer = jax.checkpoint(partial(delta_net if "w_qkv" in p else attention_full, arch=arch))
    h = h + jnp.stack([mixer(p, seq) for seq in norm(h, p["mixer_norm_weight"], eps)])
    normed = norm(h, p["ffn_norm_weight"], eps)
    out, got = jax.checkpoint(partial(moe_dense_mask, arch=arch))(
        p, normed.reshape(-1, normed.shape[-1]))
    return h + out.reshape(h.shape), got


def loss_and_counts(params: Dict[str, Dict[str, Any]], tokens, targets, arch: Dict[str, Any],
                    *, dtype=jnp.float32, precision: str = "highest"):
    """Next-token cross-entropy (mean over positions) of ``tokens, targets:
    (B, T)`` and, per block, the tokens each held expert got."""
    with jax.default_matmul_precision(precision):
        segments = sorted(params)
        h = params[segments[0]]["embedding"][tokens].astype(dtype)  # (B, T, hidden)
        counts = []
        for segment in segments[1:-1]:
            h, got = block(params[segment], h, arch)
            counts.append(got)
        head = params[segments[-1]]
        logits = norm(h, head["norm_weight"], float(arch["rms_norm_eps"])) @ head[
            "w_head"].astype(dtype)
        logits = logits.astype(jnp.float32)
        top = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
        picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - picked)
    return loss, jnp.stack(counts)


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
    blocks, held)``), the norm of every leaf of the first round's
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
