"""Expert parallelism: a mixture-of-experts FFN sharded over an expert
mesh axis.

Completes the parallelism portfolio (dp over nodes, feature/tensor
sharding in the aggregators, sp via ring/ulysses attention, pp in
:mod:`byzpy_tpu.parallel.pipeline`): experts live one-per-device on an
``"ep"`` axis, tokens route to experts with a top-k softmax gate, and the
dispatch/combine movements are the standard two ``all_to_all`` exchanges
(Shazeer et al. 2017; GShard's einsum formulation). The reference has no
MoE analogue (it has no model code at all beyond examples) — this exists
because sparse FFNs are a first-class TPU workload.

Design notes (TPU-shaped):

* **Static capacity.** Each expert processes exactly ``capacity`` token
  slots per device shard; overflow drops (standard GShard behavior),
  underflow pads with zeros. Shapes are static, XLA-friendly.
* **Dense one-hot dispatch einsums**, not gathers: the dispatch tensor
  ``(tokens, experts, capacity)`` contracts on the MXU.
* ``moe_ffn`` is the in-SPMD function (inside ``shard_map``);
  ``MoEFFN`` the flax module usable single-device (all experts local,
  same math) or expert-parallel under a mesh.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_rows_to_tokens import rows_to_tokens, rows_to_tokens_serves
from . import collectives

Array = jnp.ndarray


def top1_dispatch(
    gate_logits: Array, n_experts: int, capacity: int
) -> Tuple[Array, Array]:
    """Build dispatch/combine tensors for top-1 routing.

    ``gate_logits: (T, E)`` -> ``dispatch (T, E, C)`` one-hot (token t
    goes to expert e in slot c; all-zero when dropped) and ``combine
    (T, E, C)`` (dispatch scaled by the gate probability).
    """
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=gate_logits.dtype)  # (T, E)
    # slot index = this token's position among tokens routed to the same
    # expert (cumsum over the token axis); -1 for other experts and for
    # capacity overflow, which one_hot maps to an all-zero row (= drop)
    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # (T, E)
    keep = (position >= 0) & (position < capacity)
    pos_te = jnp.where(keep, position, -1.0).astype(jnp.int32)
    slot_tec = jax.nn.one_hot(pos_te, capacity, dtype=gate_logits.dtype)
    dispatch = onehot[:, :, None] * slot_tec  # (T, E, C)
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def moe_ffn(
    x: Array,
    gate_w: Array,
    w_in: Array,
    w_out: Array,
    axis_name: Optional[str] = None,
    *,
    capacity_factor: float = 2.0,
) -> Array:
    """Top-1 MoE FFN: ``x (T, D)``, ``gate_w (D, E)``, per-expert
    ``w_in (E, D, H)`` / ``w_out (E, H, D)``.

    With ``axis_name`` (inside ``shard_map``): ``w_in``/``w_out`` carry
    the LOCAL expert slice ``(E/p, D, H)``, tokens are the local shard,
    and the dispatched tokens ride two ``all_to_all`` exchanges so every
    device computes only its own experts. Without it: all experts local.

    Capacity semantics: ``capacity`` derives from the LOCAL token count
    and overflow is decided per shard in local token order, so the
    sharded and dense paths agree exactly only in the no-drop regime
    (``capacity_factor >= n_experts`` guarantees it; the parity tests
    pin that case). Under drops both are valid GShard-style routers but
    may drop different tokens.
    """
    t, d = x.shape
    e_local = w_in.shape[0]
    p = collectives.axis_size(axis_name) if axis_name else 1
    n_experts = e_local * p
    capacity = max(1, int(capacity_factor * t / n_experts))

    gate_logits = x @ gate_w  # (T, E)
    dispatch, combine = top1_dispatch(gate_logits, n_experts, capacity)
    # expert-major token blocks: (E, C, D)
    expert_inputs = jnp.einsum("td,tec->ecd", x, dispatch)
    if axis_name:
        # (E, C, D) -> every device keeps its expert rows, receives its
        # experts' slots from all peers: all_to_all over the expert axis,
        # tokens concatenated on the capacity axis -> (E/p, p*C, D)
        expert_inputs = lax.all_to_all(
            expert_inputs, axis_name, split_axis=0, concat_axis=1, tiled=True
        )
    h = jnp.einsum("ecd,edh->ech", expert_inputs, w_in)
    h = jax.nn.gelu(h)
    out_blocks = jnp.einsum("ech,ehd->ecd", h, w_out)
    if axis_name:
        out_blocks = lax.all_to_all(
            out_blocks, axis_name, split_axis=1, concat_axis=0, tiled=True
        )
    return jnp.einsum("ecd,tec->td", out_blocks, combine)


def _expert(h_in: Array, up: Array, down: Array, gated: Optional[Array] = None,
            activation: Callable[[Array], Array] = jax.nn.silu) -> Array:
    """``down relu(up h_in)^2``, or with a third matrix
    ``down (activation(gated h_in) * up h_in)``."""
    if gated is None:
        hidden = jnp.square(jax.nn.relu(h_in @ up.astype(h_in.dtype)))
    else:
        hidden = activation(h_in @ gated.astype(h_in.dtype)) * (h_in @ up.astype(h_in.dtype))
    return hidden @ down.astype(h_in.dtype)


def _expert_round(x, weights, gate, expert, rank, counts, r, rows, activation):
    """Round ``r`` of the held experts' part: every expert multiplies the
    ``rows`` of its tokens whose rank among them is in ``[r rows, (r + 1)
    rows)``. ``gate``, ``expert``, ``rank`` are ``(T, k)``, a column a pick
    of the token's that is held here: its weight, its expert's place among
    the held (``held`` where the column is empty) and the token's rank among
    that expert's tokens.
    Returns the round's share of ``out (T, D)`` and how many (token,
    expert) pairs it computed. What is gathered, multiplied and read back
    is the round's ``held x rows`` slots, of which the filled ones go back
    to the tokens (:func:`_rows_to_tokens`): never ``T x held`` rows and
    never ``T x k`` (at 32 held experts of 512 and 10 picks a token, 0.6 of
    a token's picks are held: ``T x held`` rows are 50 times the filled
    slots, ``T x k`` sixteen times)."""
    tokens, d = x.shape
    picks = gate.shape[1]
    held = weights[0].shape[0]
    local = rank - r * rows
    mine = (expert < held) & (local >= 0) & (local < rows)  # (T, k): this round's pairs
    slot = jnp.where(mine, expert * rows + local, held * rows)
    # a filled slot's one reader, as its place in the (T, k) picks laid flat
    reader_at = jnp.zeros((held * rows,), jnp.int32).at[slot.reshape(-1)].set(
        jnp.arange(tokens * picks, dtype=jnp.int32), mode="drop")
    # (held,): an expert's tokens fill its first slots
    filled = jnp.clip(counts - r * rows, 0, rows)
    per_expert = jax.vmap(functools.partial(_expert, activation=activation))(
        _dispatch(x, reader_at, slot, filled).reshape(held, rows, d), *weights).reshape(
            held * rows, d)
    out = _combine(per_expert, gate.astype(x.dtype), slot, reader_at, filled)
    return out, jnp.sum(mine, dtype=jnp.int32)


def _rows_to_tokens(rows, gate, slot, reader_at, filled):
    """Rows back to the tokens that read them: ``out[t] = sum_j gate[t, j]
    rows[slot[t, j]]`` over a token's columns in their order, multiplied and
    added in float32; a column whose ``slot`` is past the end of ``rows``
    reads nothing. No ``(T, k, D)`` array is made. On a TPU one kernel that
    walks the filled slots only, by the same map the other way (``reader_at``,
    and ``filled (held,)``, an expert's live slots: :func:`~byzpy_tpu.ops.
    pallas_rows_to_tokens.rows_to_tokens_serves` says where it serves);
    elsewhere the same sum as ``k`` takes of ``(T, D)``."""
    if rows_to_tokens_serves(rows):
        return rows_to_tokens(rows, gate, reader_at, filled)
    out = jnp.zeros((slot.shape[0], rows.shape[1]), jnp.float32)
    for j in range(slot.shape[1]):
        read = jnp.take(rows, slot[:, j], axis=0, mode="fill", fill_value=0)
        out = out + gate[:, j, None].astype(jnp.float32) * read.astype(jnp.float32)
    return out.astype(rows.dtype)


@jax.custom_vjp
def _dispatch(x, reader_at, slot, filled):
    """To the slots: slot ``s`` gets the token of its one reader,
    ``reader_at[s]`` of the ``(T, k)`` picks laid flat (a slot no pick fills
    gets token 0, and what is computed there reaches nothing). The cotangent
    into the tokens is the same read the other way with every weight one,
    ``sum_j d_rows[slot[t, j]]`` over a token's filled picks: a filled slot
    has exactly one reader, so it is the gather's transpose term for term.
    (Left to automatic differentiation that transpose is a scatter-add of
    ``held x rows`` rows, three quarters of them zero.)"""
    return x[reader_at // slot.shape[1]]


def _dispatch_fwd(x, reader_at, slot, filled):
    return _dispatch(x, reader_at, slot, filled), (reader_at, slot, filled)


def _dispatch_bwd(kept, d_rows):
    reader_at, slot, filled = kept
    ones = jnp.ones(slot.shape, d_rows.dtype)
    return _rows_to_tokens(d_rows, ones, slot, reader_at, filled), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(per_expert, gate, slot, reader_at, filled):
    """Back to the tokens: ``sum_j gate[t, j] per_expert[slot[t, j]]`` over
    a token's picks (:func:`_rows_to_tokens`: a slot past the end reads
    nothing; a slot no token fills is read by none). A filled slot is read by
    exactly one pick, ``reader_at[s]`` of the picks laid flat, so the
    backward is a gather too: the cotangent of slot ``s`` is ``gate * d_out``
    of that one reader. (Left to automatic differentiation the take's
    transpose is a scatter-add of as many rows as there are picks, nearly
    all of them zero, into ``held x rows``: the slowest operation of the
    layer on a TPU.)"""
    return _rows_to_tokens(per_expert, gate, slot, reader_at, filled)


def _combine_fwd(per_expert, gate, slot, reader_at, filled):
    return (_combine(per_expert, gate, slot, reader_at, filled),
            (per_expert, gate, slot, reader_at, filled))


def _combine_bwd(kept, d_out):
    per_expert, gate, slot, reader_at, filled = kept
    rows = per_expert.shape[0] // filled.shape[0]
    holds_token = (jnp.arange(rows)[None, :] < filled[:, None]).reshape(-1)
    d_read = d_out[reader_at // gate.shape[1]]  # (held x rows, D): what a slot's reader hands back
    gate_at = gate.reshape(-1)[reader_at]
    keep = holds_token[:, None]
    d_per_expert = jnp.where(keep, d_read * gate_at[:, None], 0.0).astype(per_expert.dtype)
    d_gate_at = jnp.sum(jnp.where(keep, d_read * per_expert, 0.0).astype(jnp.float32), axis=-1)
    d_gate = jnp.take(d_gate_at, slot, axis=0, mode="fill", fill_value=0).astype(gate.dtype)
    return d_per_expert, d_gate, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _expert_rounds(x, weights, gate, expert, rank, counts, rows, activation):
    """The held experts' part of every routed token: round 0, then as many
    more as the fullest expert needs. ``weights`` are the experts' stacked
    matrices as :func:`_expert` takes them (up, down, and the gated
    experts' third, whose ``activation`` is the model's). The count of
    rounds is read from the batch, so the loop is a ``while`` with a
    backward pass of its own (the same rounds again, each one's
    vector-Jacobian product added up)."""
    return _expert_rounds_fwd(x, weights, gate, expert, rank, counts, rows, activation)[0]


def _rounds_needed(counts, rows):
    return jnp.maximum(1, -(-jnp.max(counts) // rows))


def _expert_rounds_fwd(x, weights, gate, expert, rank, counts, rows, activation):
    rounds = _rounds_needed(counts, rows)

    def more(r, acc):
        out, computed = _expert_round(x, weights, gate, expert, rank, counts, r, rows, activation)
        return acc[0] + out, acc[1] + computed

    out, computed = lax.fori_loop(
        1, rounds, more,
        _expert_round(x, weights, gate, expert, rank, counts, 0, rows, activation))
    return (out, computed, rounds), (x, weights, gate, expert, rank, counts)


def _expert_rounds_bwd(rows, activation, kept, cotangents):
    x, weights, gate, expert, rank, counts = kept
    g = cotangents[0]  # the pairs computed and the rounds are counts

    def pulled(r):
        return jax.vjp(
            lambda *wrt: _expert_round(*wrt, expert, rank, counts, r, rows, activation)[0],
            x, weights, gate)[1](g)

    def more(r, acc):
        return jax.tree_util.tree_map(jnp.add, acc, pulled(r))

    return (*lax.fori_loop(1, _rounds_needed(counts, rows), more, pulled(0)), None, None, None)


_expert_rounds.defvjp(_expert_rounds_fwd, _expert_rounds_bwd)


def held_experts_ffn(
    x: Array,
    router_w: Array,
    w_up: Array,
    w_down: Array,
    shared_up: Optional[Array] = None,
    shared_down: Optional[Array] = None,
    *,
    first_held: int,
    n_experts: int,
    top_k: int,
    scale: float = 1.0,
    round_rows: Optional[int] = None,
    w_gate: Optional[Array] = None,
    shared_gate: Optional[Array] = None,
    score: Callable[[Array], Array] = jax.nn.sigmoid,
    shared_weight: Optional[Array] = None,
    denominator_eps: float = 0.0,
    router_input: Optional[Array] = None,
    activation: Callable[[Array], Array] = jax.nn.silu,
):
    """One chip's part of an expert layer whose experts are spread over
    chips: it is told which experts it holds, routes over all of them,
    and computes its own experts' part for the tokens routed to them.

    ``x (T, D)`` tokens; ``router_w (D, n_experts)`` at its full width;
    ``w_up (held, D, F)``, ``w_down (held, F, D)`` the experts
    ``first_held .. first_held + held - 1``. Routing: ``s = score(x
    router_w)`` over all ``n_experts`` (``score`` is the model's: the
    sigmoid of DeepSeek-V3 / Nemotron-H / GLM, or ``jax.nn.softmax`` over
    the experts as Qwen3-Next has it), the ``top_k`` largest a token, their
    ``s`` over their sum (plus ``denominator_eps`` where a model's code adds
    one: LFM2's ``1e-6``; 0 adds no op) and times ``scale``. Expert:
    ``w_down relu(w_up x)^2``, or, where the experts come with a third
    stacked matrix ``w_gate (held, D, F)`` (and the shared expert with
    ``shared_gate (D, Fs)``), ``w_down (activation(w_gate x) * w_up x)``:
    which of the two is read off the weights given, and ``activation`` is
    the model's (``jax.nn.silu``; SmallThinker's ``jax.nn.relu``). Where
    the router reads ANOTHER tensor than the experts do (SmallThinker
    routes on the block's normed input, before attention) it is handed as
    ``router_input (T, D)``; ``None`` routes on ``x``. A token's routed part is the weighted sum
    over those of its ``top_k`` that are held here; what the absent
    experts would add is another chip's part. The shared expert
    (``shared_up (D, Fs)``, ``shared_down (Fs, D)``), where given, is
    added for every token, times ``sigmoid(x shared_weight)`` where it
    comes with a ``shared_weight (D, 1)``.

    NO TOKEN IS DROPPED, by construction: the held experts work in rounds
    of ``round_rows`` gathered tokens each (one batched product over the
    experts a round), and there are as many rounds as the fullest expert
    of the batch at hand needs. ``round_rows`` is a size and not a limit:
    any value gives the same result; a small one spends more rounds on a
    popular expert, a large one multiplies more empty rows. Default: an
    eighth of the tokens (in whole sublanes of 8). The loop over the rounds
    already sizes the work to the batch's fullest expert, in steps of
    ``round_rows``: an even router's fullest expert (1.2 to 1.4 times the
    mean of ``tokens top_k / n_experts`` where an expert's mean is a
    sixteenth of the tokens or so) fits one round of an eighth, half the
    rows a quarter multiplied; a skewed one's takes two or three, never more
    rows than the rounds of a quarter it took before. A model whose held
    experts are many and lightly loaded hands its own (a few times the mean
    load): a second round reads the experts' matrices again, and under a
    few hundred rows the products wait for those.

    Returns ``(out (T, D), aux)``: ``aux["held_expert_tokens"]`` the
    ``(held,)`` tokens each held expert got, ``aux["tokens_dropped"]``
    the routed (token, held expert) pairs that no round computed (0),
    ``aux["expert_rounds"]`` the rounds that ran.
    """
    tokens = x.shape[0]
    held = w_up.shape[0]
    rows = round_rows or -(-max(tokens // 8, 1) // 8) * 8
    with jax.named_scope("model.moe_route"):
        # in float32 at full precision whatever the activations' type: a
        # rounding that swaps a token's sixth and seventh expert is a
        # different result, not a small error (and the matrix is small)
        routed_on = x if router_input is None else router_input
        scores = score(jnp.dot(
            routed_on.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        top_s, top_e = lax.top_k(scores, top_k)  # (T, k)
        total = jnp.sum(top_s, axis=-1, keepdims=True)
        if denominator_eps:
            total = total + denominator_eps
        weight = top_s / total * scale
        local = top_e - first_held
        here = (local >= 0) & (local < held)  # (T, k): this pick's expert is held
        # (T, held): the weight of each held expert for each token (an
        # expert is picked at most once a token)
        onehot = (local[:, :, None] == jnp.arange(held)[None, None, :]) & here[:, :, None]
        weight_of = jnp.sum(jnp.where(onehot, weight[:, :, None], 0.0), axis=1)
        routed = jnp.any(onehot, axis=1)  # (T, held)
        counts = jnp.sum(routed, axis=0, dtype=jnp.int32)  # (held,)
        # a token's place among its expert's tokens
        rank_of = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
        # what a round reads back, a token: one column a pick of the token's
        # that is held here, in the order of the experts (so that a token's sum
        # does not hang on the order of its scores): column c holds its c-th,
        # by selection over (T, c, held), no gather
        picks = min(top_k, held)
        nth = jnp.cumsum(routed, axis=1, dtype=jnp.int32) - 1
        chosen = routed[:, None, :] & (nth[:, None, :] == jnp.arange(picks)[None, :, None])
        expert = jnp.min(jnp.where(chosen, jnp.arange(held), held), axis=-1)  # held: none
        gate = jnp.sum(jnp.where(chosen, weight_of[:, None, :], 0.0), axis=-1)
        rank = jnp.sum(jnp.where(chosen, rank_of[:, None, :], 0), axis=-1)
    with jax.named_scope("model.moe_experts"):
        weights = (w_up, w_down) if w_gate is None else (w_up, w_down, w_gate)
        out, computed, rounds = _expert_rounds(
            x, weights, gate, expert, rank, counts, rows, activation)
        if shared_up is not None:
            with jax.named_scope("model.moe_shared"):
                shared = _expert(x, shared_up, shared_down, shared_gate, activation)
                if shared_weight is not None:
                    shared = shared * jax.nn.sigmoid(x @ shared_weight.astype(x.dtype))
                out = out + shared
    aux = {
        "held_expert_tokens": counts,
        "tokens_dropped": jnp.sum(counts) - computed,
        "expert_rounds": rounds,
    }
    return out, aux


class MoEFFN(nn.Module):
    """Flax MoE FFN block (top-1 routing, GShard-style static capacity).

    Single-device by default; pass ``axis_name`` when the expert axis is
    sharded under an enclosing ``shard_map`` (params then hold the local
    expert slice).
    """

    n_experts: int
    hidden: int
    capacity_factor: float = 2.0
    axis_name: Optional[str] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        t, d = x.shape
        gate_w = self.param(
            "gate", nn.initializers.lecun_normal(), (d, self.n_experts), self.dtype
        )
        p = collectives.axis_size(self.axis_name) if self.axis_name else 1
        if self.n_experts % p:
            raise ValueError(
                f"n_experts={self.n_experts} must divide over axis size {p}"
            )
        e_local = self.n_experts // p

        def per_device(base_init):
            # under expert parallelism the module RNG is replicated over
            # the axis; folding in the device's axis index keeps the E
            # experts distinct instead of collapsing them to E/p copies
            def init(key, shape, dtype):
                if self.axis_name:
                    key = jax.random.fold_in(key, lax.axis_index(self.axis_name))
                return base_init(key, shape, dtype)

            return init

        w_in = self.param(
            "w_in", per_device(nn.initializers.lecun_normal()),
            (e_local, d, self.hidden), self.dtype,
        )
        w_out = self.param(
            "w_out", per_device(nn.initializers.lecun_normal()),
            (e_local, self.hidden, d), self.dtype,
        )
        return moe_ffn(
            x, gate_w, w_in, w_out, self.axis_name,
            capacity_factor=self.capacity_factor,
        )


__all__ = ["top1_dispatch", "moe_ffn", "held_experts_ffn", "MoEFFN"]
