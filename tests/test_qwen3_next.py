"""Qwen3-Next's layers (``models/qwen3_next.py``, ``models/layers.py``,
``parallel/moe.py``, ``ops/pallas_attention.py``) against the benchmark's
plain reference (``chipbench/reference_qwen3_next``) on seeded weights, at
small sizes on the CPU: the chunked gated delta rule is the recurrence,
forward and gradient, whatever the length and the decay; the chunk's
triangular inverse is the inverse; gated attention is the full score
matrix; the sixteen shares of the softmax-routed expert layer tie to the
uncut layer; a sigmoid-routed call of the expert layer is what it was; the
whole chain is the reference's loss, gradient and counts; the kernels at
eight query heads a group of 256."""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import layers
from byzpy_tpu.models import qwen3_next as qn
from byzpy_tpu.ops import pallas_attention as pa
from byzpy_tpu.parallel.moe import held_experts_ffn
from chipbench import reference_nemotron_h
from chipbench import reference_qwen3_next as ref
from chipbench import seeded_qwen3_next as seeded

TINY = qn.Qwen3NextConfig(
    hidden_size=32, num_hidden_layers=4, vocab_size=64, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8, chunk_size=16,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, query_block=8, num_experts=32,
    num_experts_per_tok=4, moe_intermediate_size=24, shared_expert_intermediate_size=24,
    held_experts=(4, 4))


def _arch(cfg, **over):
    return {
        "rms_norm_eps": cfg.rms_norm_eps,
        "linear_num_key_heads": cfg.linear_num_key_heads,
        "linear_num_value_heads": cfg.linear_num_value_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.partial_rotary_factor, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "held_experts": list(cfg.held_experts), **over}


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _seeded_bundle(cfg, seed):
    """The bundle on the benchmark's seeded weights."""
    bundle = qn.qwen3_next_bundle(cfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bundle.params)
    return bundle.with_params(seeded.make_params(shapes, seed, {}))


def _both(fn_program, fn_reference, p, x):
    """Value and gradients (weights and input) of a scalar read-out of both."""
    probe = jax.random.normal(jax.random.PRNGKey(9), fn_reference(p, x).shape)
    outs = []
    for fn in (fn_program, fn_reference):
        grads = jax.grad(lambda p_, x_: jnp.sum(fn(p_, x_) * probe), argnums=(0, 1))(p, x)
        outs.append((fn(p, x), grads))
    return outs


# -- the gated delta rule ----------------------------------------------------------


def _rule_inputs(t, decay, hk=2, hv=4, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    q, k = (jax.random.normal(key, (t, hk, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, hv, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (t, hv)))
    u = jax.random.uniform(ks[4], (t, hv))
    # exp(g): within a thousandth of 1; under e^-5; from e^-0.02 to e^-7
    g = {"near_one": -1e-3 * u, "near_zero": -20.0 * u - 5.0, "mixed": -jnp.exp(6.0 * u - 4.0)}[
        decay]
    return (q, k, v, g, beta), jax.random.normal(ks[5], (t, hv, dv))


def _recurrence(q, k, v, g, beta):
    per = v.shape[1] // q.shape[1]
    return ref.delta_rule_recurrent(
        jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1), v, g, beta, inner=7)


@pytest.mark.parametrize("decay", ["near_one", "near_zero", "mixed"])
@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])  # under, at and over a chunk; ragged
def test_the_chunked_rule_is_the_recurrence_forward_and_gradient(t, decay):
    args, probe = _rule_inputs(t, decay)
    with jax.default_matmul_precision("highest"):
        got = qn.gated_delta_rule_chunked(*args, 64)
        want = _recurrence(*args)
        grads = jax.grad(lambda *a: jnp.sum(qn.gated_delta_rule_chunked(*a, 64) * probe),
                         argnums=(0, 1, 2, 3, 4))(*args)
        want_grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe),
                              argnums=(0, 1, 2, 3, 4))(*args)
    assert got.shape == want.shape == (t, 4, 8) and got.dtype == jnp.float32
    _close(got, want)
    for name, g_, w_ in zip("qkvgb", grads, want_grads):
        # the gradient of g is of the order of the decay itself: under e^-5
        # the comparison is against a number that small
        _close(g_, w_, tol=2e-3 if (name == "g" and decay == "near_zero") else 1e-4)
        assert np.all(np.isfinite(np.asarray(g_)))


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_the_chunk_is_a_size_and_not_mathematics(chunk):
    args, _ = _rule_inputs(50, "mixed")
    with jax.default_matmul_precision("highest"):
        _close(qn.gated_delta_rule_chunked(*args, chunk), _recurrence(*args))


def test_the_rule_forgets_and_overwrites_as_the_delta_rule_says():
    """One key written twice with beta = 1 and no decay: the second value
    REPLACES the first (a sum would read both); with a decay of e^-30 between
    them the first is gone before the second comes."""
    k = jnp.zeros((2, 1, 4)).at[:, 0, 1].set(1.0)
    v = jnp.asarray([[[1.0, 2.0]], [[5.0, -3.0]]])
    ones, none = jnp.ones((2, 1)), jnp.zeros((2, 1))
    out = qn.gated_delta_rule_chunked(k, k, v, none, ones, 64)
    np.testing.assert_allclose(out[:, 0], v[:, 0], atol=1e-6)  # reads what was last written
    half = qn.gated_delta_rule_chunked(k, k, v, none, 0.5 * ones, 64)
    np.testing.assert_allclose(half[1, 0], 0.5 * v[0, 0] + 0.5 * (v[1, 0] - 0.5 * v[0, 0]),
                               atol=1e-6)
    gone = qn.gated_delta_rule_chunked(k, k, v, jnp.asarray([[0.0], [-30.0]]), 0.5 * ones, 64)
    np.testing.assert_allclose(gone[1, 0], 0.5 * v[1, 0], atol=1e-6)


@pytest.mark.parametrize("size", [4, 16, 64])
def test_the_triangular_inverse_is_the_inverse_and_its_backward_the_inverses(size):
    key, key_probe = jax.random.split(jax.random.PRNGKey(size))
    a = jnp.tril(jax.random.normal(key, (3, 2, size, size)) * 0.15, -1)
    probe = jax.random.normal(key_probe, a.shape)
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        got = qn.unit_lower_inverse(a)
        _close(got @ (eye + a), jnp.broadcast_to(eye, a.shape), tol=1e-4)
        assert not np.any(np.triu(np.asarray(got), 1))  # lower-triangular, unit diagonal
        _close(jnp.diagonal(got, axis1=-2, axis2=-1), jnp.ones((3, 2, size)), tol=1e-6)
        grad = jax.grad(lambda m: jnp.sum(qn.unit_lower_inverse(m) * probe))(a)
        want = jax.grad(lambda m: jnp.sum(jnp.linalg.inv(eye + jnp.tril(m, -1)) * probe))(a)
    _close(grad, want, tol=2e-4)
    assert not np.any(np.triu(np.asarray(grad)))  # strictly lower, as its argument


@pytest.mark.parametrize("length", [5, 16, 37])
def test_the_delta_net_mixer_is_the_references_forward_and_gradient(length):
    p = _seeded_bundle(TINY, 3).params["seg02_delta"]
    x = jax.random.normal(jax.random.PRNGKey(0), (length, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        (y, g), (y_ref, g_ref) = _both(lambda p_, x_: qn.gated_delta_net(p_, x_, TINY),
                                       lambda p_, x_: ref.delta_net(p_, x_, _arch(TINY)), p, x)
    _close(y, y_ref)
    for name in ("w_qkv", "w_z", "w_ba", "conv_w", "a_log", "dt_bias", "gate_norm_scale",
                 "w_out"):
        _close(g[0][name], g_ref[0][name], tol=2e-4)
        assert float(jnp.max(jnp.abs(g_ref[0][name]))) > 0
    _close(g[1], g_ref[1], tol=2e-4)


def test_the_convolution_without_a_bias_is_the_plain_formula_forward_and_gradient():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x, w = jax.random.normal(ks[0], (11, 12)), jax.random.normal(ks[1], (4, 12))
    probe = jax.random.normal(ks[2], (11, 12))

    def plain(x_, w_):
        padded = jnp.concatenate([jnp.zeros((3, 12)), x_], axis=0)
        pre = sum(w_[j] * padded[j: j + 11] for j in range(4))
        return pre * jax.nn.sigmoid(pre)

    def own(x_, w_):
        return jnp.concatenate(layers.conv_silu(x_, w_, None, (4, 8)), axis=1)

    assert [blk.shape for blk in layers.conv_silu(x, w, None, (4, 8))] == [(11, 4)] * 3
    _close(own(x, w), plain(x, w), tol=1e-6)
    _close(layers.causal_depthwise_conv(x, w), layers.causal_depthwise_conv(x, w, jnp.zeros(12)),
           tol=1e-7)
    got = jax.grad(lambda *a: jnp.sum(own(*a) * probe), (0, 1))(x, w)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe), (0, 1))(x, w)
    for g_, w_ in zip(got, want):
        _close(g_, w_, tol=1e-5)


def test_the_one_plus_norm_multiplies_by_one_plus_its_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    w = jax.random.uniform(jax.random.PRNGKey(1), (16,), minval=0.1, maxval=0.4)
    _close(layers.rms_norm_one_plus(x, w, 1e-6), ref.norm(x, w, 1e-6), tol=1e-6)
    _close(layers.rms_norm_one_plus(x, w, 1e-6), layers.rms_norm(x, 1.0 + w, 1e-6), tol=1e-7)
    # at a weight of zero it is the plain norm: the two forms differ by the weight alone
    _close(layers.rms_norm_one_plus(x, jnp.zeros(16), 1e-6), layers.rms_norm(x, jnp.ones(16), 1e-6),
           tol=1e-7)
    assert float(jnp.max(jnp.abs(layers.rms_norm_one_plus(x, w, 1e-6)
                                 - layers.rms_norm(x, w, 1e-6)))) > 0.1


# -- gated attention ---------------------------------------------------------------


@pytest.mark.parametrize("length", [8, 21])  # whole blocks of queries; a ragged tail
def test_gated_attention_is_the_full_score_matrix_forward_and_gradient(length):
    p = _seeded_bundle(TINY, 3).params["seg04_attn"]
    x = jax.random.normal(jax.random.PRNGKey(0), (length, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        (y, g), (y_ref, g_ref) = _both(lambda p_, x_: qn.gated_attention(p_, x_, TINY),
                                       lambda p_, x_: ref.attention_full(p_, x_, _arch(TINY)),
                                       p, x)
    _close(y, y_ref)
    for name in ("w_q", "w_q_gate", "w_k", "w_v", "w_o", "q_norm_weight", "k_norm_weight"):
        _close(g[0][name], g_ref[0][name], tol=1e-4)
        assert float(jnp.max(jnp.abs(g_ref[0][name]))) > 0
    _close(g[1], g_ref[1], tol=1e-4)


def test_only_the_first_quarter_of_a_head_is_turned_by_position():
    """With one query and one key vector at every position, a score built
    from the unturned dimensions alone is the same everywhere; one built
    from the turned dimensions depends on the distance."""
    cfg = replace(TINY, head_dim=16, partial_rotary_factor=0.25)
    turned = int(cfg.head_dim * cfg.partial_rotary_factor)
    assert turned == 4
    a = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(1), (16,)), (12, 1, 16))
    placed = jnp.concatenate([layers.rotary(a[..., :turned], 100.0), a[..., turned:]], axis=-1)
    np.testing.assert_array_equal(placed[..., turned:], a[..., turned:])
    assert float(jnp.max(jnp.abs(placed[1:, :, :turned] - a[1:, :, :turned]))) > 1e-2
    _close(placed[..., :turned], ref.rotate(a[..., :turned], 100.0), tol=1e-6)


# -- the expert layer --------------------------------------------------------------


def _expert_weights(cfg, seed, held):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 9))
    d, f, fs = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size

    def matrix(*shape):
        return jax.random.normal(next(k), shape) / np.sqrt(shape[-2])

    return {"router": matrix(d, cfg.num_experts),
            "experts_gate": matrix(held, d, f), "experts_up": matrix(held, d, f),
            "experts_down": matrix(held, f, d), "shared_gate": matrix(d, fs),
            "shared_up": matrix(d, fs), "shared_down": matrix(fs, d),
            "shared_weight": matrix(d, 1)}


def _share(p, x, cfg, first, held, round_rows, shared):
    cut = slice(first, first + held)
    return held_experts_ffn(
        x, p["router"], p["experts_up"][cut], p["experts_down"][cut],
        p["shared_up"] if shared else None, p["shared_down"] if shared else None,
        first_held=first, n_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
        round_rows=round_rows, w_gate=p["experts_gate"][cut],
        shared_gate=p["shared_gate"] if shared else None, score=jax.nn.softmax,
        shared_weight=p["shared_weight"] if shared else None)


@pytest.mark.parametrize("round_rows", [64, 8, None])  # one round; several; the default
def test_the_sixteen_shares_of_the_softmax_routed_layer_add_up_to_the_uncut_layer(round_rows):
    p = _expert_weights(TINY, 5, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, TINY.hidden_size))
    whole = _arch(TINY, held_experts=[0, 32])
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    with jax.default_matmul_precision("highest"):
        want, want_counts = ref.moe_dense_mask(p, x, whole)
        want_grads = jax.grad(
            lambda p_, x_: jnp.sum(ref.moe_dense_mask(p_, x_, whole)[0] * probe), (0, 1))(p, x)

        def shares(p_, x_):  # sixteen chips, two experts each; the gated shared expert once
            parts = [_share(p_, x_, TINY, first, 2, round_rows, shared=first == 0)
                     for first in range(0, 32, 2)]
            return sum(out for out, _ in parts), [aux for _, aux in parts]

        total, auxes = shares(p, x)
        grads = jax.grad(lambda p_, x_: jnp.sum(shares(p_, x_)[0] * probe), (0, 1))(p, x)
    _close(total, want)
    np.testing.assert_array_equal(
        np.concatenate([aux["held_expert_tokens"] for aux in auxes]), want_counts)
    assert all(int(aux["tokens_dropped"]) == 0 for aux in auxes)
    assert int(np.sum(want_counts)) == 64 * TINY.num_experts_per_tok
    for got, wanted in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        _close(got, wanted, tol=1e-4)


@pytest.mark.parametrize("score, scale", [(jax.nn.softmax, 1.0), (jax.nn.sigmoid, 2.5)],
                         ids=["softmax", "sigmoid"])
def test_a_tokens_weights_sum_to_the_scale(score, scale):
    """Every expert the same expert, all of them held: the routed part is
    that expert's output times the sum of a token's top-k weights."""
    cfg = replace(TINY, num_experts=16, num_experts_per_tok=10)
    p = _expert_weights(cfg, 6, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.hidden_size))
    same = {name: jnp.broadcast_to(p[name], (16, *p[name].shape[1:]))
            for name in ("experts_up", "experts_down", "experts_gate")}
    with jax.default_matmul_precision("highest"):
        out, aux = held_experts_ffn(
            x, p["router"], same["experts_up"], same["experts_down"], first_held=0,
            n_experts=16, top_k=10, scale=scale, w_gate=same["experts_gate"], score=score)
        one = ref._gated(
            x, p["experts_gate"][0], p["experts_up"][0], p["experts_down"][0])
    _close(out, scale * one, tol=1e-5)
    assert int(jnp.sum(aux["held_expert_tokens"])) == 40 * 10


def test_the_shared_experts_gate_is_one_sigmoid_a_token():
    p = _expert_weights(TINY, 7, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, TINY.hidden_size))
    with jax.default_matmul_precision("highest"):
        gated = _share(p, x, TINY, 0, 4, None, shared=True)[0]
        routed = _share(p, x, TINY, 0, 4, None, shared=False)[0]
        shared = ref._gated(
            x, p["shared_gate"], p["shared_up"], p["shared_down"])
    _close(gated - routed, jax.nn.sigmoid(x @ p["shared_weight"]) * shared, tol=1e-5)


def _held_experts_ffn_as_it_was(x, router_w, w_up, w_down, shared_up, shared_down, *, first_held,
                                n_experts, top_k, scale, rows, w_gate=None, shared_gate=None):
    """``parallel.moe.held_experts_ffn`` as the parent of PR 39 computed it
    (its forward, rounds unrolled): sigmoid scores, a ``(T, held)`` gate, the
    combine a take of ``T x held`` rows and a sum over the held experts."""
    from byzpy_tpu.parallel.moe import _expert

    tokens, d = x.shape
    held = w_up.shape[0]
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST))
    top_s, top_e = jax.lax.top_k(scores, top_k)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scale
    local = top_e - first_held
    here = (local >= 0) & (local < held)
    onehot = (local[:, :, None] == jnp.arange(held)[None, None, :]) & here[:, :, None]
    gate = jnp.sum(jnp.where(onehot, weight[:, :, None], 0.0), axis=1)
    routed = jnp.any(onehot, axis=1)
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    weights = (w_up, w_down) if w_gate is None else (w_up, w_down, w_gate)
    out = jnp.zeros_like(x)
    for r in range(int(max(1, -(-int(jnp.max(jnp.sum(routed, axis=0))) // rows)))):
        local_rank = rank - r * rows
        mine = routed & (local_rank >= 0) & (local_rank < rows)
        slot = jnp.where(mine, jnp.arange(held)[None, :] * rows + local_rank, held * rows)
        token_at = jnp.zeros((held * rows,), jnp.int32).at[slot.reshape(-1)].set(
            jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), held), mode="drop")
        per_expert = jax.vmap(_expert)(
            x[token_at].reshape(held, rows, d), *weights).reshape(held * rows, d)
        read = jnp.take(per_expert, slot, axis=0, mode="fill", fill_value=0)
        part = jnp.einsum("te,ted->td", gate.astype(x.dtype), read)
        out = part if r == 0 else out + part
    return out + _expert(x, shared_up, shared_down, shared_gate)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("rows", [48, 16])  # one round; several
@pytest.mark.parametrize("top_k", [6, 3])  # six columns for eight held experts; three
def test_a_sigmoid_routed_call_of_the_expert_layer_is_what_it_was(gated, rows, top_k):
    """The layer goes by a token's picks (since PR 40 in every model, whatever
    their number against the held experts), and they stand in the order of
    their experts: a token's sum over them adds the same products in the same
    order as the sum over all held experts did (the others were zeros). Since
    PR 40 the sum is a multiply-add a column in float32 where it was one
    einsum over the columns: the same terms, equal to a rounding of the last
    bit (bit for bit until then)."""
    cfg = replace(TINY, num_experts=16, num_experts_per_tok=top_k)
    p = _expert_weights(cfg, 8, 8)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, cfg.hidden_size))
    kwargs = dict(first_held=4, n_experts=16, top_k=top_k, scale=1.8,
                  w_gate=p["experts_gate"] if gated else None,
                  shared_gate=p["shared_gate"] if gated else None)
    got, aux = held_experts_ffn(x, p["router"], p["experts_up"], p["experts_down"],
                                p["shared_up"], p["shared_down"], round_rows=rows, **kwargs)
    want = _held_experts_ffn_as_it_was(x, p["router"], p["experts_up"], p["experts_down"],
                                       p["shared_up"], p["shared_down"], rows=rows, **kwargs)
    assert int(aux["expert_rounds"]) == (1 if rows == 48 else -(-int(
        jnp.max(aux["held_expert_tokens"])) // 16))
    assert int(jnp.max(jnp.sum(jnp.asarray(got != 0), axis=1))) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    if not gated:
        arch = {"held_experts": [4, 8], "num_experts_per_tok": top_k,
                "routed_scaling_factor": 1.8}
        _close(got, reference_nemotron_h.moe_dense_mask(p, x, arch)[0], tol=1e-4)


def test_a_round_is_four_times_the_mean_load_in_whole_sublanes():
    assert qn.round_rows(qn.Qwen3NextConfig(), 4096) == 320
    assert qn.round_rows(qn.Qwen3NextConfig(), 4000) == 320  # 78.1 x 4 = 312.5 -> 320
    assert qn.round_rows(TINY, 40) == 24 and qn.round_rows(TINY, 1) == 8


# -- the chain ---------------------------------------------------------------------


def test_the_chain_is_the_references_loss_gradient_and_counts():
    bundle = _seeded_bundle(TINY, 7)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, TINY.vocab_size)
    y = jnp.roll(x, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(bundle.loss_fn)(bundle.params, x, y)
        (want, counts), want_grads = jax.value_and_grad(
            lambda p: ref.loss_and_counts(p, x, y, _arch(TINY)), has_aux=True)(bundle.params)
        # the counts the chain's expert blocks report, block by block
        h, got_counts = x, []
        for seg in bundle.segments[:-1]:
            h = seg.apply(bundle.params[seg.key], h)
            if seg.aux:
                h, aux = h
                got_counts.append(aux["held_expert_tokens"])
                assert int(aux["tokens_dropped"]) == 0
    assert [seg.key for seg in bundle.segments] == list(qn.segment_keys(TINY)) == [
        "seg00_embed", "seg01_delta", "seg02_delta", "seg03_delta", "seg04_attn", "seg05_head"]
    assert abs(float(loss) - float(want)) <= 2e-6 * float(want)
    np.testing.assert_array_equal(np.stack(got_counts), counts)
    assert counts.shape == (4, 4) and int(counts.min()) > 0
    for name in grads:
        for leaf in grads[name]:
            _close(grads[name][leaf], want_grads[name][leaf], tol=2e-4)
            assert float(jnp.max(jnp.abs(want_grads[name][leaf]))) > 0, (name, leaf)


def test_the_seeded_norm_weights_lie_away_from_where_the_two_forms_meet():
    params = _seeded_bundle(TINY, 1).params
    for leaves in params.values():
        for name, leaf in leaves.items():
            if name.endswith("norm_weight"):
                assert 0.1 <= float(leaf.min()) and float(leaf.max()) <= 0.4
            if name == "gate_norm_scale":
                assert 0.75 <= float(leaf.min()) and float(leaf.max()) <= 1.25
    default = qn.init_params(TINY)["seg01_delta"]
    assert not np.any(np.asarray(default["mixer_norm_weight"]))  # a training run's start
    assert np.all(np.asarray(default["gate_norm_scale"]) == 1.0)


def test_a_bundle_keeps_whole_periods():
    with pytest.raises(ValueError, match="whole periods"):
        qn.qwen3_next_bundle(replace(TINY, num_hidden_layers=3))
    kinds = [qn.Qwen3NextConfig().linear(i) for i in range(8)]
    assert kinds == [True, True, True, False] * 2


def test_the_published_sizes_count_625_million_parameters():
    shapes = jax.eval_shape(lambda: qn.qwen3_next_ep16(0).params)
    sizes = {name: sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
             for name, sub in shapes.items()}
    assert sizes == {
        "seg00_embed": 38_895_616, "seg01_delta": 138_582_208, "seg02_delta": 138_582_208,
        "seg03_delta": 138_582_208, "seg04_attn": 132_127_232, "seg05_head": 38_895_616 + 2048}
    assert sum(sizes.values()) == 625_667_136
    delta = shapes["seg01_delta"]
    mixer = sum(delta[k].size for k in (
        "w_qkv", "w_z", "w_ba", "conv_w", "a_log", "dt_bias", "gate_norm_scale", "w_out"))
    assert mixer == 33_718_464
    experts = sum(delta[k].size for k in (
        "router", "experts_gate", "experts_up", "experts_down", "shared_gate", "shared_up",
        "shared_down", "shared_weight"))
    assert experts == 104_859_648
    attn = shapes["seg04_attn"]
    assert sum(attn[k].size for k in ("w_q", "w_q_gate", "w_k", "w_v", "w_o", "q_norm_weight",
                                      "k_norm_weight")) == 27_263_488


# -- the kernels in this model's regime: eight query heads a group, head_dim 256 ----


def _full_scores(q, k, v, kv, per):
    t, hd = q.shape[0], k.shape[1] // kv
    q, k, v = q.reshape(t, kv, per, hd), k.reshape(t, kv, hd), v.reshape(t, kv, hd)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, kv * per * hd)


@pytest.mark.parametrize("t", [256, 300])
def test_kernels_at_head_dim_256_eight_query_heads_a_group_are_the_full_scores(t):
    kv, per, hd = 2, 8, 256
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, probe = (jax.random.normal(key, (t, kv * per * hd)) for key in keys[:2])
    k, v = (jax.random.normal(key, (t, kv * hd)) for key in keys[2:])
    assert pa._blocks(4096, per, backward=False) == (4096, 512, 1024)
    assert pa._blocks(4096, per, backward=True) == (4096, 512, 512)
    with jax.default_matmul_precision("highest"):
        want = _full_scores(q, k, v, kv, per)
        want_grads = jax.grad(lambda *a: jnp.sum(_full_scores(*a, kv, per) * probe), (0, 1, 2))(
            q, k, v)
    got = pa.causal_attention(q, k, v, kv_heads=kv)
    grads = jax.grad(
        lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv) * probe), (0, 1, 2))(q, k, v)
    _close(got, want, tol=1e-4)
    for g, w in zip(grads, want_grads):  # dq, dk, dv
        _close(g, w, tol=2e-4)


def test_gated_attention_by_the_kernels_is_the_map_route(monkeypatch):
    cfg = replace(TINY, head_dim=128, num_attention_heads=4, num_key_value_heads=2)
    p = _seeded_bundle(cfg, 4).params["seg04_attn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (140, cfg.hidden_size))
    asked = []
    routes = []
    for serves in (False, True):
        monkeypatch.setattr(qn, "causal_attention_serves",
                            lambda x_, hd, serves=serves: asked.append(hd) or serves)
        routes.append(_both(lambda p_, x_: qn.gated_attention(p_, x_, cfg),
                            lambda p_, x_: ref.attention_full(p_, x_, _arch(cfg)), p, x))
    assert set(asked) == {128}
    for (y, g), (y_ref, g_ref) in routes:
        _close(y, y_ref, tol=1e-4)
        for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
            _close(got, want, tol=5e-4)
