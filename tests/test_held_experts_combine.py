"""The combine of ``parallel.moe.held_experts_ffn``'s rounds (``moe._combine``)
and its own backward.

Held here:

* the combine's backward, a gather (a filled slot has exactly one reader), is
  the transpose automatic differentiation gives the take (a scatter-add of
  ``T x held`` rows): the slots' cotangent, exactly zero where no token sits,
  and the gates'; whatever sits in a slot no token fills (a NaN) reaches
  nothing; no scatter-add is left in it;
* through ``held_experts_ffn`` the layer with that backward is the layer
  differentiated automatically: output, the tokens' gradient and every
  weight's, two-matrix and gated experts, in one round, two and four; no token
  dropped, the counts unchanged;
* an expert no token reaches gets an exactly zero gradient;
* what the layer takes and reports is what it took and reported.
"""

from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.parallel import moe

HELD = 4
T, N_EXPERTS, TOP_K = 512, 8, 3
LD, LF = 128, 64


def _plain_combine(per_expert, gate, slot, token_at=None, holds_token=None):
    """The combine as the parent wrote it, left to automatic differentiation."""
    read = jnp.take(per_expert, slot, axis=0, mode="fill", fill_value=0)
    return jnp.einsum("te,ted->td", gate, read)


def _round(dtype, tokens=96, held=4, rows=32, d=16):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    routed = jax.random.uniform(ks[0], (tokens, held)) < 0.3
    routed = routed.at[:, 3].set(False)  # an expert nobody picks
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    mine = routed & (rank < rows)
    slot = jnp.where(mine, jnp.arange(held)[None, :] * rows + rank, held * rows)
    token_at = jnp.zeros((held * rows,), jnp.int32).at[slot.reshape(-1)].set(
        jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), held), mode="drop")
    filled = jnp.sum(mine, axis=0)
    holds_token = (jnp.arange(rows)[None, :] < filled[:, None]).reshape(held * rows)
    per_expert = jax.random.normal(ks[1], (held * rows, d)).astype(dtype)
    gate = jnp.where(mine, jax.random.uniform(ks[2], (tokens, held)), 0.0).astype(dtype)
    d_out = jax.random.normal(ks[3], (tokens, d)).astype(dtype)
    return mine, slot, token_at, holds_token, per_expert, gate, d_out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_combines_gather_backward_is_the_takes_transpose(dtype):
    """A filled slot is read by exactly one (token, expert) pair, so what
    automatic differentiation does with a scatter-add of T x held rows the
    combine's own backward does with one gather of held x rows."""
    mine, slot, token_at, holds_token, per_expert, gate, d_out = _round(dtype)
    # whatever sits in a slot no token fills reaches nothing
    poisoned = jnp.where(holds_token[:, None], per_expert, jnp.nan)
    out, pull = jax.vjp(lambda p_, g_: moe._combine(p_, g_, slot, token_at, holds_token),
                        poisoned, gate)
    want, pull_want = jax.vjp(lambda p_, g_: _plain_combine(p_, g_, slot),
                              jnp.where(holds_token[:, None], per_expert, 0), gate)
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), **tol)
    (d_per_expert, d_gate), (d_per_expert_want, d_gate_want) = pull(d_out), pull_want(d_out)
    assert d_per_expert.dtype == dtype and d_gate.dtype == dtype
    np.testing.assert_allclose(np.asarray(d_per_expert, np.float32),
                               np.asarray(d_per_expert_want, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(d_gate, np.float32),
                               np.asarray(jnp.where(mine, d_gate_want, 0), np.float32), **tol)
    assert not np.any(np.asarray(d_per_expert, np.float32)[~np.asarray(holds_token)])


def test_the_combines_backward_holds_no_scatter_add():
    _, slot, token_at, holds_token, per_expert, gate, d_out = _round(jnp.float32)

    def backward(combine):
        return str(jax.make_jaxpr(lambda p_, g_, d_: jax.vjp(
            lambda p, g: combine(p, g, slot, token_at, holds_token), p_, g_)[1](d_))(
                per_expert, gate, d_out))

    assert "scatter-add" in backward(_plain_combine)
    assert "scatter" not in backward(moe._combine)


def _layer(gated, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    p = {"router_w": jax.random.normal(ks[1], (LD, N_EXPERTS)) * 0.3,
         "w_up": jax.random.normal(ks[2], (HELD, LD, LF)) / 11,
         "w_down": jax.random.normal(ks[3], (HELD, LF, LD)) / 8,
         "shared_up": jax.random.normal(ks[5], (LD, 2 * LF)) / 11,
         "shared_down": jax.random.normal(ks[6], (2 * LF, LD)) / 11}
    if gated:
        p["w_gate"] = jax.random.normal(ks[4], (HELD, LD, LF)) / 11
        p["shared_gate"] = jax.random.normal(ks[7], (LD, 2 * LF)) / 11
    # feature 0 is one for every token: a router weight on it moves an expert's
    # score for all of them at once
    return jax.random.normal(ks[0], (T, LD)).at[:, 0].set(1.0), p


def _ffn(x, p, round_rows):
    return moe.held_experts_ffn(x, **p, first_held=0, n_experts=N_EXPERTS, top_k=TOP_K,
                                round_rows=round_rows)


# a held expert gets 170-200 of the 512 tokens: one round of 256 slots,
# two of 128 (the default: a quarter of the tokens), and where every token
# picks expert 0, four
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("round_rows, favour, rounds", [(256, 0.0, 1), (None, 0.0, 2),
                                                        (128, 20.0, 4)])
def test_the_layer_with_the_gather_backward_is_the_layer_differentiated_automatically(
        monkeypatch, gated, round_rows, favour, rounds):
    x, p = _layer(gated)
    p["router_w"] = p["router_w"].at[0, 0].add(favour)
    names = sorted(p)

    def loss(x_, weights):
        out, aux = _ffn(x_, dict(zip(names, weights)), round_rows)
        return jnp.sum(out * jnp.cos(out)), (out, aux)

    results = []
    for combine in (_plain_combine, moe._combine):
        monkeypatch.setattr(moe, "_combine", combine)
        (_, (out, aux)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x, [p[k] for k in names])
        results.append((out, aux, grads))
    (out, aux, grads), (out_own, aux_own, grads_own) = results
    assert int(aux_own["expert_rounds"]) == rounds and int(aux_own["tokens_dropped"]) == 0
    assert int(jnp.max(aux_own["held_expert_tokens"])) > (rounds - 1) * (round_rows or T // 4)
    for key in aux:
        np.testing.assert_array_equal(aux[key], aux_own[key])
    np.testing.assert_array_equal(out_own, out)  # the forward is the parent's, to the letter
    for got, want in zip(jax.tree_util.tree_leaves(grads_own), jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_an_expert_no_token_reaches_gets_an_exactly_zero_gradient():
    x, p = _layer(False)
    p["router_w"] = p["router_w"].at[0, 1].add(-20.0)  # nobody picks expert 1
    grads = jax.grad(lambda w: jnp.sum(_ffn(x, w, None)[0] ** 2))(p)
    assert int(_ffn(x, p, None)[1]["held_expert_tokens"][1]) == 0
    assert not np.any(np.asarray(grads["w_up"][1])) and not np.any(np.asarray(grads["w_down"][1]))
    assert np.any(np.asarray(grads["w_up"][0]))
    assert all(np.all(np.isfinite(g)) for g in jax.tree_util.tree_leaves(grads))


def test_the_layer_takes_and_reports_what_it_did():
    assert list(inspect.signature(moe.held_experts_ffn).parameters) == [
        "x", "router_w", "w_up", "w_down", "shared_up", "shared_down", "first_held", "n_experts",
        "top_k", "scale", "round_rows", "w_gate", "shared_gate"]
    x, p = _layer(True)
    assert sorted(_ffn(x, p, None)[1]) == ["expert_rounds", "held_expert_tokens", "tokens_dropped"]
    assert "os.environ" not in inspect.getsource(moe) and "getenv" not in inspect.getsource(moe)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
def test_the_layer_lowers_to_one_batched_product_a_round(gated):
    x, p = _layer(gated)
    text = jax.jit(lambda x_, p_: _ffn(x_, p_, None)[0]).lower(x, p).as_text()
    assert "pallas" not in text and "custom_call" not in text
    # every slot of a round in one batched product: (held, rows, D) x (held, D, F)
    assert f"tensor<{HELD}x{T // 4}x{LD}xf32>" in text and "dot_general" in text
