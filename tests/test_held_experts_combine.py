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
* the round's own size, an eighth of the tokens, serves whatever the router
  does: loads steered to one round, to its edge, to two, three and five rounds
  give the value and the gradients of the layer at a quarter (bit for bit
  where both take one round), no token dropped, never more rows multiplied
  than the rounds of a quarter would; the lowered layer is one batched product
  a matrix a round over the weights as they stand, with no branch;
* what the layer takes and reports is what it took and reported;
* the rows' way back to the tokens (``moe._rows_to_tokens``: the combine's
  forward and the dispatch gather's backward) is the sum written out as a
  Python double loop; its kernel (``ops/pallas_rows_to_tokens.py``, here
  through the interpreter) is the plain form, value and gradients, under
  ``jit`` and ``vmap``; one predicate says which of the two runs; the layer
  is the dense every-expert reference differentiated by ``jax.grad``; no
  ``(T, k, D)`` array and no scatter of rows is left in its lowered gradient.
"""

from __future__ import annotations

import inspect
import re

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


def _plain_dispatch(x, reader_at, slot, filled=None):
    """The gather to the slots as the parent wrote it, left to automatic
    differentiation (its transpose: a scatter-add of ``held x rows`` rows)."""
    return x[reader_at // slot.shape[1]]


def _automatic(monkeypatch):
    monkeypatch.setattr(moe, "_combine", _plain_combine)
    monkeypatch.setattr(moe, "_dispatch", _plain_dispatch)


def _round(dtype, tokens=96, held=4, rows=32, d=16):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    routed = jax.random.uniform(ks[0], (tokens, held)) < 0.3
    routed = routed.at[:, 3].set(False)  # an expert nobody picks
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    mine = routed & (rank < rows)
    slot = jnp.where(mine, jnp.arange(held)[None, :] * rows + rank, held * rows)
    # a slot's one reader, as its place in the (tokens, held) gates laid flat
    token_at = jnp.zeros((held * rows,), jnp.int32).at[slot.reshape(-1)].set(
        jnp.arange(tokens * held, dtype=jnp.int32), mode="drop")
    filled = jnp.sum(mine, axis=0)
    holds_token = (jnp.arange(rows)[None, :] < filled[:, None]).reshape(held * rows)
    per_expert = jax.random.normal(ks[1], (held * rows, d)).astype(dtype)
    gate = jnp.where(mine, jax.random.uniform(ks[2], (tokens, held)), 0.0).astype(dtype)
    d_out = jax.random.normal(ks[3], (tokens, d)).astype(dtype)
    return mine, slot, token_at, (filled, holds_token), per_expert, gate, d_out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_combines_gather_backward_is_the_takes_transpose(dtype):
    """A filled slot is read by exactly one (token, expert) pair, so what
    automatic differentiation does with a scatter-add of T x held rows the
    combine's own backward does with one gather of held x rows."""
    mine, slot, token_at, (filled, holds_token), per_expert, gate, d_out = _round(dtype)
    # whatever sits in a slot no token fills reaches nothing
    poisoned = jnp.where(holds_token[:, None], per_expert, jnp.nan)
    out, pull = jax.vjp(lambda p_, g_: moe._combine(p_, g_, slot, token_at, filled),
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
    _, slot, token_at, (filled, _holds), per_expert, gate, d_out = _round(jnp.float32)

    def backward(combine):
        return str(jax.make_jaxpr(lambda p_, g_, d_: jax.vjp(
            lambda p, g: combine(p, g, slot, token_at, filled), p_, g_)[1](d_))(
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


# a held expert gets 170-200 of the 512 tokens: one round of 256 slots, two
# of 128 (a quarter of the tokens), four of 64 (the default: an eighth), and
# where every token picks expert 0, four of 128
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("round_rows, favour, rounds", [(256, 0.0, 1), (None, 0.0, 4),
                                                        (T // 4, 0.0, 2), (128, 20.0, 4)])
def test_the_layer_with_the_gather_backward_is_the_layer_differentiated_automatically(
        monkeypatch, gated, round_rows, favour, rounds):
    x, p = _layer(gated)
    p["router_w"] = p["router_w"].at[0, 0].add(favour)
    names = sorted(p)

    def loss(x_, weights):
        out, aux = _ffn(x_, dict(zip(names, weights)), round_rows)
        return jnp.sum(out * jnp.cos(out)), (out, aux)

    results = []
    for automatic in (False, True):
        if automatic:
            _automatic(monkeypatch)
        (_, (out, aux)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x, [p[k] for k in names])
        results.append((out, aux, grads))
    (out_own, aux_own, grads_own), (out, aux, grads) = results
    assert int(aux_own["expert_rounds"]) == rounds and int(aux_own["tokens_dropped"]) == 0
    assert int(jnp.max(aux_own["held_expert_tokens"])) > (rounds - 1) * (round_rows or T // 8)
    for key in aux:
        np.testing.assert_array_equal(aux[key], aux_own[key])
    # the forward is the parent's terms in the parent's order, multiplied and added
    # in float32 one column at a time where the parent's einsum summed them at once
    np.testing.assert_allclose(out_own, out, rtol=1e-6, atol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads_own), jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)  # gradients of 1 to 300


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
        "top_k", "scale", "round_rows", "w_gate", "shared_gate", "score", "shared_weight",
        "denominator_eps", "router_input", "activation"]
    x, p = _layer(True)
    assert sorted(_ffn(x, p, None)[1]) == ["expert_rounds", "held_expert_tokens", "tokens_dropped"]
    assert "os.environ" not in inspect.getsource(moe) and "getenv" not in inspect.getsource(moe)


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
def test_the_layer_lowers_to_one_batched_product_a_round(gated):
    x, p = _layer(gated)
    text = jax.jit(lambda x_, p_: _ffn(x_, p_, None)[0]).lower(x, p).as_text()
    assert "pallas" not in text and "custom_call" not in text
    # every slot of a round in one batched product: (held, rows, D) x (held, D, F),
    # the rows an eighth of the tokens; round 0 and the loop's body, no branch
    slots = f"(tensor<{HELD}x{T // 8}x{LD}xf32>, tensor<{HELD}x{LD}x{LF}xf32>)"
    assert text.count(slots) == 2 * (2 if gated else 1) and "stablehlo.case" not in text
    assert f"tensor<{HELD}x{T // 4}x{LD}xf32>" not in text


# -- a round of an eighth of the tokens, whatever the router does ----------------------

# (held, experts, top_k, the score): 8 held of 64 as the GLM, Xing, LFM2 and
# SmallThinker cells hold them, 32 of 512 as Qwen3-Next's
SHARES = {"8of64": (8, 64, 4, jax.nn.sigmoid), "32of512": (32, 512, 10, jax.nn.softmax)}
STEERED_T = 2048  # its eighth is a round of 256 rows, its quarter one of 512
# tokens sent to the first held expert (the others get under 256)
STEERED = (200, 256, 257, 512, 513, 1100)


def _steered_layer(share, gated, load):
    """A layer whose router sends exactly ``load`` tokens (the first ones) to
    the first held expert: feature 0 is one for them and feature 1 for the
    rest, and that expert's two router weights decide."""
    held, n_experts, _top_k, _score = SHARES[share]
    first = n_experts // 8
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    p = {"router_w": (jax.random.normal(ks[1], (LD, n_experts)) * 0.3).at[:2].set(0.0)
         .at[0, first].set(20.0).at[1, first].set(-20.0),
         "w_up": jax.random.normal(ks[2], (held, LD, LF)) / 11,
         "w_down": jax.random.normal(ks[3], (held, LF, LD)) / 8}
    if gated:
        p["w_gate"] = jax.random.normal(ks[4], (held, LD, LF)) / 11
    chosen = (jnp.arange(STEERED_T) < load).astype(jnp.float32)
    x = jax.random.normal(ks[0], (STEERED_T, LD)).at[:, 0].set(chosen).at[:, 1].set(1.0 - chosen)
    return x, p, first


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("load", STEERED)
@pytest.mark.parametrize("share", sorted(SHARES))
def test_a_round_of_an_eighth_serves_whatever_the_router_does(monkeypatch, share, load, gated):
    """At its own size the layer is the layer at a quarter of the tokens (the
    size it had before PR 50): where both take one round its value bit for
    bit in the compiled program (the same pairs through the same matrices, a
    token's picks summed in the same order), past that the same terms summed a
    round at a time; its gradients those of the layer at a quarter and of the
    layer differentiated automatically. It never multiplies more rows than
    the rounds of a quarter would."""
    held, n_experts, top_k, score = SHARES[share]
    x, p, first = _steered_layer(share, gated, load)
    names = sorted(p)
    eighth, quarter = STEERED_T // 8, STEERED_T // 4

    def run(round_rows):
        def loss(x_, weights):
            out, aux = moe.held_experts_ffn(
                x_, **dict(zip(names, weights)), first_held=first, n_experts=n_experts,
                top_k=top_k, round_rows=round_rows, score=score)
            return jnp.sum(out * jnp.cos(out)), (out, aux)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
            x, [p[k] for k in names])

    (_, (out, aux)), grads = run(None)
    counts = np.asarray(aux["held_expert_tokens"])
    assert counts[0] == load == counts.max() and counts.shape == (held,)
    rounds = -(-load // eighth)
    assert int(aux["tokens_dropped"]) == 0 and int(aux["expert_rounds"]) == rounds
    (_, (out_quarter, aux_quarter)), grads_quarter = run(quarter)
    assert int(aux_quarter["expert_rounds"]) == -(-load // quarter)
    assert rounds * eighth <= int(aux_quarter["expert_rounds"]) * quarter
    np.testing.assert_array_equal(counts, aux_quarter["held_expert_tokens"])
    if rounds == 1:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out_quarter))
    else:
        np.testing.assert_allclose(out, out_quarter, rtol=1e-6, atol=1e-6)
    _automatic(monkeypatch)
    (_, (out_auto, _)), grads_auto = run(None)
    np.testing.assert_allclose(out, out_auto, rtol=1e-6, atol=1e-6)
    for want in (grads_quarter, grads_auto):
        for got, ref in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_the_weights_are_read_by_the_rounds_products_and_by_nothing_else():
    """The stacked matrices are arguments of the lowered layer, read by the
    products of round 0 and carried by the loop over further rounds: nothing
    makes an array of their shape."""
    x, p = _layer(True)
    text = jax.jit(lambda x_, p_: _ffn(x_, p_, None)[0]).lower(x, p).as_text()
    main = next(line for line in text.splitlines() if "func.func public @main(" in line)
    stacked = re.findall(rf"(%arg\d+): tensor<{HELD}x(?:{LD}x{LF}|{LF}x{LD})xf32>", main)
    assert len(stacked) == 3
    reads = re.compile(rf"({'|'.join(stacked)})\b")
    readers = [line for line in text.splitlines() if line != main and reads.search(line)]
    assert readers and all("stablehlo.dot_general" in r or "stablehlo.while" in r for r in readers)
    assert text.count("stablehlo.while") == 1


@pytest.mark.parametrize("model, config, layer, asked", [
    ("nemotron_h", "NemotronHConfig", "_moe_mixer", lambda cfg, tokens: None),
    ("glm4_moe_lite", "Glm4MoeLiteConfig", "_expert_ffn", lambda cfg, tokens: None),
    ("lfm2_moe", "Lfm2MoeConfig", "_expert_ffn", lambda cfg, tokens: None),
    ("smallthinker", "SmallThinkerConfig", None, lambda cfg, tokens: None),
    ("xing4", "Xing4Config", "_expert_ffn", lambda cfg, tokens: tokens // 4),
    ("qwen3_next", "Qwen3NextConfig", "_expert_ffn",
     lambda cfg, tokens: 4 * tokens * cfg.num_experts_per_tok // cfg.num_experts),
])
@pytest.mark.parametrize("tokens", [1024, 4096])
def test_which_round_each_model_asks_for(monkeypatch, model, config, layer, asked, tokens):
    """Four models take the layer's own round; the two whose held experts'
    mean load is light hand four times it, which for Xing (4 picks of 64) is
    the quarter of the tokens it had: a second round reads the experts'
    matrices again, and products that short wait for those."""
    import collections
    import importlib

    module = importlib.import_module(f"byzpy_tpu.models.{model}")
    cfg = getattr(module, config)()
    if layer is None:  # written into the block
        assert "round_rows" not in inspect.getsource(module.decoder_block)
        return
    seen = {}
    monkeypatch.setattr(module, "held_experts_ffn",
                        lambda x, *args, **kwargs: seen.update(kwargs) or (x, {}))
    getattr(module, layer)(collections.defaultdict(lambda: None), jnp.zeros((tokens, 8)), cfg)
    assert seen.get("round_rows") == asked(cfg, tokens)


# -- many held experts with a light load each: 32 of 512, top-10 by a softmax ------

MANY_HELD, MANY_EXPERTS, MANY_TOP_K = 32, 512, 10


def _many_layer(seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    p = {"router_w": jax.random.normal(ks[1], (LD, MANY_EXPERTS)) * 0.3,
         "w_up": jax.random.normal(ks[2], (MANY_HELD, LD, LF)) / 11,
         "w_down": jax.random.normal(ks[3], (MANY_HELD, LF, LD)) / 8,
         "w_gate": jax.random.normal(ks[4], (MANY_HELD, LD, LF)) / 11,
         "shared_up": jax.random.normal(ks[5], (LD, LF)) / 11,
         "shared_down": jax.random.normal(ks[6], (LF, LD)) / 8,
         "shared_gate": jax.random.normal(ks[7], (LD, LF)) / 11,
         "shared_weight": jax.random.normal(ks[8], (LD, 1)) / 11}
    return jax.random.normal(ks[0], (T, LD)), p


def _many_ffn(x, p, round_rows, first_held=64):
    return moe.held_experts_ffn(x, **p, first_held=first_held, n_experts=MANY_EXPERTS,
                                top_k=MANY_TOP_K, round_rows=round_rows, score=jax.nn.softmax)


# a held expert gets about 10 of the 512 tokens (512 x 10 / 512): one round
# of 40 slots (four times the mean), several of 8
@pytest.mark.parametrize("round_rows, one_round", [(40, True), (8, False)])
def test_thirty_two_held_of_512_top_10_is_the_layer_differentiated_automatically(
        monkeypatch, round_rows, one_round):
    x, p = _many_layer()
    names = sorted(p)

    def loss(x_, weights):
        out, aux = _many_ffn(x_, dict(zip(names, weights)), round_rows)
        return jnp.sum(out * jnp.cos(out)), (out, aux)

    results = []
    for automatic in (False, True):
        if automatic:
            _automatic(monkeypatch)
        (_, (out, aux)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x, [p[k] for k in names])
        results.append((out, aux, grads))
    (out_own, aux_own, grads_own), (out, aux, grads) = results
    counts = np.asarray(aux_own["held_expert_tokens"])
    assert counts.shape == (MANY_HELD,) and int(aux_own["tokens_dropped"]) == 0
    assert (int(aux_own["expert_rounds"]) == 1) == one_round
    assert int(aux_own["expert_rounds"]) == max(1, -(-int(counts.max()) // round_rows))
    # what the router sent here: a token's picks among experts 64 .. 95
    scores = jax.nn.softmax(jnp.dot(x, p["router_w"], precision=jax.lax.Precision.HIGHEST))
    picked = np.asarray(jax.lax.top_k(scores, MANY_TOP_K)[1])
    np.testing.assert_array_equal(
        counts, [(picked == 64 + e).sum() for e in range(MANY_HELD)])
    np.testing.assert_allclose(out_own, out, rtol=1e-6, atol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads_own), jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_round_moves_its_slots_and_the_tokens_picks_never_tokens_times_held_rows():
    """At 32 held experts a ``(T, held, D)`` read is 16 times the filled
    slots: the lowered layer, forward and backward, holds no tensor of
    ``T x held`` rows and none of ``T x k`` rows either, only the round's
    ``held x rows`` slots and ``(T, D)`` sums."""
    x, p = _many_layer()
    text = jax.jit(jax.grad(lambda x_, p_: jnp.sum(_many_ffn(x_, p_, 40)[0] ** 2), (0, 1))).lower(
        x, p).as_text()
    assert f"tensor<{T}x{MANY_HELD}x{LD}xf32>" not in text
    assert f"tensor<{T * MANY_HELD}x{LD}xf32>" not in text
    assert f"tensor<{T}x{MANY_TOP_K}x{LD}xf32>" not in text  # nor a token's picks side by side
    assert f"tensor<{T * MANY_TOP_K}x{LD}xf32>" not in text
    assert f"tensor<{MANY_HELD}x40x{LD}xf32>" in text  # the round's slots
    # the one scatter left places the picks' readers in the round's slots:
    # T x k int32 indices, no rows
    jaxpr = str(jax.make_jaxpr(
        jax.grad(lambda x_, p_: jnp.sum(_many_ffn(x_, p_, 40)[0] ** 2), (0, 1)))(x, p))
    scatters = [line for line in jaxpr.splitlines() if "= scatter[" in line]
    assert scatters and all(f":i32[{MANY_HELD * 40}] =" in line for line in scatters)
    # the one scatter-add left is top_k's own (a token's k scores back into its
    # row of all experts' scores): the dispatch gather's transpose into the
    # tokens is a read of the filled slots since PR 40
    adds = [line for line in jaxpr.splitlines() if "= scatter-add[" in line]
    assert adds and all(f":f32[{T},{MANY_EXPERTS}] =" in line for line in adds)


def test_a_softmax_router_weighs_by_the_softmax_over_all_experts():
    """All 512 experts held as 16 shares of 32: the shares add up to every
    expert on every token under the dense mask of the router's top 10."""
    x, p = _many_layer()
    x = x[:64]
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    full = {"w_up": jax.random.normal(ks[0], (MANY_EXPERTS, LD, LF)) / 11,
            "w_down": jax.random.normal(ks[1], (MANY_EXPERTS, LF, LD)) / 8,
            "w_gate": jax.random.normal(ks[2], (MANY_EXPERTS, LD, LF)) / 11}
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for first in range(0, MANY_EXPERTS, MANY_HELD):
            part = {k: v[first: first + MANY_HELD] for k, v in full.items()}
            out, aux = moe.held_experts_ffn(
                x, p["router_w"], part["w_up"], part["w_down"], first_held=first,
                n_experts=MANY_EXPERTS, top_k=MANY_TOP_K, w_gate=part["w_gate"],
                score=jax.nn.softmax)
            total = total + out
            assert int(aux["tokens_dropped"]) == 0
        probs = jax.nn.softmax(x @ p["router_w"], axis=-1)
        top_p, top_e = jax.lax.top_k(probs, MANY_TOP_K)
        weight = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(jnp.sum(weight, axis=-1)), 1.0, atol=1e-6)
        want = jnp.zeros_like(x)
        for j in range(MANY_TOP_K):
            e = top_e[:, j]
            hidden = jax.nn.silu(jnp.einsum("td,tdf->tf", x, full["w_gate"][e])) * jnp.einsum(
                "td,tdf->tf", x, full["w_up"][e])
            want = want + weight[:, j, None] * jnp.einsum("tf,tfd->td", hidden, full["w_down"][e])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=2e-4, atol=2e-5)


# -- rows back to the tokens: the plain form, its kernel, the predicate (PR 40) ----

from byzpy_tpu.ops import pallas_rows_to_tokens as prt  # noqa: E402


def _kernel_serves(rows):
    """The route's own gate with the backend's answer left out: what a TPU
    would be asked, answered here by the interpreter."""
    return rows.dtype in (jnp.float32, jnp.bfloat16) and rows.shape[1] % 128 == 0


def _read_back(tokens, held, per, k, d, dtype, seed=7):
    """A round's read-back as the layer lays it out: ``held`` runs of ``per``
    slots, a token's place in a run its rank among the run's tokens, column c
    of a token its c-th held pick. Most columns empty; token 3 picks every
    expert it may, token 5 none, nobody picks the last expert; a run that
    overflows sends its later tokens to another round (a slot far past the
    end); the slots no token fills hold NaN."""
    rng = np.random.default_rng(seed)
    routed = rng.random((tokens, held)) < 0.3
    routed[:, held - 1] = False
    routed[3 % tokens] = True
    routed[3 % tokens, held - 1] = False
    routed[5 % tokens] = False
    routed &= np.cumsum(routed, axis=1) <= k  # at most k picks a token
    rank = np.cumsum(routed, axis=0) - 1
    n_slots = held * per
    slot = np.full((tokens, k), n_slots, np.int32)
    reader_at = np.zeros((n_slots,), np.int32)
    for t in range(tokens):
        for c, e in enumerate(np.flatnonzero(routed[t])):
            if rank[t, e] < per:
                slot[t, c] = e * per + rank[t, e]
                reader_at[slot[t, c]] = t * k + c
            else:
                slot[t, c] = n_slots + 1000
    filled = np.minimum(routed.sum(axis=0), per).astype(np.int32)
    live = (np.arange(per)[None, :] < filled[:, None]).reshape(-1)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    rows = jnp.where(live[:, None], jax.random.normal(ks[0], (n_slots, d)), jnp.nan).astype(dtype)
    gate = jax.random.uniform(ks[1], (tokens, k), minval=0.1).astype(dtype)
    return rows, gate, jnp.asarray(slot), jnp.asarray(reader_at), jnp.asarray(filled)


@pytest.mark.parametrize("tokens, held, per, k, d", [(37, 4, 8, 3, 16), (64, 8, 16, 6, 256),
                                                     (9, 2, 4, 1, 130)],
                         ids=["t37-k3", "t64-k6-d256", "t9-k1-d130"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rows_to_tokens_is_the_sum_written_out(tokens, held, per, k, d, dtype):
    rows, gate, slot, reader_at, filled = _read_back(tokens, held, per, k, d, dtype)
    got = moe._rows_to_tokens(rows, gate, slot, reader_at, filled)
    assert got.shape == (tokens, d) and got.dtype == dtype
    rows_, gate_ = np.asarray(rows, np.float32), np.asarray(gate, np.float32)
    want = np.zeros((tokens, d), np.float32)
    for t in range(tokens):
        for j in range(k):  # a token's columns in their order
            if slot[t, j] < held * per:
                want[t] += gate_[t, j] * rows_[slot[t, j]]
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **tol)
    assert not np.any(np.asarray(got, np.float32)[5 % tokens])  # a token with no filled pick
    assert np.any(want[3 % tokens]) and np.all(np.isfinite(want))


# (tokens, held, per, k, D, block): several filled picks a token; T over a block,
# under one and no multiple of it; runs that are no whole tiles; a run that
# overflows; more tiles a block than copies in flight
KERNEL_SHAPES = [(64, 8, 16, 6, 256, 16), (50, 4, 9, 4, 128, 16), (300, 32, 10, 10, 256, 128),
                 (300, 8, 80, 4, 128, None), (9, 2, 4, 1, 128, None)]


@pytest.mark.parametrize("how", ["jit", "vmap2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("tokens, held, per, k, d, block", KERNEL_SHAPES,
                         ids=[f"t{s[0]}-h{s[1]}x{s[2]}-k{s[3]}-d{s[4]}" for s in KERNEL_SHAPES])
def test_the_kernel_is_the_plain_form(tokens, held, per, k, d, block, dtype, how):
    """The Mosaic kernel's program, run by the interpreter, against the plain
    form: a token's terms in the same order, multiplied and added in float32;
    a NaN in a slot no token fills reaches nothing."""
    cases = [_read_back(tokens, held, per, k, d, dtype, seed=7 + i) for i in range(2)]

    def kernel(rows, gate, slot, reader_at, filled):
        return prt.rows_to_tokens(rows, gate, reader_at, filled, block=block)

    if how == "jit":
        got, want = jax.jit(kernel)(*cases[0]), moe._rows_to_tokens(*cases[0])
    else:
        stacked = [jnp.stack(a) for a in zip(*cases)]
        got, want = jax.jit(jax.vmap(kernel))(*stacked), jax.vmap(moe._rows_to_tokens)(*stacked)
    assert got.shape == want.shape and got.dtype == dtype
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_combine_and_the_dispatch_through_the_kernel_are_those_through_the_plain_form(
        monkeypatch, dtype):
    """Value and both gradients of the combine, and the dispatch gather's
    cotangent into the tokens, with the kernel serving (interpreted) and with
    the plain form; whatever sits in a slot no token fills reaches nothing."""
    mine, slot, reader_at, (filled, holds_token), per_expert, gate, d_out = _round(dtype, d=128)
    poisoned = jnp.where(holds_token[:, None], per_expert, jnp.nan)
    x = jax.random.normal(jax.random.PRNGKey(9), (mine.shape[0], 128)).astype(dtype)

    def both():
        out, pull = jax.vjp(lambda p_, g_: moe._combine(p_, g_, slot, reader_at, filled),
                            poisoned, gate)
        gathered, pull_x = jax.vjp(lambda x_: moe._dispatch(x_, reader_at, slot, filled), x)
        return (out, *pull(d_out), gathered, *pull_x(poisoned))

    plain = both()
    calls = []
    monkeypatch.setattr(moe, "rows_to_tokens_serves", lambda rows: calls.append(rows.shape) or
                        _kernel_serves(rows))
    served = both()
    assert len(calls) == 2  # the combine's forward, the dispatch's backward
    tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    for got, want in zip(served, plain):
        assert got.dtype == want.dtype == dtype and np.all(np.isfinite(np.asarray(got, np.float32)))
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **tol)
    # the dispatch's own backward is the gather's transpose, a scatter-add, term for
    # term (the cotangent of a slot no token fills is zero there: `_combine`'s backward)
    want_dx = jax.vjp(lambda x_: _plain_dispatch(x_, reader_at, slot), x)[1](
        jnp.where(holds_token[:, None], per_expert, 0))[0]
    np.testing.assert_allclose(np.asarray(served[-1], np.float32), np.asarray(want_dx, np.float32),
                               **tol)


@pytest.mark.parametrize("shape, dtype, serves", [
    ((320, 256), jnp.float32, True), ((320, 2688), jnp.float32, True),
    ((320, 256), jnp.bfloat16, True), ((320, 130), jnp.float32, False),
    ((320, 200), jnp.bfloat16, False), ((320, 256), jnp.float16, False),
    ((4, 80, 256), jnp.float32, False)],
    ids=["f32-d256", "f32-d2688", "bf16-d256", "f32-d130", "bf16-d200", "f16", "rank3"])
def test_one_predicate_says_whether_the_kernel_serves(monkeypatch, shape, dtype, serves):
    """Backend, dtype and D, read in one place: on a CPU never; on a TPU for
    float32 / bfloat16 rows of whole lanes. An odd D takes the plain form."""
    rows = jnp.zeros(shape, dtype)
    assert not prt.rows_to_tokens_serves(rows)  # this process runs on a CPU
    monkeypatch.setattr(prt._pk, "_on_tpu", lambda: True)
    assert prt.rows_to_tokens_serves(rows) == serves
    source = inspect.getsource(prt)
    assert "os.environ" not in source and "getenv" not in source


def _dense_reference(x, p, first_held=0):
    """Every held expert on every token under a dense mask of the router's
    top picks, at full precision: what ``jax.grad`` differentiates."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router_w"])
        top_s, top_e = jax.lax.top_k(scores, TOP_K)
        weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(p["w_up"].shape[0]):
            w_e = jnp.sum(jnp.where(top_e == first_held + e, weight, 0.0), axis=-1)
            if "w_gate" in p:
                hidden = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
            else:
                hidden = jnp.square(jax.nn.relu(x @ p["w_up"][e]))
            out = out + w_e[:, None] * (hidden @ p["w_down"][e])
        if "shared_gate" in p:
            shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) @ p["shared_down"]
        else:
            shared = jnp.square(jax.nn.relu(x @ p["shared_up"])) @ p["shared_down"]
        return out + shared


@pytest.mark.parametrize("route", ["plain", "kernel"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
def test_the_layer_is_the_dense_reference_differentiated_by_jax_grad(monkeypatch, gated, route):
    """Value and the gradients of x, router, up, down (and gate) against
    ``jax.grad`` of the dense every-expert reference, at a size whose fullest
    expert needs four rounds; with the kernel serving too."""
    if route == "kernel":
        monkeypatch.setattr(moe, "rows_to_tokens_serves", _kernel_serves)
    x, p = _layer(gated)
    names = sorted(p)

    def loss(fn):
        def f(x_, weights):
            out = fn(x_, dict(zip(names, weights)))
            return jnp.sum(out * jnp.cos(out)), out
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(x, [p[k] for k in names])

    with jax.default_matmul_precision("highest"):
        aux = _ffn(x, p, None)[1]
        assert int(aux["expert_rounds"]) == 4 and int(aux["tokens_dropped"]) == 0
        (_, out), grads = loss(lambda x_, p_: _ffn(x_, p_, None)[0])
    (_, out_want), grads_want = loss(_dense_reference)
    np.testing.assert_allclose(out, out_want, rtol=2e-4, atol=2e-5)
    for name, got, want in zip(["x"] + names, jax.tree_util.tree_leaves(grads),
                               jax.tree_util.tree_leaves(grads_want)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("route", ["plain", "kernel"])
def test_the_lowered_gradient_scatters_no_rows_and_holds_no_picks_side_by_side(
        monkeypatch, route):
    """On the lowered gradient of a toy expert block: no scatter whose update
    is D wide, no ``(T, k, D)`` tensor, and the kernel's calls (forward and
    the dispatch's backward) stand under ``model.moe_experts``."""
    if route == "kernel":
        monkeypatch.setattr(moe, "rows_to_tokens_serves", _kernel_serves)
    x, p = _layer(True)
    grad = jax.grad(lambda x_, p_: jnp.sum(_ffn(x_, p_, None)[0] ** 2), (0, 1))
    text = jax.jit(grad).lower(x, p).as_text(debug_info=True)
    scatters = [line for line in text.splitlines() if "stablehlo.scatter" in line]
    assert scatters and not [line for line in scatters if f"x{LD}xf32>" in line]
    for picks in (TOP_K, HELD):
        assert f"tensor<{T}x{picks}x{LD}xf32>" not in text
        assert f"tensor<{T * picks}x{LD}xf32>" not in text
    jaxpr = str(jax.make_jaxpr(grad)(x, p))
    assert not [line for line in jaxpr.splitlines()
                if "scatter" in line and f",{LD}]" in line.split("=")[0]]
    if route == "kernel":
        assert jaxpr.count("name=rows_to_tokens") >= 2  # forward and the dispatch's backward
        # the kernel's name is a segment of its ops' `op_name`, inside the part's scope
        compiled = jax.jit(grad).lower(x, p).compile().as_text()
        named = set(re.findall(r'op_name="([^"]*rows_to_tokens/[^"]*)"', compiled))
        assert named and all("model.moe_experts" in name for name in named)
        assert any(name.startswith("jit(<lambda>)/transpose(") for name in named)  # the backward's
