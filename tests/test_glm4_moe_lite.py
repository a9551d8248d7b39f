"""GLM-4.7-Flash's layers (``models/glm4_moe_lite.py``, ``parallel/moe.py``,
``ops/pallas_attention.py``) against the benchmark's plain reference
(``chipbench/reference_glm4_moe_lite``) on seeded weights, at small sizes
on the CPU: latent attention is the full score matrix, forward and
gradient; rotary scores see position differences only; the shares of the
gated experts tie to the uncut layer; the two-term loss through tree
boundaries is ``jax.grad`` of the whole loss, embedding and head among its
leaves; the kernels at one query head a group of 256."""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import glm4_moe_lite as glm
from byzpy_tpu.models import layers
from byzpy_tpu.ops import pallas_attention as pa
from byzpy_tpu.parallel.moe import held_experts_ffn
from chipbench import reference_glm4_moe_lite as ref
from chipbench import seeded_glm4_moe_lite as seeded

TINY = glm.Glm4MoeLiteConfig(
    hidden_size=32, num_hidden_layers=3, vocab_size=64, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, query_block=8,
    intermediate_size=48, n_routed_experts=16, num_experts_per_tok=3, moe_intermediate_size=24,
    held_experts=(4, 4))


def _arch(cfg, **over):
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "num_attention_heads": cfg.num_attention_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "held_experts": list(cfg.held_experts), "mtp_loss_weight": cfg.mtp_loss_weight, **over}


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _seeded_bundle(cfg, seed):
    """The bundle on the benchmark's seeded weights."""
    bundle = glm.glm4_moe_lite_bundle(cfg, 0)
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


@pytest.mark.parametrize("length", [8, 21])  # whole blocks of queries; a ragged tail
@pytest.mark.parametrize("sizes", ["v_wider", "equal", "qk_wider"])
def test_latent_attention_is_the_full_score_matrix_forward_and_gradient(length, sizes):
    nope, vd = {"v_wider": (8, 16), "equal": (12, 16), "qk_wider": (12, 8)}[sizes]
    cfg = replace(TINY, qk_nope_head_dim=nope, v_head_dim=vd)
    p = _seeded_bundle(cfg, 3).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(0), (length, cfg.hidden_size))
    (y, g), (y_ref, g_ref) = _both(lambda p_, x_: glm.mla_attention(p_, x_, cfg),
                                   lambda p_, x_: ref.mla_full(p_, x_, _arch(cfg)), p, x)
    _close(y, y_ref)
    names = ("w_qa", "q_norm_scale", "w_qb", "w_kva", "w_kr", "kv_norm_scale", "w_kvb", "w_o")
    for name in names:
        _close(g[0][name], g_ref[0][name], tol=1e-4)
        assert float(jnp.max(jnp.abs(g_ref[0][name]))) > 0
    _close(g[1], g_ref[1], tol=1e-4)


def test_rotary_is_the_references_rotation_and_scores_see_position_differences_only():
    x = jax.random.normal(jax.random.PRNGKey(1), (11, 3, 8))
    _close(glm.rotary(x, 1e6), ref.rotate(x, 1e6), tol=1e-6)
    _close(glm.rotary(x[:, 0], 100.0), ref.rotate(x[:, 0], 100.0), tol=1e-6)
    # one query and one key vector at every position: the score of (i, j)
    # is a function of i - j alone, so every diagonal is constant
    q, k = jax.random.normal(jax.random.PRNGKey(2), (2, 8))
    turned_q = glm.rotary(jnp.broadcast_to(q, (16, 8)), 100.0)
    turned_k = glm.rotary(jnp.broadcast_to(k, (16, 8)), 100.0)
    scores = np.asarray(turned_q @ turned_k.T)
    for offset in range(-15, 16):
        diagonal = np.diagonal(scores, offset)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=0, atol=2e-5)
    assert np.ptp(scores[:, 0]) > 1e-2  # and it does depend on the difference


def _gated_weights(cfg, seed, held):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    d, f = cfg.hidden_size, cfg.moe_intermediate_size

    def matrix(*shape):
        return jax.random.normal(next(k), shape) / np.sqrt(shape[-2])

    return {"router": matrix(d, cfg.n_routed_experts),
            "experts_gate": matrix(held, d, f), "experts_up": matrix(held, d, f),
            "experts_down": matrix(held, f, d), "shared_gate": matrix(d, f),
            "shared_up": matrix(d, f), "shared_down": matrix(f, d)}


def _share(p, x, cfg, first, held, round_rows, shared):
    cut = slice(first, first + held)
    return held_experts_ffn(
        x, p["router"], p["experts_up"][cut], p["experts_down"][cut],
        p["shared_up"] if shared else None, p["shared_down"] if shared else None,
        first_held=first, n_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, round_rows=round_rows,
        w_gate=p["experts_gate"][cut], shared_gate=p["shared_gate"] if shared else None)


@pytest.mark.parametrize("round_rows", [64, 8, None])  # one round; several; the default
def test_the_shares_of_the_gated_experts_add_up_to_the_uncut_layer(round_rows):
    p = _gated_weights(TINY, 5, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, TINY.hidden_size))
    whole = _arch(TINY, held_experts=[0, 16])
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    want, want_counts = ref.moe_dense_mask(p, x, whole)
    want_grads = jax.grad(
        lambda p_, x_: jnp.sum(ref.moe_dense_mask(p_, x_, whole)[0] * probe), (0, 1))(p, x)

    def shares(p_, x_):  # four chips, four experts each; the shared expert once
        parts = [_share(p_, x_, TINY, first, 4, round_rows, shared=first == 0)
                 for first in (0, 4, 8, 12)]
        return sum(out for out, _ in parts), [aux for _, aux in parts]

    total, auxes = shares(p, x)
    _close(total, want)
    np.testing.assert_array_equal(
        np.concatenate([aux["held_expert_tokens"] for aux in auxes]), want_counts)
    assert all(int(aux["tokens_dropped"]) == 0 for aux in auxes)
    assert int(np.sum(want_counts)) == 64 * TINY.num_experts_per_tok
    grads = jax.grad(lambda p_, x_: jnp.sum(shares(p_, x_)[0] * probe), (0, 1))(p, x)
    for got, wanted in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        _close(got, wanted, tol=1e-4)


def test_experts_without_a_third_matrix_are_the_squared_relu_experts_they_were():
    p = _gated_weights(TINY, 6, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, TINY.hidden_size))
    out, _ = held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=4, n_experts=16, top_k=3, scale=1.8)
    gated, _ = held_experts_ffn(
        x, p["router"], p["experts_up"], p["experts_down"], p["shared_up"], p["shared_down"],
        first_held=4, n_experts=16, top_k=3, scale=1.8, w_gate=p["experts_gate"],
        shared_gate=p["shared_gate"])
    from chipbench import reference_nemotron_h

    want, _ = reference_nemotron_h.moe_dense_mask(p, x, _arch(TINY))
    _close(out, want)
    assert float(jnp.max(jnp.abs(out - gated))) > 1e-3


@pytest.mark.parametrize("weight", [0.3, 0.0])
def test_the_chain_is_the_reference_two_term_loss_gradient_and_counts(weight):
    cfg = replace(TINY, mtp_loss_weight=weight)
    bundle = _seeded_bundle(cfg, 8)
    # a batch as the trainer packs it: y[t] is x[t + 1]
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 22), 0, cfg.vocab_size)
    x, y = tokens[:, :-1], tokens[:, 1:]
    loss, grads = jax.value_and_grad(bundle.loss_fn)(bundle.params, x, y)
    (want, (counts, terms)), want_grads = jax.value_and_grad(ref.loss_and_counts, has_aux=True)(
        bundle.params, x, y, _arch(cfg))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_allclose(want, terms[0] + weight * terms[1], rtol=1e-6)
    for name in bundle.params:
        for leaf in bundle.params[name]:
            _close(grads[name][leaf], want_grads[name][leaf], tol=2e-4)
    assert counts.shape == (3, 4)  # two expert layers and the MTP module's
    table, head = grads["seg00_embed"]["embedding"], grads["seg05_head"]["w_head"]
    mtp_only = jax.tree_util.tree_leaves(grads["seg04_mtp"])
    if weight:
        assert all(float(jnp.max(jnp.abs(leaf))) > 0 for leaf in mtp_only)
    else:
        # with weight 0 the main path's alone: the MTP module gets no gradient,
        # and the table and the head get the one-term model's
        assert all(float(jnp.max(jnp.abs(leaf))) == 0 for leaf in mtp_only)
        one_term = jax.grad(lambda p: ref.loss_and_counts(p, x, y, _arch(cfg))[1][1][0])(
            bundle.params)
        _close(table, one_term["seg00_embed"]["embedding"], tol=2e-4)
        _close(head, one_term["seg05_head"]["w_head"], tol=2e-4)


def test_the_embedding_and_the_head_hold_both_paths_gradient():
    """The table's and the head's gradient is the sum of the two terms'
    gradients, each taken alone by the reference."""
    bundle = _seeded_bundle(TINY, 11)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 18), 0, TINY.vocab_size)
    x, y = tokens[:, :-1], tokens[:, 1:]
    grads = jax.grad(bundle.loss_fn)(bundle.params, x, y)
    terms = [jax.grad(lambda p, at=at: ref.loss_and_counts(p, x, y, _arch(TINY))[1][1][at])(
        bundle.params) for at in (0, 1)]
    for name, leaf in (("seg00_embed", "embedding"), ("seg05_head", "w_head")):
        both = terms[0][name][leaf] + TINY.mtp_loss_weight * terms[1][name][leaf]
        _close(grads[name][leaf], both, tol=2e-4)
        assert float(jnp.max(jnp.abs(terms[1][name][leaf]))) > 0


def test_the_published_sizes_count_706_million_parameters():
    shapes = jax.eval_shape(lambda: glm.glm47_flash_ep8(0).params)
    sizes = {name: sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
             for name, sub in shapes.items()}
    assert sizes == {
        "seg00_embed": 39_649_280, "seg01_dense": 84_677_888, "seg02_moe": 106_829_056,
        "seg03_moe": 106_829_056, "seg04_moe": 106_829_056, "seg05_moe": 106_829_056,
        "seg06_mtp": 115_223_808, "seg07_head": 39_649_280 + 2048}
    assert sum(sizes.values()) == 706_518_528
    mla = sum(shapes["seg02_moe"][k].size for k in (
        "w_qa", "q_norm_scale", "w_qb", "w_kva", "w_kr", "kv_norm_scale", "w_kvb", "w_o"))
    assert mla == 21_759_232


# -- the kernels in MLA's regime: one query head a key/value head, head_dim 256 --


def _full_scores(q, k, v, heads):
    t, hd = q.shape[0], q.shape[1] // heads
    q, k, v = (a.reshape(t, heads, hd) for a in (q, k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(t, heads * hd)


@pytest.mark.parametrize("t", [256, 300])
def test_kernels_at_head_dim_256_one_query_head_a_group_are_the_full_scores(t):
    heads, hd = 3, 256
    q, k, v, probe = (jax.random.normal(key, (t, heads * hd))
                      for key in jax.random.split(jax.random.PRNGKey(7), 4))
    with jax.default_matmul_precision("highest"):
        want = _full_scores(q, k, v, heads)
        want_grads = jax.grad(lambda *a: jnp.sum(_full_scores(*a, heads) * probe), (0, 1, 2))(
            q, k, v)
    got = pa.causal_attention(q, k, v, kv_heads=heads)
    grads = jax.grad(
        lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=heads) * probe), (0, 1, 2))(q, k, v)
    _close(got, want, tol=1e-4)
    for g, w in zip(grads, want_grads):  # dq, dk, dv
        _close(g, w, tol=2e-4)


def test_latent_attention_by_the_kernels_is_the_map_route(monkeypatch):
    cfg = replace(TINY, qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
                  num_attention_heads=2)
    p = _seeded_bundle(cfg, 4).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (140, cfg.hidden_size))
    asked = []
    routes = []
    for serves in (False, True):
        monkeypatch.setattr(layers, "causal_attention_serves",
                            lambda x_, hd, vd, serves=serves: asked.append((hd, vd)) or serves)
        routes.append(_both(lambda p_, x_: glm.mla_attention(p_, x_, cfg),
                            lambda p_, x_: ref.mla_full(p_, x_, _arch(cfg)), p, x))
    assert set(asked) == {(128, 128)}
    for (y, g), (y_ref, g_ref) in routes:
        _close(y, y_ref, tol=1e-4)
        for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
            _close(got, want, tol=5e-4)


# -- the kernels' route in two forms: q, k and v cut on the activations, or born in rows ----

MLA_LEAVES = ("w_qa", "q_norm_scale", "w_qb", "w_kva", "w_kr", "kv_norm_scale", "w_kvb", "w_o")
LONG = 140  # positions, against latents of 16 and 12 rows


def _forms_config(yarn):
    """Whole-lane heads (96 + 32 / 128, nothing padded), the GLM cell's regime."""
    scaling = layers.YarnScaling(factor=8.0, original_max_position_embeddings=32, mscale=2.0,
                                 mscale_all_dim=1.0) if yarn else None
    return replace(TINY, qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128,
                   num_attention_heads=2, rope_scaling=scaling)


@functools.lru_cache(maxsize=None)
def _two_forms(yarn):
    """``{rows: (output, {leaf: gradient})}`` of latent attention by the
    kernels, the form forced: cut on three axes (``rows`` False, the plain
    reference here) and born in the kernels' rows."""
    cfg = _forms_config(yarn)
    block = _seeded_bundle(cfg, 5).params["seg02_moe"]
    p = {name: block[name] for name in MLA_LEAVES}
    x = jax.random.normal(jax.random.PRNGKey(8), (LONG, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    forms = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(layers, "causal_attention_serves", lambda x_, hd, vd: True)
        for rows in (False, True):
            patch.setattr(layers, "_rows_route_pays", lambda *sizes, rows=rows: rows)
            grads, dx = jax.grad(lambda p_, x_: jnp.sum(glm.mla_attention(p_, x_, cfg) * probe),
                                 argnums=(0, 1))(p, x)
            forms[rows] = (glm.mla_attention(p, x, cfg), {**grads, "x": dx})
    return forms


@pytest.mark.parametrize("what", ["output", "x", *MLA_LEAVES])
@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
def test_whole_lane_heads_born_in_rows_are_the_three_axis_form(yarn, what):
    forms = _two_forms(yarn)
    got, want = (forms[rows][0] if what == "output" else forms[rows][1][what]
                 for rows in (True, False))
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(got, want, tol=5e-6)


def _rows_of_activations(fn, p, x):
    """Shapes of three or more axes that start with the sequence's length
    among what ``fn`` computes outside the attention core."""
    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("custom_vjp"):
                continue  # the kernels' call
            for name in ("jaxpr", "call_jaxpr"):
                if name in eqn.params:
                    inner = eqn.params[name]
                    yield from shapes(getattr(inner, "jaxpr", inner))
            for var in eqn.outvars:
                yield var.aval.shape

    return {s for s in shapes(jax.make_jaxpr(lambda p_, x_: fn(p_, x_))(p, x).jaxpr)
            if len(s) >= 3 and s[0] == x.shape[0]}


def test_born_in_rows_no_activation_takes_a_third_axis(monkeypatch):
    cfg = _forms_config(False)
    p = _seeded_bundle(cfg, 5).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (LONG, cfg.hidden_size))
    monkeypatch.setattr(layers, "causal_attention_serves", lambda x_, hd, vd: True)
    asked = []
    monkeypatch.setattr(layers, "_rows_route_pays", lambda *sizes: asked.append(sizes) or True)
    assert _rows_of_activations(lambda p_, x_: glm.mla_attention(p_, x_, cfg), p, x) == set()
    # the rule is handed the sequence, both latents' rows and the zero lanes a head
    assert asked == [(LONG, cfg.q_lora_rank, cfg.kv_lora_rank, 0)]
    monkeypatch.setattr(layers, "_rows_route_pays", lambda *sizes: False)
    assert (LONG, 2, 128) in _rows_of_activations(
        lambda p_, x_: glm.mla_attention(p_, x_, cfg), p, x)


@pytest.mark.parametrize("sizes, pays", [
    ((4096, 768, 512, 0), True),    # the GLM cell
    ((1024, 768, 512, 64), False),  # the Xing4.0 cell
    ((2048, 768, 512, 0), False), ((3840, 768, 512, 0), True),
    ((4096, 768, 512, 64), False), ((8192, 768, 512, 64), False)])
def test_the_rule_takes_the_rows_at_whole_lane_heads_and_a_sequence_long_against_the_latents(
        sizes, pays):
    assert layers._rows_route_pays(*sizes) is pays


def test_the_cpu_never_asks_the_rule(monkeypatch):
    monkeypatch.setattr(layers, "_rows_route_pays", lambda *sizes: pytest.fail("asked"))
    cfg = _forms_config(False)
    p = _seeded_bundle(cfg, 5).params["seg02_moe"]
    glm.mla_attention(p, jnp.ones((16, cfg.hidden_size)), cfg)
