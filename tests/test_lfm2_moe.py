"""LFM2's layers (``models/lfm2_moe.py``, ``models/layers.py``,
``parallel/moe.py``, ``models/bundle.py``) against the benchmark's plain
reference (``chipbench/reference_lfm2_moe``) on seeded weights, at small
sizes on the CPU: the gated short convolution's own backward against
automatic differentiation of the definition (odd lengths, T < 3); the
operators and the whole chain, loss and gradients, leaf by leaf, the tied
table's leaf among them; four broken variants that each FAIL the same
comparison; the eight shares of the expert layer tie to the uncut layer of
64; the streamed round on the tied bundle is the (n, d) round on
``chain_loss`` of the same bundle."""

from __future__ import annotations

import ast
import os
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import layers, lfm2_moe
from byzpy_tpu.models.bundle import ModelBundle, Segment, chain_loss
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel.moe import held_experts_ffn
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step
from chipbench import reference_lfm2_moe as ref
from chipbench import seeded_lfm2_moe as seeded

# heads of 64, two key/value heads (one lane tile), two query heads each
TINY = lfm2_moe.Lfm2MoeConfig(
    hidden_size=128, layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
    vocab_size=96, num_attention_heads=2, num_key_value_heads=2, query_block=8,
    intermediate_size=48, num_experts=16, num_experts_per_tok=3, moe_intermediate_size=24,
    held_experts=(4, 4))
PUBLISHED = lfm2_moe.Lfm2MoeConfig()


def _arch(cfg, **over):
    return {"norm_eps": cfg.norm_eps, "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "router_denominator_eps": cfg.router_denominator_eps,
            "held_experts": list(cfg.held_experts), **over}


def _gap(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want))) / max(float(np.max(np.abs(want))), 1e-6)


def _close(got, want, tol=2e-5):
    assert _gap(got, want) <= tol


def _norm_gap(got, want):
    """The comparison's own form (``chipbench.reference.worst_leaf_norm_gap``
    of one leaf): the gap of the two norms over the reference's."""
    got, want = float(jnp.linalg.norm(got)), float(jnp.linalg.norm(want))
    return abs(got - want) / want


def _seeded_bundle(cfg, seed):
    """The bundle on the benchmark's seeded weights."""
    bundle = lfm2_moe.lfm2_moe_bundle(cfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bundle.params)
    return bundle.with_params(seeded.make_params(shapes, seed, {}))


def _batch(cfg, seed, batch=2, length=19):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, cfg.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


# -- the gated short convolution ------------------------------------------------


def _definition(bcx, w):
    """``C * conv(B * X)`` position by position."""
    hidden = w.shape[1]
    b, c, x = bcx[:, :hidden], bcx[:, hidden:2 * hidden], bcx[:, 2 * hidden:]
    g = b * x
    rows = []
    for t in range(bcx.shape[0]):
        acc = jnp.zeros((hidden,), bcx.dtype)
        for j in range(w.shape[0]):
            at = t - (w.shape[0] - 1) + j
            if at >= 0:
                acc = acc + w[j] * g[at]
        rows.append(c[t] * acc)
    return jnp.stack(rows)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("length", [1, 2, 3, 7, 33])
def test_the_gated_convolutions_own_backward_is_jax_grad_of_the_definition(length, taps):
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    hidden = 16
    bcx = jax.random.normal(keys[0], (length, 3 * hidden))
    w = jax.random.normal(keys[1], (taps, hidden))
    probe = jax.random.normal(keys[2], (length, hidden))
    _close(layers.gated_short_conv(bcx, w), _definition(bcx, w), tol=1e-6)
    got = jax.grad(lambda a, b: jnp.sum(layers.gated_short_conv(a, b) * probe), (0, 1))(bcx, w)
    want = jax.grad(lambda a, b: jnp.sum(_definition(a, b) * probe), (0, 1))(bcx, w)
    for g, wanted in zip(got, want):
        _close(g, wanted, tol=1e-5)


def test_the_gated_convolution_under_vmap_sums_the_taps_gradient_over_the_sequences():
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    bcx, w = jax.random.normal(keys[0], (3, 9, 24)), jax.random.normal(keys[1], (3, 8))
    got = jax.grad(lambda w_: jnp.sum(jax.vmap(lambda s: layers.gated_short_conv(s, w_))(bcx)))(w)
    want = sum(jax.grad(lambda w_, s=s: jnp.sum(_definition(s, w_)))(w) for s in bcx)
    _close(got, want, tol=1e-5)


def _both(fn_program, fn_reference, p, x):
    probe = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    got = jax.value_and_grad(lambda p_, x_: jnp.sum(fn_program(p_, x_) * probe), (0, 1))(p, x)
    want = jax.value_and_grad(lambda p_, x_: jnp.sum(fn_reference(p_, x_) * probe), (0, 1))(p, x)
    return got, want


@pytest.mark.parametrize("length", [2, 8, 21])
@pytest.mark.parametrize("which", ["conv", "full_attention"])
def test_each_operator_is_the_references_forward_and_gradient(which, length):
    bundle = _seeded_bundle(TINY, 4)
    name = {"conv": "seg01_conv_dense", "full_attention": "seg02_attn_moe"}[which]
    p = bundle.params[name]
    x = jax.random.normal(jax.random.PRNGKey(length), (length, TINY.hidden_size))
    program = {"conv": lfm2_moe.short_conv_operator, "full_attention": lfm2_moe.gqa_attention}
    reference = {"conv": ref.short_conv, "full_attention": ref.attention_full}
    (got, got_grads), (want, want_grads) = _both(
        lambda p_, x_: program[which](p_, x_, TINY),
        lambda p_, x_: reference[which](p_, x_, _arch(TINY)), p, x)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)) + 1e-5
    _close(got_grads[1], want_grads[1], tol=1e-4)
    used = {"conv": ("w_in", "conv_w", "w_out"),
            "full_attention": ("w_q", "w_k", "w_v", "w_o", "q_norm_scale", "k_norm_scale")}
    for leaf in used[which]:
        assert float(jnp.max(jnp.abs(want_grads[0][leaf]))) > 0, leaf
        _close(got_grads[0][leaf], want_grads[0][leaf], tol=1e-4)


# -- the chain ---------------------------------------------------------------------


def _chain_gaps(bundle, cfg, params=None, seed=3):
    """The worst relative gap, leaf by leaf, between the bundle's loss and
    gradients and the reference's (on ``params``, where the two differ)."""
    x, y = _batch(cfg, seed)
    loss, grads = jax.value_and_grad(bundle.loss_fn)(bundle.params, x, y)
    (want, counts), want_grads = jax.value_and_grad(ref.loss_and_counts, has_aux=True)(
        params or bundle.params, x, y, _arch(cfg))
    gaps = {"loss": abs(float(loss) - float(want)) / abs(float(want))}
    norm_gaps = {}
    for name in bundle.params:
        for leaf in bundle.params[name]:
            assert float(jnp.max(jnp.abs(want_grads[name][leaf]))) > 0, (name, leaf)
            gaps[f"{name}.{leaf}"] = _gap(grads[name][leaf], want_grads[name][leaf])
            norm_gaps[f"{name}.{leaf}"] = _norm_gap(grads[name][leaf], want_grads[name][leaf])
    return gaps, norm_gaps, counts


LOSS_TOL, GRAD_TOL = 1e-5, 2e-4


def test_the_chain_is_the_reference_loss_gradient_and_counts():
    bundle = _seeded_bundle(TINY, 8)
    gaps, _, counts = _chain_gaps(bundle, TINY)
    assert gaps.pop("loss") <= LOSS_TOL
    assert max(gaps.values()) <= GRAD_TOL, max(gaps.items(), key=lambda kv: kv[1])
    assert "seg00_embed.embedding" in gaps and list(bundle.params["seg04_head"]) == ["norm_scale"]
    assert counts.shape == (2, 4)  # two expert layers, four held experts
    x, _ = _batch(TINY, 3)
    h, got = x, []
    for seg in bundle.segments[:-1]:
        h = seg.apply(bundle.params[seg.key], h)
        if seg.aux:
            h, aux = h
            got.append(aux["held_expert_tokens"])
            assert int(aux["tokens_dropped"]) == 0
        assert h.shape == (2, 19, TINY.hidden_size)
    np.testing.assert_array_equal(np.stack(got), counts)


def test_the_tables_gradient_holds_both_its_uses():
    """Each use alone is far from the whole, element by element and by the
    comparison's own form (the gap of the leaf's norms, over the cell's
    limit of 0.032)."""
    bundle = _seeded_bundle(TINY, 8)
    x, y = _batch(TINY, 3)
    whole = jax.grad(bundle.loss_fn)(bundle.params, x, y)["seg00_embed"]["embedding"]
    frozen = jax.lax.stop_gradient(bundle.params["seg00_embed"]["embedding"])
    # the tokens embedded from a table no gradient reaches: the head's path alone
    head_path = chain_loss(_with_first(bundle.segments, lambda p, t: frozen[t]))
    head_only = jax.grad(head_path)(bundle.params, x, y)["seg00_embed"]["embedding"]
    embed_only = whole - head_only
    assert float(jnp.max(jnp.abs(head_only))) > 0 and float(jnp.max(jnp.abs(embed_only))) > 0
    print("norm gaps of one path alone", _norm_gap(head_only, whole), _norm_gap(embed_only, whole))
    assert min(_norm_gap(head_only, whole), _norm_gap(embed_only, whole)) > 0.032
    assert min(_gap(head_only, whole), _gap(embed_only, whole)) > 0.2


def _with_first(segments, apply):
    return (Segment(segments[0].key, apply),) + tuple(segments[1:])


def _broken(monkeypatch, what, cfg):
    """The bundle with one thing wrong."""
    bundle = _seeded_bundle(cfg, 8)
    if what == "dropped_gate":
        real = layers.gated_short_conv
        monkeypatch.setattr(lfm2_moe, "gated_short_conv", lambda bcx, w: real(
            jnp.concatenate([bcx[:, :w.shape[1]], jnp.ones_like(bcx[:, :w.shape[1]]),
                             bcx[:, 2 * w.shape[1]:]], axis=1), w))
    elif what == "reversed_taps":
        real = layers.gated_short_conv
        monkeypatch.setattr(lfm2_moe, "gated_short_conv", lambda bcx, w: real(bcx, w[::-1]))
    elif what == "ignored_head_norm_weight":
        real = lfm2_moe.rms_norm
        monkeypatch.setattr(lfm2_moe, "rms_norm", lambda x, scale, eps: real(
            x, jnp.ones_like(scale) if scale.shape == (cfg.head_dim,) else scale, eps))
    elif what == "table_holds_the_heads_path_only":
        frozen = jax.lax.stop_gradient(bundle.params["seg00_embed"]["embedding"])
        segments = _with_first(bundle.segments, lambda p, t: frozen[t])
        return ModelBundle(apply_fn=None, params=bundle.params, segments=segments)
    return bundle


@pytest.mark.parametrize("what", ["dropped_gate", "reversed_taps", "ignored_head_norm_weight",
                                  "table_holds_the_heads_path_only"])
def test_each_broken_variant_fails_the_comparison(monkeypatch, what):
    bundle = _broken(monkeypatch, what, TINY)
    gaps, norm_gaps, _ = _chain_gaps(bundle, TINY)
    loss_gap = gaps.pop("loss")
    # by one of the limits at least, and by a wide margin: ten times the sound gap
    assert loss_gap > 10 * LOSS_TOL or max(gaps.values()) > 10 * GRAD_TOL, (what, loss_gap)
    assert max(gaps.values()) > 10 * GRAD_TOL, (what, max(gaps.values()))
    # and in the form the chip's comparison has, the worst leaf's gap of norms,
    # over the cell's limit (0.032)
    assert max(norm_gaps.values()) > 0.032, (what, max(norm_gaps.values()))
    if what == "table_holds_the_heads_path_only":
        assert norm_gaps["seg00_embed.embedding"] > 0.032 or gaps["seg00_embed.embedding"] > 0.3


def test_a_layer_type_the_model_does_not_have_is_refused():
    with pytest.raises(ValueError, match="full_attention"):
        lfm2_moe.lfm2_moe_bundle(replace(TINY, layer_types=("conv", "sliding_attention")))


# -- the tied leaf's rule -------------------------------------------------------------


def test_a_segment_reads_only_segments_before_it():
    bundle = _seeded_bundle(TINY, 1)
    head = bundle.segments[-1]
    assert head.reads == ("seg00_embed",)
    late = (bundle.segments[0], replace(bundle.segments[1], reads=("seg04_head",)),
            *bundle.segments[2:])
    with pytest.raises(ValueError, match="before it"):
        ModelBundle(apply_fn=None, params=bundle.params, segments=late)


@pytest.mark.parametrize("attack", ["signflip", "none"])
def test_the_streamed_round_of_the_tied_bundle_is_the_n_by_d_round(attack):
    n = 8
    b, attack_fn = {"signflip": (2, coordinatewise.RoundAttack(
        attack_ops.sign_flip, of="honest_mean")), "none": (0, None)}[attack]
    cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=0.05, momentum=0.9)
    streamed = _seeded_bundle(TINY, 2)
    whole = ModelBundle(apply_fn=None, params=streamed.params,
                        loss_fn=chain_loss(streamed.segments))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (3, n, 1, 18), 0, TINY.vocab_size)
    results = []
    for bundle in (streamed, whole):
        step, opt = build_ps_train_step(bundle, partial(robust.trimmed_mean, f=2), cfg,
                                        attack=attack_fn)
        step = jax.jit(step)
        params, seen = bundle.params, []
        for i, batch in enumerate(tokens):
            params, opt, metrics = step(params, opt, batch[..., :-1], batch[..., 1:],
                                        jax.random.PRNGKey(i))
            seen.append(metrics)
        results.append((params, opt, seen))
    (p_s, o_s, m_s), (p_w, o_w, m_w) = results
    # one leaf, one momentum, one update: the table is in the first segment alone
    assert [k for k, sub in p_s.items() if "embedding" in sub] == ["seg00_embed"]
    assert jax.tree_util.tree_structure(o_s["seg00_embed"]) == jax.tree_util.tree_structure(
        build_ps_train_step(streamed, robust.coordinate_median, cfg)[1]["seg00_embed"])
    for got, want in zip(jax.tree_util.tree_leaves((p_s, o_s)),
                         jax.tree_util.tree_leaves((p_w, o_w))):
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-6)
    for got, want in zip(m_s, m_w):
        np.testing.assert_allclose(got["honest_loss"], want["honest_loss"], rtol=1e-6)
        np.testing.assert_allclose(got["agg_grad_norm"], want["agg_grad_norm"], rtol=1e-5)
        assert got["segment_aux"]["seg02_attn_moe"]["held_expert_tokens"].shape == (n - b, 4)


def test_the_streamed_step_keeps_no_array_of_the_tables_size_a_worker_but_its_rows():
    """What the round holds of the table: the leaf, its momentum (each once
    in, once out), and the h rows that both paths' gradients meet in; no
    second stack of h rows (a cotangent riding back along the chain), no
    copy a worker."""
    n, b = 8, 2
    cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=0.05, momentum=0.9)
    bundle = _seeded_bundle(replace(TINY, vocab_size=128), 2)  # a table of whole tiles
    step, opt = build_ps_train_step(bundle, partial(robust.trimmed_mean, f=2), cfg,
                                    attack=coordinatewise.RoundAttack(
                                        attack_ops.sign_flip, of="honest_mean"))
    tokens = jnp.zeros((n, 1, 18), jnp.int32)
    jaxpr = jax.make_jaxpr(step)(bundle.params, opt, tokens, tokens, jax.random.PRNGKey(0))
    size = 128 * TINY.hidden_size

    def stacks(jaxpr, found):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                if len(shape) >= 2 and int(np.prod(shape[1:])) == size and shape[0] in (n - b, n):
                    found.add((eqn.primitive.name, shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                stacks(sub, found)
        return found

    found = stacks(jaxpr.jaxpr, set())
    # made ONCE (`empty`, at the head's turn), carried by the two loops that
    # write it (the head's, the embedding's) and by the loops between; the
    # byzantine rows are broadcast into it and the aggregate reads it flat
    assert [shape for name, shape in found if name == "empty"] == [(n, size // 128, 128)]
    # a boundary kept for the h honest workers, or a cotangent's stack, would
    # have h = 6 rows: the rows of a round that writes the byzantine ones have n
    # (the attack reads `stack[:h]`, a view)
    assert not [(name, shape) for name, shape in found
                if shape[0] == n - b and name not in ("slice", "reshape")]


# -- the share the chip holds ----------------------------------------------------------


def _expert_weights(cfg, seed, held):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    d, f = cfg.hidden_size, cfg.moe_intermediate_size

    def matrix(*shape):
        return jax.random.normal(next(k), shape) / np.sqrt(shape[-2])

    return {"router": matrix(d, cfg.num_experts),
            "experts_gate": matrix(held, d, f), "experts_up": matrix(held, d, f),
            "experts_down": matrix(held, f, d)}


@pytest.mark.parametrize("round_rows", [None, 8])  # the default (a quarter); several rounds
def test_the_eight_shares_of_the_expert_layer_add_up_to_the_uncut_layer_of_64(round_rows):
    """The published router: 64 outputs, top-4, over (their sum + 1e-6),
    times 1. Eight chips of eight experts each, no shared expert."""
    cfg = replace(TINY, num_experts=64, num_experts_per_tok=4)
    p = _expert_weights(cfg, 5, 64)
    x = jax.random.normal(jax.random.PRNGKey(0), (96, cfg.hidden_size))
    whole = _arch(cfg, held_experts=[0, 64])
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    want, want_counts = ref.moe_dense_mask(p, x, whole)
    want_grads = jax.grad(
        lambda p_, x_: jnp.sum(ref.moe_dense_mask(p_, x_, whole)[0] * probe), (0, 1))(p, x)

    def share(p_, x_, first):
        cut = slice(first, first + 8)
        return held_experts_ffn(
            x_, p_["router"], p_["experts_up"][cut], p_["experts_down"][cut],
            first_held=first, n_experts=64, top_k=4, scale=1.0, round_rows=round_rows,
            w_gate=p_["experts_gate"][cut], denominator_eps=1e-6)

    def shares(p_, x_):
        parts = [share(p_, x_, first) for first in range(0, 64, 8)]
        return sum(out for out, _ in parts), [aux for _, aux in parts]

    total, auxes = shares(p, x)
    _close(total, want)
    np.testing.assert_array_equal(
        np.concatenate([aux["held_expert_tokens"] for aux in auxes]), want_counts)
    assert all(int(aux["tokens_dropped"]) == 0 for aux in auxes)
    assert int(np.sum(want_counts)) == 96 * 4
    grads = jax.grad(lambda p_, x_: jnp.sum(shares(p_, x_)[0] * probe), (0, 1))(p, x)
    for got, wanted in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        _close(got, wanted, tol=1e-4)


def test_the_denominators_eps_is_an_argument_that_adds_no_op_where_it_is_not_given():
    cfg = replace(TINY, num_experts=16)
    p = _expert_weights(cfg, 1, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, cfg.hidden_size))

    def layer(**kwargs):
        return lambda x_: held_experts_ffn(
            x_, p["router"], p["experts_up"], p["experts_down"], first_held=0, n_experts=16,
            top_k=3, w_gate=p["experts_gate"], **kwargs)[0]

    plain, zero = (str(jax.make_jaxpr(layer(**kw))(x)) for kw in ({}, {"denominator_eps": 0.0}))
    assert plain == zero
    with_eps = layer(denominator_eps=0.25)(x)
    assert _gap(with_eps, layer()(x)) > 1e-2  # an eps that large shows


# -- the published sizes ------------------------------------------------------------------


def test_the_published_sizes_count_833_million_parameters():
    shapes = jax.eval_shape(lambda: lfm2_moe.lfm2_24b_ep8(0).params)
    sizes = {name: sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
             for name, sub in shapes.items()}
    conv_moe, attn_moe = 92_416_000, 86_118_528
    assert sizes == {
        "seg00_embed": 16_777_216, "seg01_conv_dense": 89_139_200,
        "seg02_attn_moe": attn_moe, "seg03_conv_moe": conv_moe, "seg04_conv_moe": conv_moe,
        "seg05_conv_moe": conv_moe, "seg06_attn_moe": attn_moe, "seg07_conv_moe": conv_moe,
        "seg08_conv_moe": conv_moe, "seg09_conv_moe": conv_moe, "seg10_head": 2_048}
    assert sum(sizes.values()) == 832_651_520
    assert PUBLISHED.head_dim == 64 and PUBLISHED.num_hidden_layers == 9
    # one period fewer (the fallback the configuration names): layers 0 and 2-5
    five = jax.eval_shape(lambda: lfm2_moe.lfm2_24b_ep8(
        0, layer_types=list(PUBLISHED.layer_types[:5])).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(five)) == 469_284_992


def test_the_reference_imports_nothing_of_the_program():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = ["reference_lfm2_moe"]
    for module in seen:
        with open(os.path.join(root, "chipbench", module + ".py"), encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            names = ([a.name for a in node.names] if isinstance(node, ast.Import)
                     else [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            for name in names:
                assert not name.startswith("byzpy_tpu"), (module, name)
                if name.startswith("chipbench.") and name.split(".")[1] not in seen:
                    seen.append(name.split(".")[1])
