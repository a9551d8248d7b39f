"""SmallThinker's layers (``models/smallthinker.py``, ``models/layers.py``,
``parallel/moe.py``) against the benchmark's plain reference
(``chipbench/reference_smallthinker``) on seeded weights, at small sizes on
the CPU, with MORE positions than the window (80 against 24, query blocks
of 16: both of the window's edges cross blocks): windowed attention, global
attention and the router-before-attention expert layer, forward and
gradient; the whole chain, loss, gradients and held experts' counts, leaf by
leaf; six broken variants that each FAIL the same comparison; the eight
shares of the expert layer tie to the uncut layer of 64; the streamed round
of the bundle is the (n, d) round on ``chain_loss`` of the same bundle."""

from __future__ import annotations

import ast
import json
import os
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import smallthinker
from byzpy_tpu.models.bundle import ModelBundle, chain_loss
from byzpy_tpu.models.layers import rms_norm
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel import moe
from byzpy_tpu.parallel.moe import held_experts_ffn
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step
from chipbench import reference_smallthinker as ref
from chipbench import seeded_smallthinker as seeded

WINDOW, LENGTH = 24, 80
# two key/value heads of three query heads each; one period: global, then windows
TINY = smallthinker.SmallThinkerConfig(
    hidden_size=64, vocab_size=96, num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    sliding_window_layout=(0, 1, 1), rope_layout=(0, 1, 1), sliding_window_size=WINDOW,
    query_block=16, moe_num_primary_experts=16, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=24, held_experts=(4, 4))
PUBLISHED = smallthinker.SmallThinkerConfig()
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chipbench",
                       "configs", "smallthinker-21b-ep8-ps.json"), encoding="utf-8") as _fh:
    # the cell's own limit on a leaf's norm gap
    LIMIT = json.load(_fh)["limits"]["first_gradient_norm_gap"]


def _arch(cfg, **over):
    blocks = cfg.num_hidden_layers
    return {"rms_norm_eps": cfg.rms_norm_eps, "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "sliding_window_size": cfg.sliding_window_size,
            "sliding_window_layout": list(cfg.sliding_window_layout),
            "rope_layout": list(cfg.rope_layout), "layers_held": list(range(blocks)),
            "moe_num_active_primary_experts": cfg.moe_num_active_primary_experts,
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
    bundle = smallthinker.smallthinker_bundle(cfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bundle.params)
    return bundle.with_params(seeded.make_params(shapes, seed, {}))


def _batch(cfg, seed, batch=2, length=LENGTH):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, cfg.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


# -- the operators -------------------------------------------------------------------


def _both(fn_program, fn_reference, p, *xs):
    probe = jax.random.normal(jax.random.PRNGKey(11), xs[0].shape)
    wrt = tuple(range(len(xs) + 1))
    got = jax.value_and_grad(lambda *a: jnp.sum(fn_program(*a) * probe), wrt)(p, *xs)
    want = jax.value_and_grad(lambda *a: jnp.sum(fn_reference(*a) * probe), wrt)(p, *xs)
    return got, want


@pytest.mark.parametrize("length", [2, 24, 25, LENGTH])
@pytest.mark.parametrize("kind", [(True, True), (False, False)], ids=["window", "global"])
def test_each_attention_is_the_references_forward_and_gradient(kind, length):
    p = _seeded_bundle(TINY, 4).params["seg02_window"]
    u = jax.random.normal(jax.random.PRNGKey(length), (length, TINY.hidden_size))
    (got, got_grads), (want, want_grads) = _both(
        lambda p_, u_: smallthinker.attention(p_, u_, TINY, kind),
        lambda p_, u_: ref.attention_by_rows(p_, u_, _arch(TINY), *kind), p, u)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)) + 1e-5
    _close(got_grads[1], want_grads[1], tol=1e-4)
    for leaf in ("w_q", "w_k", "w_v", "w_o"):
        assert float(jnp.max(jnp.abs(want_grads[0][leaf]))) > 0, leaf
        _close(got_grads[0][leaf], want_grads[0][leaf], tol=1e-4)


def test_the_window_shows_at_these_sizes():
    """The two kinds differ where the sequence is longer than the window
    and agree where it is not (with the turn off in both)."""
    p = _seeded_bundle(TINY, 4).params["seg02_window"]
    u = jax.random.normal(jax.random.PRNGKey(0), (LENGTH, TINY.hidden_size))
    windowed = smallthinker.attention(p, u, TINY, (True, False))
    whole = smallthinker.attention(p, u, TINY, (False, False))
    _close(windowed[:WINDOW], whole[:WINDOW], tol=1e-6)
    assert _gap(windowed[WINDOW:], whole[WINDOW:]) > 0.05


def _layer(p, m, u, cfg, **sizes):
    return held_experts_ffn(
        m, p["router"], p["experts_up"], p["experts_down"], first_held=cfg.held_experts[0],
        n_experts=cfg.moe_num_primary_experts, top_k=cfg.moe_num_active_primary_experts,
        w_gate=p["experts_gate"], score=jax.nn.softmax, router_input=u,
        activation=jax.nn.relu, **sizes)


def test_the_expert_layer_routed_on_another_tensor_is_the_references():
    p = _seeded_bundle(TINY, 4).params["seg01_global"]
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    m, u = (jax.random.normal(k, (56, TINY.hidden_size)) for k in keys)
    (got, got_grads), (want, want_grads) = _both(
        lambda p_, m_, u_: _layer(p_, m_, u_, TINY)[0],
        lambda p_, m_, u_: ref.moe_dense_mask(p_, m_, u_, _arch(TINY))[0], p, m, u)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)) + 1e-5
    for g, w in zip(got_grads[1:], want_grads[1:]):  # the experts' tensor, the router's
        assert float(jnp.max(jnp.abs(w))) > 0
        _close(g, w, tol=1e-4)
    for leaf in ("router", "experts_gate", "experts_up", "experts_down"):
        _close(got_grads[0][leaf], want_grads[0][leaf], tol=1e-4)
    np.testing.assert_array_equal(_layer(p, m, u, TINY)[1]["held_expert_tokens"],
                                  ref.moe_dense_mask(p, m, u, _arch(TINY))[1])


@pytest.mark.parametrize("skew", [0.0, 3.0])
@pytest.mark.parametrize("round_rows", [None, 8, 16, 48, 64])
def test_rounds_of_any_size_are_the_reference_whatever_the_router_does(round_rows, skew):
    """The layer routed on another tensor, with ``relu``: the same result
    and gradients as the dense reference whatever a round holds, with an
    even router and with one that sends most tokens to one held expert; no
    token dropped; as many rounds as the fullest expert needs."""
    p = _seeded_bundle(TINY, 4).params["seg01_global"]
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    m = jax.random.normal(keys[0], (200, TINY.hidden_size))
    u = jax.random.normal(keys[1], m.shape) + skew * jax.random.normal(keys[2], m.shape[1:])
    want, want_counts = ref.moe_dense_mask(p, m, u, _arch(TINY))
    (got, got_grads), (_, want_grads) = _both(
        lambda p_, m_, u_: _layer(p_, m_, u_, TINY, round_rows=round_rows)[0],
        lambda p_, m_, u_: ref.moe_dense_mask(p_, m_, u_, _arch(TINY))[0], p, m, u)
    out, aux = _layer(p, m, u, TINY, round_rows=round_rows)
    _close(out, want)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads), jax.tree_util.tree_leaves(want_grads)):
        _close(g, w, tol=1e-4)
    np.testing.assert_array_equal(aux["held_expert_tokens"], want_counts)
    assert int(aux["tokens_dropped"]) == 0
    counts = np.asarray(want_counts)
    assert int(aux["expert_rounds"]) == max(1, -(-int(counts.max()) // (round_rows or 32)))  # its own: an eighth of 200, in whole sublanes
    if skew:  # one expert far over the others
        assert counts.max() > 2 * np.median(counts)


def test_the_new_arguments_add_no_op_where_they_are_not_given():
    """``held_experts_ffn`` without ``router_input`` and ``activation``
    lowers to the text it lowers to with the defaults spelled out, and each
    of the two shows where it is given."""
    p = _seeded_bundle(TINY, 1).params["seg01_global"]
    x = jax.random.normal(jax.random.PRNGKey(0), (32, TINY.hidden_size))

    def layer(**kwargs):
        return lambda x_: held_experts_ffn(
            x_, p["router"], p["experts_up"], p["experts_down"], first_held=0, n_experts=16,
            top_k=3, w_gate=p["experts_gate"], **kwargs)[0]

    plain, spelled = (jax.jit(layer(**kw)).lower(x).as_text() for kw in (
        {}, {"router_input": None, "activation": jax.nn.silu}))
    assert plain == spelled
    assert _gap(layer(activation=jax.nn.relu)(x), layer()(x)) > 1e-2
    assert _gap(layer(router_input=x[::-1])(x), layer()(x)) > 1e-2
    # the routed tensor itself, handed as the router's, is the layer of today
    _close(layer(router_input=x)(x), layer()(x), tol=0)


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
    assert smallthinker.segment_keys(TINY) == (
        "seg00_embed", "seg01_global", "seg02_window", "seg03_window", "seg04_head")
    gaps, _, counts = _chain_gaps(bundle, TINY)
    assert gaps.pop("loss") <= LOSS_TOL
    assert max(gaps.values()) <= GRAD_TOL, max(gaps.items(), key=lambda kv: kv[1])
    assert counts.shape == (3, 4)  # three expert layers, four held experts
    x, _ = _batch(TINY, 3)
    h, got = x, []
    for seg in bundle.segments[:-1]:
        h = seg.apply(bundle.params[seg.key], h)
        if seg.aux:
            h, aux = h
            got.append(aux["held_expert_tokens"])
            assert int(aux["tokens_dropped"]) == 0 and int(aux["expert_rounds"]) >= 1
        assert h.shape == (2, LENGTH, TINY.hidden_size)
    np.testing.assert_array_equal(np.stack(got), counts)


def _broken(monkeypatch, what, cfg):
    """The bundle with one thing wrong."""
    if what == "window_ignored":
        cfg = replace(cfg, sliding_window_layout=(0, 0, 0))
    elif what == "window_off_by_one":
        cfg = replace(cfg, sliding_window_size=cfg.sliding_window_size + 1)
    elif what == "global_block_turned":
        cfg = replace(cfg, rope_layout=(1, 1, 1))
    elif what == "window_blocks_turn_dropped":
        cfg = replace(cfg, rope_layout=(0, 0, 0))
    elif what == "silu_for_relu":
        real = moe.held_experts_ffn
        monkeypatch.setattr(smallthinker, "held_experts_ffn", lambda *a, **kw: real(
            *a, **{**kw, "activation": jax.nn.silu}))
    elif what == "router_fed_the_stream_after_attention":
        real = moe.held_experts_ffn
        monkeypatch.setattr(smallthinker, "held_experts_ffn", lambda *a, **kw: real(
            *a, **{**kw, "router_input": None}))
    return _seeded_bundle(cfg, 8)


@pytest.mark.parametrize("what", [
    "window_ignored", "window_off_by_one", "global_block_turned", "window_blocks_turn_dropped",
    "silu_for_relu", "router_fed_the_stream_after_attention"])
def test_each_broken_variant_fails_the_comparison(monkeypatch, what):
    bundle = _broken(monkeypatch, what, TINY)
    gaps, norm_gaps, _ = _chain_gaps(bundle, TINY)  # the reference reads TINY as it is
    loss_gap = gaps.pop("loss")
    # by a wide margin: ten times the sound gap, element by element
    assert max(gaps.values()) > 10 * GRAD_TOL, (what, max(gaps.values()))
    # and in the form the chip's comparison has, the worst leaf's gap of norms
    # over the limits' form, or the loss's over its own
    print(what, "loss gap", loss_gap, "worst norm gap", max(norm_gaps.values()))
    assert max(norm_gaps.values()) > LIMIT or loss_gap > 3e-4, (what, max(norm_gaps.values()))


@pytest.mark.parametrize("unit_gain", [False, True], ids=["seeded", "unit_gain_branches"])
def test_the_seeded_weights_keep_what_the_tokens_share_small_through_the_chain(
        monkeypatch, unit_gain):
    """Why ``seeded_smallthinker`` draws ``w_o`` and ``experts_down`` at the
    chain's depth: with unit gain there the share of the routers' input that
    every token of a sequence has in common grows block by block (attention
    averages the keys: the tokens' own parts average away, the shared part
    does not) and the last router is uneven; as seeded, it stays where the
    embedding left it and every held expert keeps near its mean."""
    cfg = replace(TINY, sliding_window_layout=(0, 1, 1, 1) * 2, rope_layout=(0, 1, 1, 1) * 2,
                  vocab_size=512, sliding_window_size=128, query_block=64)
    if unit_gain:
        monkeypatch.setattr(seeded, "BRANCH_OUTPUTS", ())
    monkeypatch.setattr(seeded, "_BUILDERS", {})
    params = _seeded_bundle(cfg, 5).params
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0, cfg.vocab_size)
    h, shared = params["seg00_embed"]["embedding"][x], []
    for i, name in enumerate(smallthinker.segment_keys(cfg)[1:-1]):
        u = rms_norm(h, params[name]["attention_norm_scale"], cfg.rms_norm_eps)[0]
        shared.append(float(jnp.sum(jnp.mean(u, 0) ** 2) / jnp.mean(jnp.sum(u ** 2, -1))))
        h, aux = smallthinker.decoder_block(params[name], h, cfg, cfg.kind(i))
    counts, mean = np.asarray(aux["held_expert_tokens"]), 512 * 3 / 16
    if unit_gain:
        assert shared[-1] > 20 * shared[0] and shared[-1] > 0.1
        assert counts.min() < mean / 2
    else:
        assert max(shared) < 3 * shared[0] < 0.02
        assert mean / 2 < counts.min() and counts.max() < 2 * mean


def test_layouts_that_do_not_fit_are_refused():
    with pytest.raises(ValueError, match="layouts"):
        smallthinker.smallthinker_bundle(replace(TINY, rope_layout=(0, 1)))
    with pytest.raises(ValueError, match="layouts"):
        smallthinker.smallthinker_bundle(replace(TINY, sliding_window_layout=(0, 2, 1)))


@pytest.mark.parametrize("attack", ["signflip", "none"])
def test_the_streamed_round_of_the_bundle_is_the_n_by_d_round(attack):
    n = 8
    b, attack_fn = {"signflip": (2, coordinatewise.RoundAttack(
        attack_ops.sign_flip, of="honest_mean")), "none": (0, None)}[attack]
    cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=0.05, momentum=0.9)
    streamed = _seeded_bundle(TINY, 2)
    whole = ModelBundle(apply_fn=None, params=streamed.params,
                        loss_fn=chain_loss(streamed.segments))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (3, n, 1, 34), 0, TINY.vocab_size)
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
    for got, want in zip(jax.tree_util.tree_leaves((p_s, o_s)),
                         jax.tree_util.tree_leaves((p_w, o_w))):
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-6)
    for got, want in zip(m_s, m_w):
        np.testing.assert_allclose(got["honest_loss"], want["honest_loss"], rtol=1e-6)
        np.testing.assert_allclose(got["agg_grad_norm"], want["agg_grad_norm"], rtol=1e-5)
        aux = got["segment_aux"]
        assert sorted(aux) == ["seg01_global", "seg02_window", "seg03_window"]
        assert aux["seg02_window"]["held_expert_tokens"].shape == (n - b, 4)
        assert int(jnp.sum(aux["seg02_window"]["tokens_dropped"])) == 0


# -- the share the chip holds ----------------------------------------------------------


def _expert_weights(cfg, seed, held):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    d, f = cfg.hidden_size, cfg.moe_ffn_hidden_size

    def matrix(*shape):
        return jax.random.normal(next(k), shape) / np.sqrt(shape[-2])

    return {"router": matrix(d, cfg.moe_num_primary_experts),
            "experts_gate": matrix(held, d, f), "experts_up": matrix(held, d, f),
            "experts_down": matrix(held, f, d)}


# the default round (a quarter: one round); several rounds; two
@pytest.mark.parametrize("round_rows", [None, 8, 16])
def test_the_eight_shares_of_the_expert_layer_add_up_to_the_uncut_layer_of_64(round_rows):
    """The published router: a softmax over 64, top 6 over their sum, read
    off ANOTHER tensor than the experts read. Eight chips of eight experts
    each, no shared expert."""
    cfg = replace(TINY, moe_num_primary_experts=64, moe_num_active_primary_experts=6)
    p = _expert_weights(cfg, 5, 64)
    m, u = (jax.random.normal(jax.random.PRNGKey(s), (96, cfg.hidden_size)) for s in (0, 1))
    whole = _arch(cfg, held_experts=[0, 64])
    probe = jax.random.normal(jax.random.PRNGKey(4), m.shape)
    want, want_counts = ref.moe_dense_mask(p, m, u, whole)
    want_grads = jax.grad(
        lambda p_, m_, u_: jnp.sum(ref.moe_dense_mask(p_, m_, u_, whole)[0] * probe),
        (0, 1, 2))(p, m, u)

    def share(p_, m_, u_, first):
        cut = slice(first, first + 8)
        return held_experts_ffn(
            m_, p_["router"], p_["experts_up"][cut], p_["experts_down"][cut],
            first_held=first, n_experts=64, top_k=6, round_rows=round_rows,
            w_gate=p_["experts_gate"][cut], score=jax.nn.softmax, router_input=u_,
            activation=jax.nn.relu)

    def shares(p_, m_, u_):
        parts = [share(p_, m_, u_, first) for first in range(0, 64, 8)]
        return sum(out for out, _ in parts), [aux for _, aux in parts]

    total, auxes = shares(p, m, u)
    _close(total, want)
    np.testing.assert_array_equal(
        np.concatenate([aux["held_expert_tokens"] for aux in auxes]), want_counts)
    assert all(int(aux["tokens_dropped"]) == 0 for aux in auxes)
    assert int(np.sum(want_counts)) == 96 * 6
    grads = jax.grad(lambda p_, m_, u_: jnp.sum(shares(p_, m_, u_)[0] * probe), (0, 1, 2))(p, m, u)
    for got, wanted in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        _close(got, wanted, tol=1e-4)


# -- the published sizes ------------------------------------------------------------------


def test_the_published_sizes_count_644_million_parameters():
    shapes = jax.eval_shape(lambda: smallthinker.smallthinker_21b_ep8(0).params)
    sizes = {name: sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
             for name, sub in shapes.items()}
    block = 68_326_400
    assert sizes == {
        "seg00_embed": 48_619_520, "seg01_global": block, "seg02_window": block,
        "seg03_window": block, "seg04_window": block, "seg05_global": block,
        "seg06_window": block, "seg07_window": block, "seg08_window": block,
        "seg09_head": 48_619_520 + 2_560}
    assert sum(sizes.values()) == 643_852_800
    assert PUBLISHED.num_hidden_layers == 8 and PUBLISHED.sliding_window_size == 4096
    assert [PUBLISHED.kind(i) for i in range(4)] == [(False, False)] + 3 * [(True, True)]
    # one period (the fallback the issue names): blocks 0-3
    four = jax.eval_shape(lambda: smallthinker.smallthinker_21b_ep8(
        0, sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1]).params)
    assert sum(leaf.size for leaf in jax.tree_util.tree_leaves(four)) == 370_547_200


def test_the_reference_imports_nothing_of_the_program():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    seen = ["reference_smallthinker"]
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
