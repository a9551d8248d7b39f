"""Xing4.0's layers (``models/xing4.py``, ``models/layers.py``,
``parallel/moe.py``) against the benchmark's plain reference
(``chipbench/reference_xing4``) on seeded weights, at small sizes on the
CPU: the Sinkhorn projection and its gradient against an explicit loop;
YaRN's frequencies against the definition; a hyper-connected sublayer and
the whole chain, loss and gradients, leaf by leaf; four broken variants
that each FAIL the same comparison; the eight shares of the expert layer
tie to the uncut layer of 64; the streamed round is the (n, d) round."""

from __future__ import annotations

import functools
import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import layers, xing4
from byzpy_tpu.models.bundle import ModelBundle, chain_loss
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel.moe import held_experts_ffn
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step
from chipbench import reference_xing4 as ref
from chipbench import seeded_xing4 as seeded

TINY = xing4.Xing4Config(
    hidden_size=32, num_hidden_layers=3, vocab_size=64, num_attention_heads=2, q_lora_rank=16,
    kv_lora_rank=12, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8, query_block=8,
    intermediate_size=48, n_routed_experts=16, num_experts_per_tok=3, moe_intermediate_size=24,
    held_experts=(4, 4))
PUBLISHED = xing4.Xing4Config()


def _arch(cfg, **over):
    scaling = cfg.rope_scaling
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "hc_mult": cfg.hc_mult,
        "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
        "mhc_h_res_clamp_min": cfg.mhc_h_res_clamp_min,
        "mhc_h_res_clamp_max": cfg.mhc_h_res_clamp_max,
        "num_attention_heads": cfg.num_attention_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": scaling.factor, "beta_fast": scaling.beta_fast,
            "beta_slow": scaling.beta_slow, "mscale": scaling.mscale,
            "mscale_all_dim": scaling.mscale_all_dim,
            "original_max_position_embeddings": scaling.original_max_position_embeddings},
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "held_experts": list(cfg.held_experts), **over}


def _gap(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want))) / max(float(np.max(np.abs(want))), 1e-6)


def _close(got, want, tol=2e-5):
    assert _gap(got, want) <= tol


def _seeded_bundle(cfg, seed):
    """The bundle on the benchmark's seeded weights."""
    bundle = xing4.xing4_bundle(cfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), bundle.params)
    return bundle.with_params(seeded.make_params(shapes, seed, {}))


def _batch(cfg, seed, batch=2, length=19):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, length + 1), 0, cfg.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


# -- the Sinkhorn projection ----------------------------------------------------


def _logits(seed, positions, n=4):
    """Logits as the seeded connections make them (``b``: 2 on the diagonal
    + uniform in [-1, 1]; the projected part: ``alpha`` in [0.5, 1] times a
    unit normal), with two entries of the first position at the clamp."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    raw = (2.0 * jnp.eye(n)[:, :, None]
           + jax.random.uniform(keys[0], (n, n, 1), minval=-1, maxval=1)
           + 0.75 * jax.random.normal(keys[1], (n, n, positions)))
    return raw.at[0, 1, 0].set(30.0).at[2, 2, 0].set(-30.0)


def test_sinkhorn_is_the_explicit_loop_and_doubly_stochastic_within_the_stated_gap():
    logits = _logits(0, 2000)
    got = xing4.sinkhorn(logits, 20, 1e-6)
    want = jax.vmap(lambda m: ref.sinkhorn_loop(m, 20, 1e-6), in_axes=2, out_axes=2)(logits)
    _close(got, want, tol=1e-6)
    assert float(jnp.min(got)) >= 0
    rows, columns = jnp.sum(got, axis=1), jnp.sum(got, axis=0)
    # the last normalisation was the rows': they sum to 1 but for hc_eps and a
    # rounding; the columns to 1 within what 20 iterations leave (the
    # configuration's assumed.sinkhorn_order_and_eps states 1e-5 and 3e-2 at the
    # seeded logits, read over 200,000 positions)
    assert float(jnp.max(jnp.abs(rows - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(columns[:, 1:] - 1))) < 3e-2
    assert float(jnp.quantile(jnp.max(jnp.abs(columns - 1), axis=0), 0.99)) < 1e-2
    # neither the identity nor uniform
    diagonal = jnp.stack([got[i, i] for i in range(4)])
    assert 0.5 < float(jnp.mean(diagonal)) < 0.85
    assert float(jnp.quantile(diagonal, 0.05)) > 0.3 and float(jnp.quantile(diagonal, 0.95)) < 0.95


def test_sinkhorns_gradient_is_jax_grad_of_the_loop():
    logits = _logits(1, 9)
    probe = jax.random.normal(jax.random.PRNGKey(2), logits.shape)
    got = jax.grad(lambda m: jnp.sum(xing4.sinkhorn(m, 20, 1e-6) * probe))(logits)
    want = jax.grad(lambda m: jnp.sum(jax.vmap(
        lambda one: ref.sinkhorn_loop(one, 20, 1e-6), in_axes=2, out_axes=2)(m) * probe))(logits)
    _close(got, want, tol=1e-5)
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_fewer_iterations_are_a_different_matrix():
    logits = _logits(3, 16)
    assert _gap(xing4.sinkhorn(logits, 5, 1e-6), xing4.sinkhorn(logits, 20, 1e-6)) > 1e-4


# -- YaRN -------------------------------------------------------------------------


def test_yarn_blends_pairs_10_to_23_at_the_published_numbers():
    scaling = PUBLISHED.rope_scaling
    assert scaling.blend_range(64, 1e4) == (10, 23)
    got = np.asarray(scaling.frequencies(64, 1e4), np.float64)
    want = ref.yarn_frequencies(64, 1e4, _arch(PUBLISHED)["rope_scaling"])
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 1e4 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(want[:11], plain[:11], rtol=1e-12)  # fast pairs keep theirs
    np.testing.assert_allclose(want[23:], plain[23:] / 64, rtol=1e-12)  # slow ones: over 64
    inside = want[11:23] / plain[11:23]
    assert np.all(np.diff(inside) < 0) and inside[0] < 1 and inside[-1] > 1 / 64
    # the definition, written out for one pair: m = 1 - (15 - 10) / 13
    m = 1 - 5 / 13
    assert want[15] == pytest.approx(plain[15] * m + plain[15] / 64 * (1 - m), rel=1e-12)
    # cos and sin keep their size; the scores' scale is 192^-1/2 x 2.0047
    assert scaling.rotary_scale == 1.0
    assert scaling.softmax_scale == pytest.approx((0.1 * math.log(64) + 1) ** 2)
    assert scaling.softmax_scale == pytest.approx(2.0047, abs=5e-5)


def test_rotary_under_yarn_is_the_references_rotation_and_plain_rotary_is_unchanged():
    x = jax.random.normal(jax.random.PRNGKey(1), (300, 3, 64))
    scaling = PUBLISHED.rope_scaling
    frequencies = ref.yarn_frequencies(64, 1e4, _arch(PUBLISHED)["rope_scaling"])
    _close(layers.rotary(x, 1e4, scaling), ref.rotate(x, frequencies, 1.0), tol=2e-5)
    assert _gap(layers.rotary(x, 1e4, scaling), layers.rotary(x, 1e4)) > 0.1
    from chipbench import reference_glm4_moe_lite

    _close(layers.rotary(x, 1e4), reference_glm4_moe_lite.rotate(x, 1e4), tol=2e-5)
    bigger = replace(scaling, mscale=2.0)  # cos and sin times (0.2 ln 64 + 1) / (0.1 ln 64 + 1)
    _close(layers.rotary(x, 1e4, bigger), ref.rotate(x, frequencies, bigger.rotary_scale))
    assert bigger.rotary_scale == pytest.approx((0.2 * math.log(64) + 1) / (0.1 * math.log(64) + 1))


# -- latent attention at two widths ------------------------------------------------


def _both(fn_program, fn_reference, p, x):
    """Value and gradients (weights and input) of a scalar read-out of both."""
    probe = jax.random.normal(jax.random.PRNGKey(9), fn_reference(p, x).shape)
    outs = []
    for fn in (fn_program, fn_reference):
        grads = jax.grad(lambda p_, x_: jnp.sum(fn(p_, x_) * probe), argnums=(0, 1))(p, x)
        outs.append((fn(p, x), grads))
    return outs


MLA_LEAVES = ("w_qa", "q_norm_scale", "w_qb", "w_kva", "w_kr", "kv_norm_scale", "w_kvb", "w_o")


@pytest.mark.parametrize("length", [8, 21])
def test_latent_attention_under_yarn_is_the_full_score_matrix_forward_and_gradient(length):
    p = _seeded_bundle(TINY, 3).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(0), (length, TINY.hidden_size))
    (y, g), (y_ref, g_ref) = _both(lambda p_, x_: layers.mla_attention(p_, x_, TINY),
                                   lambda p_, x_: ref.mla_full(p_, x_, _arch(TINY)), p, x)
    _close(y, y_ref)
    for name in MLA_LEAVES:
        _close(g[0][name], g_ref[0][name], tol=1e-4)
        assert float(jnp.max(jnp.abs(g_ref[0][name]))) > 0
    _close(g[1], g_ref[1], tol=1e-4)


def test_latent_attention_by_the_kernels_pads_queries_and_keys_and_never_the_values(monkeypatch):
    """192 / 128 a head: the gate is asked both widths, the kernels get
    queries and keys at 256 (64 zero columns) and values at 128, and the
    result is the reference's, forward and gradient."""
    cfg = replace(TINY, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    p = _seeded_bundle(cfg, 4).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (140, cfg.hidden_size))
    asked, shapes = [], []
    kernels = layers.causal_attention

    def spy(q, k, v, **kwargs):
        shapes.append((q.shape, k.shape, v.shape, kwargs["kv_heads"], kwargs["scale"]))
        return kernels(q, k, v, **kwargs)

    monkeypatch.setattr(layers, "causal_attention", spy)
    routes = []
    for serves in (False, True):
        monkeypatch.setattr(layers, "causal_attention_serves",
                            lambda x_, hd, vd, serves=serves: asked.append((hd, vd)) or serves)
        routes.append(_both(lambda p_, x_: layers.mla_attention(p_, x_, cfg),
                            lambda p_, x_: ref.mla_full(p_, x_, _arch(cfg)), p, x))
    assert set(asked) == {(256, 128)}
    assert set(shapes) == {((140, 2 * 256), (140, 2 * 256), (140, 2 * 128), 2,
                            cfg.rope_scaling.softmax_scale / math.sqrt(192))}
    for (y, g), (y_ref, g_ref) in routes:
        _close(y, y_ref, tol=1e-4)
        for name in MLA_LEAVES:
            _close(g[0][name], g_ref[0][name], tol=5e-4)
        _close(g[1], g_ref[1], tol=5e-4)


SHORT = 40  # positions: barely longer than the latents' 16 + 12 rows


@functools.lru_cache(maxsize=None)
def _two_forms(yarn):
    """``{rows: (output, {leaf: gradient})}`` of latent attention by the
    kernels at padded heads (128 + 64 and 64 zero lanes / 128) over a short
    sequence, the Xing4.0 cell's regime, the form forced: cut on three axes
    (``rows`` False, what the rule keeps there and the plain reference
    here) and born in the kernels' rows."""
    cfg = replace(TINY, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    if not yarn:
        cfg = replace(cfg, rope_scaling=None)
    block = _seeded_bundle(cfg, 5).params["seg02_moe"]
    p = {name: block[name] for name in MLA_LEAVES}
    x = jax.random.normal(jax.random.PRNGKey(8), (SHORT, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    forms = {}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(layers, "causal_attention_serves", lambda x_, hd, vd: True)
        for rows in (False, True):
            patch.setattr(layers, "_rows_route_pays", lambda *sizes, rows=rows: rows)
            grads, dx = jax.grad(
                lambda p_, x_: jnp.sum(layers.mla_attention(p_, x_, cfg) * probe),
                argnums=(0, 1))(p, x)
            forms[rows] = (layers.mla_attention(p, x, cfg), {**grads, "x": dx})
    return forms


@pytest.mark.parametrize("what", ["output", "x", *MLA_LEAVES])
@pytest.mark.parametrize("yarn", [True, False], ids=["yarn", "plain"])
def test_padded_heads_born_in_rows_are_the_three_axis_form(yarn, what):
    forms = _two_forms(yarn)
    got, want = (forms[rows][0] if what == "output" else forms[rows][1][what]
                 for rows in (True, False))
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(got, want, tol=5e-6)


def test_born_in_rows_the_zero_lanes_are_exact_zeros_on_the_weights_columns(monkeypatch):
    """What the kernels are handed: q and k at 256 a head with the last 64
    columns exactly zero, k's rotary columns the ONE shared key in every
    head, v at its own 128."""
    cfg = replace(TINY, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    p = _seeded_bundle(cfg, 5).params["seg02_moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (SHORT, cfg.hidden_size))
    handed = []
    monkeypatch.setattr(layers, "causal_attention_serves", lambda x_, hd, vd: True)
    monkeypatch.setattr(layers, "_rows_route_pays", lambda *sizes: True)
    monkeypatch.setattr(layers, "causal_attention",
                        lambda q, k, v, **kwargs: handed.append((q, k, v)) or v)
    layers.mla_attention(p, x, cfg)
    (q, k, v), = handed
    assert q.shape == k.shape == (SHORT, 2 * 256) and v.shape == (SHORT, 2 * 128)
    q, k = q.reshape(SHORT, 2, 256), k.reshape(SHORT, 2, 256)
    assert not np.any(np.asarray(q[..., 192:])) and not np.any(np.asarray(k[..., 192:]))
    np.testing.assert_array_equal(np.asarray(k[:, 0, 128:192]), np.asarray(k[:, 1, 128:192]))
    shared = layers.rotary(x @ p["w_kr"].astype(x.dtype), cfg.rope_theta, cfg.rope_scaling)
    _close(k[:, 0, 128:192], shared, tol=1e-6)


# -- a hyper-connected sublayer ------------------------------------------------------


def _connection(seed, n, hidden):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"phi": seeded._leaf("attn_hc_phi", (n * hidden, n * (2 + n)), jnp.float32, keys[0]),
            "b": seeded._leaf("attn_hc_b", (n * (2 + n),), jnp.float32, keys[1]),
            "alpha": seeded._leaf("attn_hc_alpha", (3,), jnp.float32, keys[2])}


def test_the_mappings_are_the_references_position_by_position():
    n, hidden, positions = 4, 32, 23
    p = _connection(0, n, hidden)
    x = jax.random.normal(jax.random.PRNGKey(1), (positions, n, hidden)) * 1.7
    named = {f"attn_hc_{k}": v for k, v in p.items()}
    want = jax.vmap(lambda s: ref.mappings_of_position(named, "attn", s, _arch(TINY)))(x)
    got = xing4.hc_maps(p, x.reshape(positions, n * hidden), TINY)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32
        _close(g, w, tol=1e-5)
    pre, post, res = got
    assert 0 < float(jnp.min(pre)) and float(jnp.max(pre)) < 1
    assert 0 < float(jnp.min(post)) and float(jnp.max(post)) < 2
    # the dynamic part is no rounding: the mappings move from position to position
    assert float(jnp.std(res[:, 0, 0])) > 0.02 and float(jnp.std(pre[:, 0])) > 0.02


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_two_mixes_and_their_own_backward_are_the_plain_formulas(dtype):
    n, hidden, positions = 4, 16, 11
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(keys[0], (positions, n * hidden)).astype(dtype)
    y = jax.random.normal(keys[1], (positions, hidden)).astype(dtype)
    pre = jax.random.uniform(keys[2], (positions, n))
    post = jax.random.uniform(keys[3], (positions, n)) * 2
    res = jax.random.uniform(keys[4], (positions, n, n))
    probe = jax.random.normal(keys[5], (positions, n * hidden))

    def plain_pre(x_, pre_):
        streams = x_.astype(jnp.float32).reshape(positions, n, hidden)
        return jnp.einsum("ti,tid->td", pre_, streams, precision="highest").astype(x_.dtype)

    def plain_back(x_, y_, res_, post_):
        streams = x_.astype(jnp.float32).reshape(positions, n, hidden)
        out = jnp.einsum("tij,tjd->tid", res_, streams, precision="highest") + (
            post_[:, :, None] * y_.astype(jnp.float32)[:, None, :])
        return out.reshape(positions, n * hidden).astype(x_.dtype)

    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    _close(xing4.pre_mix(x, pre).astype(jnp.float32), plain_pre(x, pre).astype(jnp.float32), tol)
    _close(xing4.write_back(x, y, res, post).astype(jnp.float32),
           plain_back(x, y, res, post).astype(jnp.float32), tol)
    assert xing4.pre_mix(x, pre).dtype == xing4.write_back(x, y, res, post).dtype == dtype

    def read(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe[:, :fn(*a).shape[1]])

    for own, plain, args in ((xing4.pre_mix, plain_pre, (x, pre)),
                             (xing4.write_back, plain_back, (x, y, res, post))):
        got = jax.grad(read(own), argnums=tuple(range(len(args))))(*args)
        want = jax.grad(read(plain), argnums=tuple(range(len(args))))(*args)
        for g, w, a in zip(got, want, args):
            assert g.dtype == a.dtype and g.shape == a.shape
            _close(g.astype(jnp.float32), w.astype(jnp.float32), tol)


# -- the chain against the reference, and four broken variants -----------------------


def _chain_gaps(bundle, cfg, arch=None, seed=3):
    """The worst relative gap, leaf by leaf, between the bundle's loss and
    gradients and the reference's."""
    x, y = _batch(cfg, seed)
    loss, grads = jax.value_and_grad(bundle.loss_fn)(bundle.params, x, y)
    (want, counts), want_grads = jax.value_and_grad(ref.loss_and_counts, has_aux=True)(
        bundle.params, x, y, arch or _arch(cfg))
    gaps = {"loss": abs(float(loss) - float(want)) / abs(float(want))}
    for name in bundle.params:
        for leaf in bundle.params[name]:
            assert float(jnp.max(jnp.abs(want_grads[name][leaf]))) > 0, (name, leaf)
            gaps[f"{name}.{leaf}"] = _gap(grads[name][leaf], want_grads[name][leaf])
    return gaps, counts


LOSS_TOL, GRAD_TOL = 1e-5, 2e-4


def test_the_chain_is_the_reference_loss_gradient_and_counts():
    bundle = _seeded_bundle(TINY, 8)
    gaps, counts = _chain_gaps(bundle, TINY)
    assert gaps.pop("loss") <= LOSS_TOL
    assert max(gaps.values()) <= GRAD_TOL, max(gaps.items(), key=lambda kv: kv[1])
    assert counts.shape == (2, 4)  # two expert layers, four held experts
    # every leaf of both connections of every block is among them
    assert sum(".attn_hc_" in k or ".ffn_hc_" in k for k in gaps) == 3 * 6
    # the program's own counts are the reference's
    x, y = _batch(TINY, 3)
    h = x
    got = []
    for seg in bundle.segments[:-1]:
        h = seg.apply(bundle.params[seg.key], h)
        if seg.aux:
            h, aux = h
            got.append(aux["held_expert_tokens"])
            assert int(aux["tokens_dropped"]) == 0
    np.testing.assert_array_equal(np.stack(got), counts)
    # boundaries: (B, T, hidden) behind the embedding and before the head,
    # (B, T, n, hidden) between the blocks
    shapes = []
    h = x
    for seg in bundle.segments[:-1]:
        h = seg.apply(bundle.params[seg.key], h)
        h = h[0] if seg.aux else h
        shapes.append(h.shape)
    assert shapes == [(2, 19, 32), (2, 19, 4, 32), (2, 19, 4, 32), (2, 19, 32)]


def _broken(monkeypatch, what, cfg):
    """The bundle with one thing wrong; returns (bundle, the reference's arch)."""
    bundle = _seeded_bundle(cfg, 8)
    if what == "perturbed_phi":
        params = jax.tree_util.tree_map(lambda a: a, bundle.params)
        phi = params["seg02_moe"]["ffn_hc_phi"]
        params["seg02_moe"] = dict(params["seg02_moe"], ffn_hc_phi=phi + 0.05 * jnp.roll(phi, 1, 0))
        # the reference keeps the right Phi: compare on ITS parameters
        return bundle, bundle.with_params(params)
    if what == "dropped_h_post":
        real = xing4.hc_maps

        def no_post(p, x, c):
            pre, post, res = real(p, x, c)
            return pre, jnp.ones_like(post), res

        monkeypatch.setattr(xing4, "hc_maps", no_post)
    elif what == "plain_residual":
        def plain(p, x, norm_scale, sublayer, c):
            n = c.hc_mult
            u = x.reshape(x.shape[0], n, -1)[:, 0]
            y, aux = sublayer(layers.rms_norm(u, norm_scale, c.rms_norm_eps))
            return (x.reshape(x.shape[0], n, -1) + y[:, None, :]).reshape(x.shape), aux

        monkeypatch.setattr(xing4, "hyper_connected", plain)
    elif what == "unscaled_softmax":
        monkeypatch.setattr(layers.YarnScaling, "softmax_scale", property(lambda self: 1.0))
    return bundle, bundle


@pytest.mark.parametrize("what", ["perturbed_phi", "dropped_h_post", "plain_residual",
                                  "unscaled_softmax"])
def test_each_broken_variant_fails_the_comparison(monkeypatch, what):
    reference_side, program_side = _broken(monkeypatch, what, TINY)
    x, y = _batch(TINY, 3)
    loss, grads = jax.value_and_grad(program_side.loss_fn)(program_side.params, x, y)
    (want, _), want_grads = jax.value_and_grad(ref.loss_and_counts, has_aux=True)(
        reference_side.params, x, y, _arch(TINY))
    worst = max(_gap(grads[name][leaf], want_grads[name][leaf])
                for name in grads for leaf in grads[name])
    loss_gap = abs(float(loss) - float(want)) / abs(float(want))
    # by one of the limits at least, and by a wide margin: ten times the sound gap
    assert loss_gap > 10 * LOSS_TOL or worst > 10 * GRAD_TOL, (what, loss_gap, worst)
    assert worst > 10 * GRAD_TOL, (what, worst)


def test_no_mtp_module_is_written_and_the_bundle_says_so():
    with pytest.raises(ValueError, match="MTP"):
        xing4.xing4_bundle(replace(TINY, num_nextn_predict_layers=1))


# -- the share the chip holds ------------------------------------------------------------


def _expert_weights(cfg, seed, held):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    d, f = cfg.hidden_size, cfg.moe_intermediate_size

    def matrix(*shape):
        return jax.random.normal(next(k), shape) / np.sqrt(shape[-2])

    return {"router": matrix(d, cfg.n_routed_experts),
            "experts_gate": matrix(held, d, f), "experts_up": matrix(held, d, f),
            "experts_down": matrix(held, f, d), "shared_gate": matrix(d, f),
            "shared_up": matrix(d, f), "shared_down": matrix(f, d)}


@pytest.mark.parametrize("round_rows", [None, 8])  # the default (a quarter); several rounds
def test_the_eight_shares_of_the_expert_layer_add_up_to_the_uncut_layer_of_64(round_rows):
    """The published router: 64 outputs, top-4, normalised, times 2. Eight
    chips of eight experts each, the shared expert counted once."""
    cfg = replace(TINY, n_routed_experts=64, num_experts_per_tok=4, routed_scaling_factor=2.0)
    p = _expert_weights(cfg, 5, 64)
    x = jax.random.normal(jax.random.PRNGKey(0), (96, cfg.hidden_size))
    whole = _arch(cfg, held_experts=[0, 64])
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    want, want_counts = ref.moe_dense_mask(p, x, whole)
    want_grads = jax.grad(
        lambda p_, x_: jnp.sum(ref.moe_dense_mask(p_, x_, whole)[0] * probe), (0, 1))(p, x)

    def share(p_, x_, first):
        cut = slice(first, first + 8)
        shared = first == 0
        return held_experts_ffn(
            x_, p_["router"], p_["experts_up"][cut], p_["experts_down"][cut],
            p_["shared_up"] if shared else None, p_["shared_down"] if shared else None,
            first_held=first, n_experts=64, top_k=4, scale=2.0, round_rows=round_rows,
            w_gate=p_["experts_gate"][cut], shared_gate=p_["shared_gate"] if shared else None)

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


# -- the round ----------------------------------------------------------------------------


@pytest.mark.parametrize("attack", ["signflip", "none"])
def test_the_streamed_round_of_the_toy_bundle_is_the_n_by_d_round(attack):
    n = 8
    b, attack_fn = {"signflip": (2, coordinatewise.RoundAttack(
        attack_ops.sign_flip, of="honest_mean")), "none": (0, None)}[attack]
    cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=0.05, momentum=0.9)
    streamed = _seeded_bundle(replace(TINY, num_hidden_layers=2), 2)
    whole = ModelBundle(apply_fn=None, params=streamed.params,
                        loss_fn=chain_loss(streamed.segments))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, n, 1, 18), 0, TINY.vocab_size)
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
        assert got["segment_aux"]["seg02_moe"]["held_expert_tokens"].shape == (n - b, 4)


def test_the_cells_sequence_is_one_pair_of_blocks_a_head_in_all_three_kernels():
    from byzpy_tpu.ops import pallas_attention as pa

    assert pa._blocks(1024, 1, backward=False) == (1024, 1024, 1024)
    assert pa._blocks(1024, 1, backward=True) == (1024, 1024, 1024)
    for key_major in (False, True):
        qs, ks, flags = pa._pairs(1, 1, 1024, 1024, key_major=key_major)
        assert (list(qs), list(ks)) == ([0], [0])
        assert int(flags[0]) == pa._FIRST | pa._LAST | pa._MASKED


def test_the_published_sizes_count_759_million_parameters():
    shapes = jax.eval_shape(lambda: xing4.xing4_29b_ep8(0).params)
    sizes = {name: sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
             for name, sub in shapes.items()}
    assert sizes == {
        "seg00_embed": 58_720_256, "seg01_dense": 128_196_918, "seg02_moe": 128_426_294,
        "seg03_moe": 128_426_294, "seg04_moe": 128_426_294, "seg05_moe": 128_426_294,
        "seg06_head": 58_720_256 + 3584}
    assert sum(sizes.values()) == 759_346_190
    block = shapes["seg02_moe"]
    assert sum(block[k].size for k in MLA_LEAVES) == 28_411_136
    connection = sum(block[f"attn_hc_{k}"].size for k in ("phi", "b", "alpha"))
    assert connection == 14_336 * 24 + 24 + 3
    assert block["attn_hc_phi"].shape == (4 * 3584, 4 + 4 + 16)
    assert block["w_qb"].shape == (768, 32 * 192) and block["w_kvb"].shape == (512, 32 * 256)
    assert block["w_o"].shape == (32 * 128, 3584) and block["router"].shape == (3584, 64)
    assert block["experts_up"].shape == (8, 3584, 1024)
    assert shapes["seg01_dense"]["w_up"].shape == (3584, 9216)
