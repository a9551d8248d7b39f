"""Nemotron-H's mixers (``models/nemotron_h.py``, ``parallel/moe.py``)
against the benchmark's plain reference (``chipbench/reference_nemotron_h``)
on seeded weights, at small sizes on the CPU: the chunked scan is the
recurrence, forward and gradient; the share of the experts a chip holds
ties to the uncut layer; no token is dropped; the convolution and its SiLU
carry a backward of their own that is the plain formula's gradient."""

from __future__ import annotations

import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byzpy_tpu.models import nemotron_h as nh
from byzpy_tpu.parallel.moe import held_experts_ffn
from chipbench import reference_nemotron_h as ref

TINY = nh.NemotronHConfig(
    hidden_size=32, pattern="ME*E", vocab_size=64, mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, query_block=8, n_routed_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    held_experts=(4, 4),
)


def _arch(cfg):
    return {
        "pattern": cfg.pattern, "norm_eps": cfg.norm_eps,
        "mamba_num_heads": cfg.mamba_num_heads, "mamba_head_dim": cfg.mamba_head_dim,
        "n_groups": cfg.n_groups, "ssm_state_size": cfg.ssm_state_size,
        "conv_kernel": cfg.conv_kernel, "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "held_experts": list(cfg.held_experts),
    }


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


def _both(fn_program, fn_reference, p, x):
    """Value and gradients (weights and input) of a scalar read-out of both."""
    probe = jax.random.normal(jax.random.PRNGKey(9), fn_reference(p, x).shape)
    outs = []
    for fn in (fn_program, fn_reference):
        value, grads = jax.value_and_grad(lambda p_, x_: jnp.sum(fn(p_, x_) * probe),
                                          argnums=(0, 1))(p, x)
        outs.append((fn(p, x), value, grads))
    return outs


@pytest.mark.parametrize("length", [21, 8, 5, 32])  # no multiple of the chunk; one chunk; less
def test_chunked_scan_is_the_recurrence_forward_and_gradient(length):
    params = nh.init_params(TINY, seed=3)
    p = params[nh.segment_keys(TINY)[1]]
    x = jax.random.normal(jax.random.PRNGKey(length), (length, TINY.hidden_size))
    (y, _, g), (y_ref, _, g_ref) = _both(
        lambda p_, x_: nh.mamba2_mixer(p_, x_, TINY),
        lambda p_, x_: ref.mamba2_recurrent(p_, x_, _arch(TINY), inner=4), p, x)
    _close(y, y_ref)
    for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
        _close(got, want, tol=1e-4)


@pytest.mark.parametrize("length", [21, 8])
def test_blocked_attention_is_the_full_score_matrix(length):
    params = nh.init_params(TINY, seed=4)
    p = params[nh.segment_keys(TINY)[3]]
    x = jax.random.normal(jax.random.PRNGKey(length), (length, TINY.hidden_size))
    (y, _, g), (y_ref, _, g_ref) = _both(
        lambda p_, x_: nh.gqa_attention(p_, x_, TINY),
        lambda p_, x_: ref.attention_full(p_, x_, _arch(TINY)), p, x)
    _close(y, y_ref)
    for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
        _close(got, want, tol=1e-4)


def _plain_conv(x, w, bias):
    """The definition, written out: ``bias + sum_j w[j] x[t - (K - 1) + j]``."""
    k, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return bias + sum(w[j] * padded[j:j + t] for j in range(k))


@pytest.mark.parametrize("batched", [False, True], ids=["jit", "vmap2"])
@pytest.mark.parametrize("length", [1, 3, 4, 5, 17, 64])  # under, at and over the four taps
def test_the_convolutions_own_backward_is_the_plain_formulas_gradient(length, batched):
    channels = 200  # no whole number of lanes
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(length), 4)
    shape = (2, length, channels) if batched else (length, channels)
    x, probe = jax.random.normal(k1, shape), jax.random.normal(k4, shape)
    w, bias = jax.random.normal(k2, (4, channels)), jax.random.normal(k3, (channels,))

    def value_and_gradients(fn):
        def read_out(x_, w_, bias_):
            out = jax.vmap(fn, (0, None, None))(x_, w_, bias_) if batched else fn(x_, w_, bias_)
            return jnp.sum(out * probe), out

        (_, out), grads = jax.jit(jax.value_and_grad(read_out, argnums=(0, 1, 2), has_aux=True))(
            x, w, bias)
        return (out, *grads)

    splits, one = (128, 136), (x[0] if batched else x)  # blocks of 128, 8 and 64 columns
    assert [blk.shape for blk in nh.conv_silu(one, w, bias, splits)] == [
        (length, 128), (length, 8), (length, 64)]
    got = value_and_gradients(
        lambda *args: jnp.concatenate(nh.conv_silu(*args, splits), axis=1))
    want = value_and_gradients(lambda *args: jax.nn.silu(_plain_conv(*args)))
    for mine, plain in zip(got, want):  # value, dx, dw, dbias
        assert mine.dtype == jnp.float32 and mine.shape == plain.shape
        _close(mine, plain, tol=1e-5)
    _close(nh.causal_depthwise_conv(one, w, bias), _plain_conv(one, w, bias), tol=1e-6)


def test_the_mixers_gradient_holds_no_padded_copy_of_the_convolution():
    """What automatic differentiation made of the four taps: four
    ``dynamic_update_slice`` into zero arrays of ``T + 3`` rows (under
    ``vmap``: scatter-adds). The lowered gradient holds none outside the
    scan, which reads its chunks' last rows that way, and stays float32."""
    p = nh.init_params(TINY, seed=3)[nh.segment_keys(TINY)[1]]
    x = jax.random.normal(jax.random.PRNGKey(1), (21, TINY.hidden_size))

    def loss(p_, x_, batched):
        mixer = lambda s: nh.mamba2_mixer(p_, s, TINY)  # noqa: E731
        return jnp.sum(jax.vmap(mixer)(x_[None]) if batched else mixer(x_))

    for batched in (False, True):
        text = jax.jit(jax.grad(loss, argnums=(0, 1)), static_argnums=2).lower(
            p, x, batched).as_text(debug_info=True)
        named = re.findall(r'^#loc\d+ = loc\("(jit\(loss\)/[^"]*)"', text, re.M)
        moved = [name for name in named
                 if name.rsplit("/", 1)[-1] in ("dynamic_update_slice", "scatter", "scatter-add")]
        assert len(moved) == 1 and "model.ssm_scan" in moved[0], moved
        assert len(re.findall(r'= "?stablehlo\.(?:dynamic_update_slice|scatter)\b', text)) == 1
        backward = [name for name in named if re.search(
            r"transpose\(jvp\((?:vmap\()?model\.ssm_gate\)+/model\.ssm_gate/\w+$", name)]
        assert {name.rsplit("/", 1)[-1] for name in backward} >= {"logistic", "pad", "reduce_sum"}
        floats = set(re.findall(r"tensor<(?:\d+x)*((?:bf|f)\d+)>", text))
        assert floats == {"f32"}, floats


def _moe_weights(cfg, seed, experts):
    whole = replace(cfg, held_experts=(0, experts))
    return nh.init_params(replace(whole, pattern="E"), seed)[nh.segment_keys(
        replace(whole, pattern="E"))[1]]


def _share(p, x, cfg, first, count, round_rows, shared):
    return held_experts_ffn(
        x, p["router"], p["experts_up"][first:first + count],
        p["experts_down"][first:first + count],
        p["shared_up"] if shared else None, p["shared_down"] if shared else None,
        first_held=first, n_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, round_rows=round_rows)


@pytest.mark.parametrize("round_rows", [64, 8, None])  # one round; several; the default
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(round_rows):
    p = _moe_weights(TINY, 5, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, TINY.hidden_size))
    want, want_counts = ref.moe_dense_mask(p, x, {**_arch(TINY), "held_experts": [0, 16]})
    total, counts = 0.0, []
    for first in (0, 4, 8, 12):  # four chips, four experts each; the shared expert once
        out, aux = _share(p, x, TINY, first, 4, round_rows, shared=first == 0)
        total = total + out
        counts.append(aux["held_expert_tokens"])
        assert int(aux["tokens_dropped"]) == 0
    _close(total, want)
    np.testing.assert_array_equal(np.concatenate(counts), want_counts)
    assert int(np.sum(want_counts)) == 64 * TINY.num_experts_per_tok


@pytest.mark.parametrize("round_rows", [32, 8])  # one round; several, backward too
def test_one_share_and_its_gradient_match_the_reference_given_the_same_share(round_rows):
    p = _moe_weights(TINY, 6, 16)
    held = {**p, "experts_up": p["experts_up"][4:8], "experts_down": p["experts_down"][4:8]}
    x = jax.random.normal(jax.random.PRNGKey(1), (48, TINY.hidden_size))
    (y, _, g), (y_ref, _, g_ref) = _both(
        lambda p_, x_: held_experts_ffn(
            x_, p_["router"], p_["experts_up"], p_["experts_down"], p_["shared_up"],
            p_["shared_down"], first_held=4, n_experts=16, top_k=3,
            scale=TINY.routed_scaling_factor, round_rows=round_rows)[0],
        lambda p_, x_: ref.moe_dense_mask(p_, x_, _arch(TINY))[0], held, x)
    _close(y, y_ref)
    for got, want in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g_ref)):
        _close(got, want, tol=1e-4)


def test_no_token_is_dropped_under_a_router_skewed_to_one_expert():
    p = _moe_weights(TINY, 7, 16)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, TINY.hidden_size))) + 0.1
    router = p["router"].at[:, 5].set(4.0)  # every token's first choice is expert 5
    p = {**p, "router": router}
    out, aux = _share(p, x, TINY, 4, 4, 8, shared=True)
    assert int(aux["held_expert_tokens"][1]) == 64
    assert int(aux["tokens_dropped"]) == 0 and int(aux["expert_rounds"]) == 8
    want, _ = ref.moe_dense_mask(
        {**p, "experts_up": p["experts_up"][4:8], "experts_down": p["experts_down"][4:8]},
        x, _arch(TINY))
    _close(out, want)
    # and with rows for them all in one round the same
    roomy, aux = _share(p, x, TINY, 4, 4, 64, shared=True)
    assert int(aux["expert_rounds"]) == 1 and int(aux["tokens_dropped"]) == 0
    _close(roomy, want)


def test_the_chain_is_the_reference_model_loss_gradient_and_counts():
    bundle = nh.nemotron_h_bundle(TINY, seed=8)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.randint(k1, (2, 21), 0, TINY.vocab_size)
    y = jax.random.randint(k2, (2, 21), 0, TINY.vocab_size)
    loss, grads = jax.value_and_grad(bundle.loss_fn)(bundle.params, x, y)
    (want, counts), want_grads = jax.value_and_grad(ref.loss_and_counts, has_aux=True)(
        bundle.params, x, y, _arch(TINY))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref_leaf in zip(jax.tree_util.tree_leaves(grads),
                             jax.tree_util.tree_leaves(want_grads)):
        _close(got, ref_leaf, tol=2e-4)
    assert counts.shape == (2, 4)


def test_the_published_sizes_count_667_million_parameters():
    shapes = jax.eval_shape(lambda: nh.nemotron3_nano_ep16(0).params)
    per_segment = {key: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(sub))
                   for key, sub in shapes.items()}
    assert list(per_segment) == list(nh.segment_keys(nh.NemotronHConfig()))
    assert abs(sum(per_segment.values()) - 667e6) < 0.01 * 667e6
    assert per_segment["seg02_moe"] == max(per_segment.values())
    assert abs(per_segment["seg01_mamba"] - 38.74e6) < 0.01e6
    assert abs(per_segment["seg06_attn"] - 23.40e6) < 0.01e6
    assert abs(per_segment["seg02_moe"] - 100.1e6) < 0.1e6
