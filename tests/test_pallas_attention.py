"""The block-causal attention kernels (``ops/pallas_attention.py``) and the
gate that hands ``models.nemotron_h.gqa_attention`` to them.

On the CPU the kernels run in the Pallas interpreter. Held here:

* the kernels are the full score matrix: output and the gradients with
  respect to q, k and v against a plain ``(T, T)`` softmax at the highest
  precision, at 32 / 2 and 4 / 2 heads, for a sequence of one block, of
  several, and of a length that is no whole number of blocks;
* through ``gqa_attention`` the kernel route is the ``lax.map`` route and
  the benchmark's reference (``chipbench/reference_nemotron_h.
  attention_full``), output and gradients down to the four weights;
* a block above the diagonal is skipped, not masked after the fact: keys
  there may be NaN;
* ``vmap`` over sequences;
* the gate, condition by condition, asked once a call in Python; off a
  TPU ``gqa_attention`` lowers to the text it lowered to before there was
  a kernel;
* the list of pairs a kernel's grid walks is the pairs at or under the
  diagonal, no more and no fewer;
* with a ``window`` (SmallThinker's sliding-window blocks) the kernels are
  the plain masked form ``0 <= i - j < W``, forward, dq, dk and dv, at 7 / 16
  / 1 heads a group, head widths 128 and 64, lengths and windows that are no
  whole blocks, a window of one; the pairs are those the window reaches, no
  more and no fewer; ``W >= T`` and ``window=None`` are the causal call bit
  for bit, with the same pair lists and the same kernels' names.

The kernels' Mosaic compile at the real width is held in
``tests/test_round_matrix_once.py`` (the one file that compiles for a
described TPU).
"""

from __future__ import annotations

import hashlib
import inspect
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from byzpy_tpu.models import nemotron_h as nh
from byzpy_tpu.ops import pallas_attention as pa
from byzpy_tpu.ops import pallas_kernels as pk
from chipbench import reference_nemotron_h as ref

HD = 128
CONFIGS = {
    "4of2": nh.NemotronHConfig(hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
                               head_dim=HD, query_block=64),
    "32of2": nh.NemotronHConfig(hidden_size=48, num_attention_heads=32, num_key_value_heads=2,
                                head_dim=HD, query_block=64),
}
# one block; no whole number of blocks (three of 128); several (query block
# 256 against key blocks of 1024, 512 and 256: pairs the diagonal crosses
# and pairs wholly under it)
LENGTHS = {"4of2": [128, 300, 2048, 768], "32of2": [128, 300, 512]}
CASES = [(name, t) for name, lengths in LENGTHS.items() for t in lengths]
# keys 192 / values 128 a head (latent attention's DeepSeek-V3 shape): the
# queries and keys go in at 256, 64 zero columns behind the 192, with the
# scale of 192; the values, the output and its cotangent at 128. One query
# head a key/value head (three of them), and eight (two groups)
TWO_WIDTHS = {"3of3@192/128": (3, 3), "16of2@192/128": (16, 2)}
CASES_192_128 = [(name, t) for name in TWO_WIDTHS for t in (128, 300, 1280)]
QK, QK_PADDED, VD = 192, 256, 128


def _arch(cfg):
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim}


def _weights(cfg, seed):
    heads, kv, hd, hidden = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                             cfg.hidden_size)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = {"w_q": (hidden, heads * hd), "w_k": (hidden, kv * hd), "w_v": (hidden, kv * hd),
              "w_o": (heads * hd, hidden)}
    # keys and queries a few units long: a softmax that is far from uniform
    return {name: jax.random.normal(k, shape) * (0.5 if name in ("w_q", "w_k") else
                                                 1 / math.sqrt(shape[0]))
            for k, (name, shape) in zip(keys, shapes.items())}


def _qkv(cfg, t, seed):
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (t, heads * HD)), jax.random.normal(keys[1], (t, kv * HD)),
            jax.random.normal(keys[2], (t, kv * HD)), jax.random.normal(keys[3], (t, heads * HD)))


def _full_scores(q, k, v, kv, qk=HD, vd=HD):
    t = q.shape[0]
    per = q.shape[1] // (kv * qk)
    q, k, v = q.reshape(t, kv, per, qk), k.reshape(t, kv, qk), v.reshape(t, kv, vd)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k, precision="highest") / math.sqrt(qk)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("grqk,kgd->qgrd", probs, v, precision="highest").reshape(t, -1)
    return out, jax.scipy.special.logsumexp(scores, axis=-1)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale


@pytest.fixture
def kernel_route(monkeypatch):
    """``kernel_route(True | False)``: what the gate answers
    ``gqa_attention`` (the kernels themselves stay interpreted: the
    backend is the CPU's)."""

    def choose(serves):
        monkeypatch.setattr(nh, "causal_attention_serves", lambda x, head_dim: serves)

    return choose


# -- the kernels are the full score matrix -----------------------------------


@pytest.mark.parametrize("name, t", CASES)
def test_kernels_are_the_full_score_matrix_forward_and_gradient(name, t):
    cfg = CONFIGS[name]
    kv = cfg.num_key_value_heads
    q, k, v, probe = _qkv(cfg, t, seed=t)
    out = pa.causal_attention(q, k, v, kv_heads=kv)
    assert out.shape == q.shape and out.dtype == q.dtype
    _close(out, _full_scores(q, k, v, kv)[0])
    got = jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv) * probe),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_full_scores(*a, kv)[0] * probe),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w)


def _padded_heads(a, heads, width, to):
    """``(T, heads * width) -> (T, heads * to)``: zero columns behind every head."""
    t = a.shape[0]
    return jnp.pad(a.reshape(t, heads, width), ((0, 0), (0, 0), (0, to - width))).reshape(t, -1)


@pytest.mark.parametrize("name, t", CASES_192_128)
def test_kernels_at_keys_192_values_128_are_the_full_score_matrix_forward_and_gradient(name, t):
    """Forward, dq, dk and dv against the plain softmax at float32, on the
    PUBLISHED widths: the kernels see the padded queries and keys, and the
    gradient with respect to the 192 real columns is what is compared (the
    padded columns' is cut off by the pad's own transpose)."""
    heads, kv = TWO_WIDTHS[name]
    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(keys[0], (t, heads * QK))
    k = jax.random.normal(keys[1], (t, kv * QK))
    v = jax.random.normal(keys[2], (t, kv * VD))
    probe = jax.random.normal(keys[3], (t, heads * VD))

    def kernels(q_, k_, v_):
        return pa.causal_attention(
            _padded_heads(q_, heads, QK, QK_PADDED), _padded_heads(k_, kv, QK, QK_PADDED), v_,
            kv_heads=kv, scale=1.0 / math.sqrt(QK))

    out = kernels(q, k, v)
    assert out.shape == (t, heads * VD) and out.dtype == q.dtype
    _close(out, _full_scores(q, k, v, kv, QK, VD)[0])
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * probe), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_full_scores(*a, kv, QK, VD)[0] * probe),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):  # dq, dk, dv
        assert g.shape == w.shape
        _close(g, w)


def test_the_default_scale_is_the_keys_width_and_an_explicit_one_replaces_it():
    q, k, v, _ = _qkv(CONFIGS["4of2"], 128, seed=3)
    plain = pa.causal_attention(q, k, v, kv_heads=2)
    np.testing.assert_array_equal(
        plain, pa.causal_attention(q, k, v, kv_heads=2, scale=1.0 / math.sqrt(HD)))
    # softmax(c q k^T) v: a scale handed over is a scale on the queries
    _close(pa.causal_attention(q, k, v, kv_heads=2, scale=0.5 / math.sqrt(HD)),
           pa.causal_attention(0.5 * q, k, v, kv_heads=2))


def test_values_wider_than_keys_are_served_too():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(key, (200, 2 * 128)) for key in keys[:2])
    v = jax.random.normal(keys[2], (200, 2 * 256))
    out = pa.causal_attention(q, k, v, kv_heads=2)
    assert out.shape == (200, 2 * 256)
    _close(out, _full_scores(q, k, v, 2, 128, 256)[0])


@pytest.mark.parametrize("name, t", [("4of2", 300), ("32of2", 512)])
def test_forward_writes_one_log_sum_exp_a_query_row_a_head(name, t):
    cfg = CONFIGS[name]
    kv, per = cfg.num_key_value_heads, cfg.num_attention_heads // cfg.num_key_value_heads
    q, k, v, _ = _qkv(cfg, t, seed=1)
    out, lse = pa._forward(q, k, v, kv, 1.0 / math.sqrt(HD), True)
    t_pad = pa._blocks(t, per, backward=False)[0]
    assert lse.shape == (kv, per, t_pad) and lse.dtype == jnp.float32
    _close(lse[..., :t], _full_scores(q, k, v, kv)[1])
    assert out.shape == q.shape


def test_bfloat16_operands_give_a_bfloat16_result_near_the_float32_one():
    cfg = CONFIGS["4of2"]
    q, k, v, probe = _qkv(cfg, 300, seed=2)
    want = pa.causal_attention(q, k, v, kv_heads=2)
    narrow = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    got = pa.causal_attention(*narrow, kv_heads=2)
    assert got.dtype == jnp.bfloat16
    _close(got, want, tol=3e-2)
    grads = jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=2).astype(jnp.float32)
                                        * probe), argnums=(0, 1, 2))(*narrow)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)


def test_a_head_dim_that_is_no_whole_lanes_is_refused_by_the_wrapper():
    with pytest.raises(ValueError, match="head_dim"):
        pa.causal_attention(jnp.zeros((16, 4 * 8)), jnp.zeros((16, 2 * 8)), jnp.zeros((16, 2 * 8)),
                            kv_heads=2)


def test_narrow_heads_that_do_not_fill_whole_lane_tiles_are_refused_by_the_wrapper():
    with pytest.raises(ValueError, match="whole"):  # three key/value heads of 64: a tile and a half
        pa.causal_attention(jnp.zeros((16, 6 * 64)), jnp.zeros((16, 3 * 64)),
                            jnp.zeros((16, 3 * 64)), kv_heads=3)
    with pytest.raises(ValueError, match="whole"):  # values at another width than the keys
        pa.causal_attention(jnp.zeros((16, 4 * 64)), jnp.zeros((16, 2 * 64)),
                            jnp.zeros((16, 2 * 128)), kv_heads=2)


# -- heads narrower than a lane tile: two (four) to a tile, nothing padded -------

# (query heads, key/value heads, head width): four query heads a key/value head
# in one tile (a step of eight); the cell's 32 / 8 (four steps); one query head a
# key/value head; four heads of 32 a tile
NARROW = {"8of2@64": (8, 2, 64), "32of8@64": (32, 8, 64), "4of4@64": (4, 4, 64),
          "8of4@32": (8, 4, 32)}
NARROW_CASES = [(name, t) for name in NARROW for t in ((128, 300, 768) if name != "32of8@64"
                                                       else (300,))]


@pytest.mark.parametrize("name, t", NARROW_CASES)
def test_kernels_at_narrow_heads_are_the_full_score_matrix_forward_and_gradient(name, t):
    heads, kv, hd = NARROW[name]
    keys = jax.random.split(jax.random.PRNGKey(t), 4)
    q, probe = (jax.random.normal(k, (t, heads * hd)) for k in keys[:2])
    k, v = (jax.random.normal(k_, (t, kv * hd)) for k_ in keys[2:])
    out = pa.causal_attention(q, k, v, kv_heads=kv)
    assert out.shape == q.shape and out.dtype == q.dtype
    want_out, want_lse = _full_scores(q, k, v, kv, hd, hd)
    _close(out, want_out)
    got = jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv) * probe),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_full_scores(*a, kv, hd, hd)[0] * probe),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _close(g, w)
    # one log-sum-exp a query row a head, a step's heads in the heads' own order
    pack = 128 // hd
    t_pad, block_q, block_k = pa._blocks(t, heads // kv * pack, backward=False)
    _, lse = pa._causal_attention_fwd_call(
        *pa._padded(t_pad, q, k, v), kv_heads=kv, scale=1 / math.sqrt(hd), block_q=block_q,
        block_k=block_k, interpret=True)
    assert lse.shape == (kv // pack, heads // kv * pack, t_pad)
    _close(lse.reshape(kv, heads // kv, t_pad)[..., :t], want_lse, tol=1e-5)


def test_narrow_heads_in_bfloat16_give_a_bfloat16_result_near_the_float32_one():
    heads, kv, hd, t = 8, 2, 64, 300
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (t, heads * hd))
    k, v = (jax.random.normal(k_, (t, kv * hd)) for k_ in keys[1:])
    want = pa.causal_attention(q, k, v, kv_heads=kv)
    lo = [a.astype(jnp.bfloat16) for a in (q, k, v)]
    got = pa.causal_attention(*lo, kv_heads=kv)
    assert got.dtype == jnp.bfloat16
    _close(got, want, tol=0.05)
    grads = jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv).astype(jnp.float32)),
                     argnums=(0, 1, 2))(*lo)
    assert all(g.dtype == jnp.bfloat16 for g in grads)
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)


@pytest.mark.parametrize("t", [21, 300, 768])
def test_lfm2_attention_by_the_kernels_is_the_map_route_and_the_reference(monkeypatch, t):
    """Head width 64, per-head norms and the rotary turn in front: the
    kernel route against the ``lax.map`` route and the benchmark's
    reference, the output and the gradient of every weight and of the
    input."""
    from byzpy_tpu.models import lfm2_moe
    from chipbench import reference_lfm2_moe

    cfg = lfm2_moe.Lfm2MoeConfig(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
                                 query_block=64)
    keys = jax.random.split(jax.random.PRNGKey(t), 8)
    p = {"w_q": jax.random.normal(keys[0], (256, 256)) / 16,
         "w_k": jax.random.normal(keys[1], (256, 128)) / 16,
         "w_v": jax.random.normal(keys[2], (256, 128)) / 16,
         "w_o": jax.random.normal(keys[3], (256, 256)) / 16,
         "q_norm_scale": jax.random.uniform(keys[4], (64,), minval=1.0, maxval=3.0),
         "k_norm_scale": jax.random.uniform(keys[5], (64,), minval=1.0, maxval=3.0)}
    x, probe = jax.random.normal(keys[6], (t, 256)), jax.random.normal(keys[7], (t, 256))
    arch = {"num_attention_heads": 4, "num_key_value_heads": 2, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}
    routes = {}
    for serves in (True, False):
        monkeypatch.setattr(lfm2_moe, "causal_attention_serves", lambda x_, hd: serves)
        routes[serves] = _value_and_grads(lambda p_, x_: lfm2_moe.gqa_attention(p_, x_, cfg),
                                          p, x, probe)
    want = _value_and_grads(lambda p_, x_: reference_lfm2_moe.attention_full(p_, x_, arch),
                            p, x, probe)
    for got in routes.values():
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            _close(g, w, tol=1e-4)
    calls = str(jax.make_jaxpr(jax.grad(
        lambda p_, x_: jnp.sum(lfm2_moe.gqa_attention(p_, x_, cfg))))(p, x)).count("pallas_call")
    assert calls == 0  # the gate's last answer was no
    monkeypatch.setattr(lfm2_moe, "causal_attention_serves", lambda x_, hd: True)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p_, x_: jnp.sum(lfm2_moe.gqa_attention(p_, x_, cfg))))(p, x))
    # the forward and the backward's two, and no pad of a head to 128 in front
    assert text.count("pallas_call") == 3
    assert f"f32[{t},4,128]" not in text and f"f32[{t},2,128]" not in text


# -- through gqa_attention: the kernel route, the map route, the reference ----


def _value_and_grads(fn, p, x, probe):
    out = fn(p, x)
    grads = jax.grad(lambda p_, x_: jnp.sum(fn(p_, x_) * probe), argnums=(0, 1))(p, x)
    return out, grads


@pytest.mark.parametrize("name, t", [("4of2", 128), ("4of2", 300), ("4of2", 768),
                                     ("32of2", 128), ("32of2", 300)])
def test_gqa_attention_by_the_kernels_is_the_map_route_and_the_reference(name, t, kernel_route):
    cfg = CONFIGS[name]
    p = _weights(cfg, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(9), (t, cfg.hidden_size))
    kernel_route(False)
    by_map = _value_and_grads(lambda p_, x_: nh.gqa_attention(p_, x_, cfg), p, x, probe)
    kernel_route(True)
    by_kernel = _value_and_grads(lambda p_, x_: nh.gqa_attention(p_, x_, cfg), p, x, probe)
    with jax.default_matmul_precision("highest"):
        by_reference = _value_and_grads(
            lambda p_, x_: ref.attention_full(p_, x_, _arch(cfg)), p, x, probe)
    for other in (by_map, by_reference):
        _close(by_kernel[0], other[0], tol=1e-4)
        for leaf in ("w_q", "w_k", "w_v", "w_o"):
            _close(by_kernel[1][0][leaf], other[1][0][leaf], tol=1e-4)
        _close(by_kernel[1][1], other[1][1], tol=1e-4)


# -- blocks above the diagonal are skipped ------------------------------------


@pytest.mark.parametrize("name, t", [("4of2", 2048), ("32of2", 768)])
def test_blocks_above_the_diagonal_may_be_nan(name, t):
    """Skipped, not masked after the fact. The LAST KEY BLOCK is NaN:
    every query before it has it wholly above its diagonal, so their
    output and dq never read it. The FIRST QUERY BLOCK is NaN: every key
    block after the first has it wholly above, so their dk and dv never
    read it. Masked after the fact, the backward's products would multiply
    a zero by the NaN (as the ``lax.map`` route's do)."""
    cfg = CONFIGS[name]
    kv = cfg.num_key_value_heads
    q, k, v, probe = _qkv(cfg, t, seed=3)
    per = cfg.num_attention_heads // kv
    _, block_q, block_k = pa._blocks(t, per, backward=False)  # the wider of the two
    assert block_k >= pa._blocks(t, per, backward=True)[2]

    def grads(q_, k_, probe_):
        return jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv) * probe_),
                        argnums=(0, 1, 2))(q_, k_, v)

    cut = t - block_k
    assert cut >= 512
    poisoned = k.at[cut:].set(jnp.nan)
    out = pa.causal_attention(q, poisoned, v, kv_heads=kv)
    assert bool(jnp.all(jnp.isfinite(out[:cut]))) and bool(jnp.all(jnp.isnan(out[cut:])))
    _close(out[:cut], pa.causal_attention(q[:cut], k[:cut], v[:cut], kv_heads=kv))
    # the queries that do see the NaN keys are left out of the read-out
    dq, _, _ = grads(q, poisoned, probe.at[cut:].set(0.0))
    assert bool(jnp.all(jnp.isfinite(dq[:cut])))

    poisoned = q.at[:block_q].set(jnp.nan)
    out = pa.causal_attention(poisoned, k, v, kv_heads=kv)
    assert bool(jnp.all(jnp.isfinite(out[block_q:]))) and bool(jnp.all(jnp.isnan(out[:block_q])))
    _, dk, dv = grads(poisoned, k, probe.at[:block_q].set(0.0))
    after = max(block_q, block_k)
    assert bool(jnp.all(jnp.isfinite(dk[after:]))) and bool(jnp.all(jnp.isfinite(dv[after:])))
    assert bool(jnp.all(jnp.isnan(dk[:block_q])))  # the keys the NaN queries do see


# -- vmap over sequences -------------------------------------------------------


def test_vmap_over_sequences_is_one_sequence_after_another(kernel_route):
    cfg = CONFIGS["4of2"]
    p = _weights(cfg, seed=6)
    xs = jax.random.normal(jax.random.PRNGKey(4), (3, 200, cfg.hidden_size))
    kernel_route(True)

    def read(p_, xs_):
        return jnp.sum(jnp.sin(jax.vmap(lambda s: nh.gqa_attention(p_, s, cfg))(xs_)))

    value, (dp, dxs) = jax.jit(jax.value_and_grad(read, argnums=(0, 1)))(p, xs)
    one_by_one = [jax.value_and_grad(
        lambda p_, s: jnp.sum(jnp.sin(nh.gqa_attention(p_, s, cfg))), argnums=(0, 1))(p, s)
        for s in xs]
    _close(value, sum(v for v, _ in one_by_one), tol=1e-5)
    _close(dxs, jnp.stack([g[1] for _, g in one_by_one]), tol=1e-4)
    for leaf in p:
        _close(dp[leaf], sum(g[0][leaf] for _, g in one_by_one), tol=1e-4)


# -- the gate -------------------------------------------------------------------


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16", "float64", "int32"])
@pytest.mark.parametrize("head_dim", [128, 256, 8, 64, 192])
def test_gate_table(monkeypatch, platform, dtype, head_dim):
    monkeypatch.setattr(pk, "_on_tpu", lambda: platform == "tpu")
    # whole lanes, or (PR 46) a head that lies two or four to a lane tile
    want = (platform == "tpu" and dtype in ("float32", "bfloat16")
            and (head_dim % 128 == 0 or head_dim in (64, 32)))
    with jax.enable_x64(dtype == "float64"):
        assert pa.causal_attention_serves(_sds((4096, 2688), jnp.dtype(dtype)), head_dim) is want


@pytest.mark.parametrize("head_dim, v_head_dim, want", [
    (256, 128, True), (128, 256, True), (256, None, True), (192, 128, False), (256, 64, False),
    (256, 192, False), (64, None, True), (64, 64, True), (64, 128, False), (128, 64, False),
    (32, 32, True), (96, 96, False)])
def test_gate_asks_both_widths(monkeypatch, head_dim, v_head_dim, want):
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    x = _sds((1024, 3584))
    assert pa.causal_attention_serves(x, head_dim, v_head_dim) is want
    monkeypatch.setattr(pk, "_on_tpu", lambda: False)
    assert pa.causal_attention_serves(x, head_dim, v_head_dim) is False


def test_gate_refuses_a_device_sharded_operand(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    mesh = jax.make_mesh((4,), ("seq",), devices=jax.devices()[:4],
                         axis_types=(AxisType.Explicit,))
    x = jnp.zeros((4096, 256), jnp.float32)
    with jax.set_mesh(mesh):
        sharded = jax.device_put(x, NamedSharding(mesh, P("seq", None)))
        whole = jax.device_put(x, NamedSharding(mesh, P(None, None)))
        assert not pa.causal_attention_serves(sharded, 128)
        assert pa.causal_attention_serves(whole, 128)
    auto = jax.make_mesh((4,), ("seq",), devices=jax.devices()[:4], axis_types=(AxisType.Auto,))
    # an Auto mesh hides the real spec at trace time: stay on XLA
    assert not pa.causal_attention_serves(
        jax.device_put(x, NamedSharding(auto, P("seq", None))), 128)
    assert pa.causal_attention_serves(x, 128)


def _pallas_calls(cfg, t, *, grad):
    p = jax.eval_shape(lambda: _weights(cfg, 0))
    x = _sds((t, cfg.hidden_size))

    def fn(p_, x_):
        return jnp.sum(nh.gqa_attention(p_, x_, cfg))

    jaxpr = jax.make_jaxpr(jax.grad(fn) if grad else fn)(p, x)
    return str(jaxpr).count("pallas_call")


@pytest.mark.parametrize("name, t", [("4of2", 4096), ("32of2", 4096), ("4of2", 21), ("32of2", 300)])
def test_on_a_tpu_gqa_attention_is_the_kernels_whatever_the_length(monkeypatch, name, t):
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert _pallas_calls(CONFIGS[name], t, grad=False) == 1
    assert _pallas_calls(CONFIGS[name], t, grad=True) == 3  # forward, dq, dk / dv
    jaxpr = str(jax.make_jaxpr(lambda p_, x_: nh.gqa_attention(p_, x_, CONFIGS[name]))(
        jax.eval_shape(lambda: _weights(CONFIGS[name], 0)), _sds((t, 48))))
    assert "remat" not in jaxpr  # no query block rematerialised: the kernels recompute


@pytest.mark.parametrize("platform, head_dim, dtype", [
    ("cpu", 128, jnp.float32), ("tpu", 8, jnp.float32), ("tpu", 96, jnp.bfloat16),
    ("tpu", 128, jnp.float16)])
def test_elsewhere_gqa_attention_is_the_map_over_query_blocks(monkeypatch, platform, head_dim,
                                                              dtype):
    monkeypatch.setattr(pk, "_on_tpu", lambda: platform == "tpu")
    cfg = nh.NemotronHConfig(hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
                             head_dim=head_dim, query_block=64)
    p = jax.eval_shape(lambda: jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                                      _weights(cfg, 0)))
    jaxpr = str(jax.make_jaxpr(lambda p_, x_: nh.gqa_attention(p_, x_, cfg))(
        p, _sds((200, 48), dtype)))
    assert "pallas_call" not in jaxpr and "scan" in jaxpr and "remat" in jaxpr


def test_the_gate_is_a_fact_of_the_call_not_of_an_earlier_trace(monkeypatch):
    cfg = CONFIGS["4of2"]
    seen = []
    for platform in ("cpu", "tpu", "cpu"):
        monkeypatch.setattr(pk, "_on_tpu", lambda platform=platform: platform == "tpu")
        seen.append(_pallas_calls(cfg, 256, grad=False))
    assert seen == [0, 1, 0]


def test_the_gate_is_asked_once_a_call(monkeypatch):
    asked = []
    monkeypatch.setattr(nh, "causal_attention_serves",
                        lambda x, head_dim: asked.append((x.shape, head_dim)) or False)
    cfg = CONFIGS["4of2"]
    jax.make_jaxpr(lambda p_, x_: nh.gqa_attention(p_, x_, cfg))(
        jax.eval_shape(lambda: _weights(cfg, 0)), _sds((200, 48)))
    assert asked == [((200, 48), 128)]


def test_no_variable_field_or_argument_chooses_the_route():
    assert "os.environ" not in inspect.getsource(pa) and "getenv" not in inspect.getsource(pa)
    assert list(inspect.signature(nh.gqa_attention).parameters) == ["p", "x", "cfg"]
    assert list(inspect.signature(pa.causal_attention_serves).parameters) == [
        "x", "head_dim", "v_head_dim"]
    assert list(inspect.signature(pa.causal_attention).parameters) == [
        "q", "k", "v", "kv_heads", "scale", "interpret", "window"]
    # the configuration's attention fields are the four it had (query_block: the XLA route's)
    fields = [f for f in nh.NemotronHConfig.__dataclass_fields__
              if "attention" in f or "head" in f or "block" in f or "key_value" in f]
    assert fields == ["mamba_num_heads", "mamba_head_dim", "num_attention_heads",
                      "num_key_value_heads", "head_dim", "query_block"]


# sha256 of gqa_attention's lowered text (value and gradient under vmap,
# locations stripped) on the commit before the kernels (6625b24), taken with
# this file's own function: off a TPU, and for a head_dim the gate refuses,
# the route is that commit's to the letter.
_LOC = re.compile(r"\s*loc\([^\n]*\)|#loc[^\n]*\n")
PARENT_TEXTS = {
    "head_dim_8": "21a6d49594b68406d4a66da763b7e0b02ff8a7a3aa5d6a53523b04d9a6233bae",
    "head_dim_128": "22a54f0176361f59a1629b514ab7d600c8a90d7b31241fd67eb488864233c3f5",
}
_TEXT_SHAPES = {"head_dim_8": (8, 21), "head_dim_128": (128, 200)}


@pytest.mark.parametrize("case", sorted(PARENT_TEXTS))
def test_off_a_tpu_gqa_attention_lowers_to_the_text_it_had(case):
    hd, t = _TEXT_SHAPES[case]
    cfg = nh.NemotronHConfig(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                             head_dim=hd, query_block=64)
    p = {"w_q": jnp.zeros((32, 4 * hd)), "w_k": jnp.zeros((32, 2 * hd)),
         "w_v": jnp.zeros((32, 2 * hd)), "w_o": jnp.zeros((4 * hd, 32))}
    x = jnp.zeros((3, t, 32))
    fn = jax.jit(jax.value_and_grad(
        lambda p_, x_: jnp.sum(jax.vmap(lambda s: nh.gqa_attention(p_, s, cfg))(x_)), (0, 1)))
    text = _LOC.sub("", fn.lower(p, x).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXTS[case]


# -- blocks and pairs -----------------------------------------------------------


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("per", [16, 1, 2, 8])
@pytest.mark.parametrize("t", [1, 21, 128, 129, 300, 512, 768, 1024, 2048, 4096, 4097, 65536])
def test_blocks_divide_the_padded_length(t, per, backward):
    t_pad, block_q, block_k = pa._blocks(t, per, backward=backward)
    assert t <= t_pad < t + 128 and t_pad % block_q == 0 and t_pad % block_k == 0
    assert block_q in (128, 256, 512, 1024) and block_k in (128, 256, 512, 1024)
    assert block_q & (block_q - 1) == 0  # the mask takes a row's position by a bitwise and
    assert per * block_q <= pa._FOLDED_ROWS
    if backward:
        assert per * block_q * block_k <= pa._BACKWARD_TILE
    # forward and backward agree on the padded length and on the query block: the
    # log-sum-exp is handed over whole
    assert (t_pad, block_q) == pa._blocks(t, per, backward=not backward)[:2]
    if t == 4096:
        # sixteen heads a group (Nemotron, PR 33): 256 x 1024 forward, 256 x 512 backward;
        # one head a group (latent attention, PR 34): 1024 x 1024 in all three kernels
        # eight heads a step: eight of 256 a key/value head (PR 39), or two key/value
        # heads of 64 in one lane tile with four query heads each (PR 46)
        want = {16: (256, 512 if backward else 1024), 1: (1024, 1024), 2: (1024, 1024),
                8: (512, 512 if backward else 1024)}[per]
        assert (block_q, block_k) == want


@pytest.mark.parametrize("key_major", [False, True])
@pytest.mark.parametrize("block_q, block_k, t", [
    (128, 128, 384), (256, 512, 1024), (256, 256, 768), (256, 512, 4096), (256, 1024, 4096),
    (128, 512, 1024), (256, 128, 512)])
def test_pairs_are_the_pairs_at_or_under_the_diagonal(block_q, block_k, t, key_major):
    n_q, n_k = t // block_q, t // block_k
    qs, ks, flags = pa._pairs(n_q, n_k, block_q, block_k, key_major=key_major)
    walked = list(zip(qs.tolist(), ks.tolist()))
    needed = {(qi, kj) for qi in range(n_q) for kj in range(n_k)
              if kj * block_k <= (qi + 1) * block_q - 1}
    assert len(walked) == len(set(walked)) and set(walked) == needed
    assert len(needed) < n_q * n_k or n_q == 1 or n_k == 1
    outer = ks if key_major else qs
    for block in set(outer.tolist()):
        run = [i for i, o in enumerate(outer.tolist()) if o == block]
        assert run == list(range(run[0], run[-1] + 1))  # an outer block's pairs lie together
        assert [bool(flags[i] & pa._FIRST) for i in run] == [True] + [False] * (len(run) - 1)
        assert [bool(flags[i] & pa._LAST) for i in run] == [False] * (len(run) - 1) + [True]
    for (qi, kj), flag in zip(walked, flags.tolist()):
        wholly_seen = (kj + 1) * block_k - 1 <= qi * block_q
        assert bool(flag & pa._MASKED) == (not wholly_seen)


# -- a window ------------------------------------------------------------------------


def _masked_form(q, k, v, kv, hd, window):
    """``softmax(q k^T / sqrt(hd) + mask) v`` from the definition, the mask
    ``0 <= i - j`` and, with a window, ``i - j < window``."""
    t = q.shape[0]
    per = q.shape[1] // (kv * hd)
    q, k, v = q.reshape(t, kv, per, hd), k.reshape(t, kv, hd), v.reshape(t, kv, hd)
    scores = jnp.einsum("qgrd,kgd->grqk", q, k, precision="highest") / math.sqrt(hd)
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = behind >= 0 if window is None else (behind >= 0) & (behind < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", probs, v, precision="highest").reshape(t, -1)


def _windowed_operands(per, kv, hd, t, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (t, per * kv * hd)), jax.random.normal(keys[1], (t, kv * hd)),
            jax.random.normal(keys[2], (t, kv * hd)), jax.random.normal(keys[3], (t, per * kv * hd)))


# (query heads a group, key/value heads, head width, T, W): seven heads a group
# (SmallThinker), sixteen, one; heads of 64 two to a tile; lengths of no whole
# block; windows of no whole block, of one block, of one position, of one less
# than the sequence
WINDOW_CASES = [
    (7, 1, 128, 300, 130), (7, 2, 128, 520, 24), (7, 1, 128, 300, 1), (7, 1, 128, 384, 128),
    (16, 1, 128, 300, 33), (1, 2, 128, 640, 257), (1, 1, 128, 300, 299),
    (4, 2, 64, 300, 70), (2, 2, 64, 200, 128)]


@pytest.mark.parametrize("per, kv, hd, t, window", WINDOW_CASES)
def test_windowed_kernels_are_the_plain_masked_form_forward_and_gradient(per, kv, hd, t, window):
    q, k, v, probe = _windowed_operands(per, kv, hd, t)
    got = pa.causal_attention(q, k, v, kv_heads=kv, window=window)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, _masked_form(q, k, v, kv, hd, window))
    grads = jax.grad(lambda *a: jnp.sum(pa.causal_attention(*a, kv_heads=kv, window=window) * probe),
                     (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: jnp.sum(_masked_form(*a, kv, hd, window) * probe),
                      (0, 1, 2))(q, k, v)
    for name, g, w in zip(("dq", "dk", "dv"), grads, wanted):
        assert bool(jnp.all(jnp.isfinite(g))), name
        # (a window of one: the softmax of one score has no gradient, dq = dk = 0)
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-5 * max(float(jnp.max(jnp.abs(w))), 0.1))


def _kernel_names(fn, *args):
    """The kernels a function calls, by the names in its lowered text (the
    interpreter's lowering carries them in its locations)."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return sorted(set(re.findall(r"(?<![\w.])((?:causal|window)_attention_(?:fwd|dq|dkv))(?!_call)\b",
                                 text)))


@pytest.mark.parametrize("window", [None, 300, 301, 4096])
def test_a_window_no_shorter_than_the_sequence_is_the_causal_call_bit_for_bit(window):
    q, k, v, probe = _windowed_operands(7, 1, 128, 300)

    def both(window_):
        def value(*a):
            return jnp.sum(pa.causal_attention(*a, kv_heads=1, window=window_) * probe)

        return pa.causal_attention(q, k, v, kv_heads=1, window=window_), jax.grad(
            value, (0, 1, 2))(q, k, v), _kernel_names(jax.grad(value, (0, 1, 2)), q, k, v)

    (got, got_grads, got_names), (want, want_grads, want_names) = both(window), both(None)
    np.testing.assert_array_equal(got, want)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_array_equal(g, w)
    assert got_names == want_names == [
        "causal_attention_dkv", "causal_attention_dq", "causal_attention_fwd"]
    # and a window that cuts has kernels of its own names, and no causal one
    assert _kernel_names(jax.grad(lambda *a: jnp.sum(
        pa.causal_attention(*a, kv_heads=1, window=299) * probe), (0, 1, 2)), q, k, v) == [
            "window_attention_dkv", "window_attention_dq", "window_attention_fwd"]
    with pytest.raises(ValueError, match="window"):
        pa.causal_attention(q, k, v, kv_heads=1, window=0)


@pytest.mark.parametrize("key_major", [False, True])
@pytest.mark.parametrize("window", [1, 24, 128, 129, 300, 512, 4096])
@pytest.mark.parametrize("block_q, block_k, t", [
    (128, 128, 384), (256, 512, 1024), (512, 1024, 8192), (512, 512, 8192), (128, 512, 1024),
    (256, 128, 512)])
def test_windowed_pairs_are_the_pairs_the_window_reaches(block_q, block_k, t, window, key_major):
    n_q, n_k = t // block_q, t // block_k
    qs, ks, flags = pa._pairs(n_q, n_k, block_q, block_k, key_major=key_major, window=window)
    walked = list(zip(qs.tolist(), ks.tolist()))

    def entries(qi, kj):  # (i - j) over the pair: least and most
        return (qi * block_q - ((kj + 1) * block_k - 1), (qi + 1) * block_q - 1 - kj * block_k)

    needed = {(qi, kj) for qi in range(n_q) for kj in range(n_k)
              if entries(qi, kj)[1] >= 0 and entries(qi, kj)[0] < window}
    assert len(walked) == len(set(walked)) and set(walked) == needed
    causal = pa._pairs(n_q, n_k, block_q, block_k, key_major=key_major)
    assert set(walked) <= set(zip(causal[0].tolist(), causal[1].tolist()))
    if window >= t:  # nothing to leave out, no edge to mask: the causal lists themselves
        for got, want in zip((qs, ks, flags), causal):
            np.testing.assert_array_equal(got, want)
    outer = ks if key_major else qs
    assert sorted(set(outer.tolist())) == list(range(n_k if key_major else n_q))
    for block in set(outer.tolist()):
        run = [i for i, o in enumerate(outer.tolist()) if o == block]
        assert run == list(range(run[0], run[-1] + 1))
        assert [bool(flags[i] & pa._FIRST) for i in run] == [True] + [False] * (len(run) - 1)
        assert [bool(flags[i] & pa._LAST) for i in run] == [False] * (len(run) - 1) + [True]
    for (qi, kj), flag in zip(walked, flags.tolist()):
        least, most = entries(qi, kj)
        assert bool(flag & pa._MASKED) == (least < 0)
        assert bool(flag & pa._EDGED) == (most >= window)
    if (block_q, block_k, t, window) == (512, 1024, 8192, 4096):
        # the cell's forward: 60 of the causal 72 block pairs (three quarters of the
        # ENTRIES lie inside the window; the pairs its edge crosses are walked whole)
        assert (len(walked), len(causal[0])) == (60, 72)


def test_a_windowed_head_visits_three_quarters_of_the_causal_entries_at_8192():
    """The benchmark's count of a windowed call's entries, against the mask
    itself counted row by row."""
    from chipbench import opcount_window_attention

    t, window = 8192, 4096
    inside = sum(min(i + 1, window) for i in range(t))
    assert inside == 25_167_872 == opcount_window_attention.window_entries(t, window)
    assert 4 * inside == 3 * (t * (t + 1) // 2) - window  # three quarters of the causal half


@pytest.mark.parametrize("rows, want", [
    (4096, 1024), (1024, 1024), (768, 768), (128, 128), (3584, 896), (1792, 896), (1536, 768),
    (2048, 1024)])
def test_a_folded_block_is_walked_in_pieces_that_divide_it(rows, want):
    """Seven heads a group fold 7 x 512 = 3584 rows, which 1024 does not
    divide: the pieces are 896 (the blocks that 1024 divides, or that are
    shorter, are walked as they always were)."""
    assert pa._chunk_rows(rows) == want and rows % want == 0
