"""The streamed round's update (``parallel/ps.py``, ``_streamed_train_step``,
scope ``round.update``): one pass a leaf over the segment's aggregate row.

An optimizer whose update is elementwise (``coordinatewise.is_elementwise``,
read off its jaxpr) is run on the leaves' tiles in the row's own order
(``utils.trees.tile_view``); any other is handed whole leaves, as before.
Both are held against a plain reference made leaf by leaf from the
workers' gradients, with no row and no layout in it, on a segmented toy
whose leaves are tiles that keep their order, tiles one lane group wide,
tiles narrower than 128 lanes, and leaves that are no whole tiles at all."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.bundle import ModelBundle, Segment, chain_loss
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step, default_optimizer
from byzpy_tpu.utils.trees import leaf_view, row_layout, tile_view, tree_size

N, B, BATCH, CLASSES = 8, 2, 4, 5
LR, MU = 0.1, 0.9
CFG = PSStepConfig(n_nodes=N, n_byzantine=B, learning_rate=LR, momentum=MU)
AGGREGATE = partial(robust.trimmed_mean, f=2)
SIGN_FLIP = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")

# leaf shapes: (16, 256) keeps the order of its tiles; (256, 128) is row-major
# tiles already; (128, 64) is 8 x 1024 elements under 128 lanes wide; (64, 24),
# (24, 5) and the biases are no whole tiles
SHAPES = {
    "s0_in": {"kernel": (16, 256), "bias": (256,)},
    "s1_mid": {"kernel": (256, 128), "bias": (128,), "narrow": (128, 64), "back": (64, 24)},
    "s2_head": {"kernel": (24, CLASSES), "bias": (CLASSES,)},
}


def _segments():
    def first(p, x):
        return jnp.tanh(x @ p["kernel"] + p["bias"])

    def middle(p, x):
        h = jnp.tanh(x @ p["kernel"] + p["bias"])
        return jnp.tanh(h @ p["narrow"]) @ p["back"]

    def head(p, x, y):
        logits = x @ p["kernel"] + p["bias"]
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    return (Segment("s0_in", first), Segment("s1_mid", middle), Segment("s2_head", head))


def _params(seed=0):
    out, key = {}, jax.random.PRNGKey(seed)
    for seg, leaves in SHAPES.items():
        out[seg] = {}
        for name, shape in leaves.items():
            key, sub = jax.random.split(key)
            out[seg][name] = jax.random.normal(sub, shape) / np.sqrt(shape[0])
    return out


def _batches(steps=2):
    key, out = jax.random.PRNGKey(7), []
    for _ in range(steps):
        kx, ky, key = jax.random.split(key, 3)
        out.append((jax.random.normal(kx, (N, BATCH, 16)),
                    jax.random.randint(ky, (N, BATCH), 0, CLASSES)))
    return out


OPTIMIZERS = {
    "default": None,
    "sgd": optax.sgd(LR),
    "nesterov": optax.sgd(LR, momentum=MU, nesterov=True),
    "adam": optax.adam(1e-2),
    "adamw": optax.adamw(1e-2, weight_decay=0.05),
    # leaf by leaf, and not element by element: whole leaves, as before
    "block_rms": optax.chain(optax.clip_by_block_rms(0.01), optax.sgd(LR, momentum=MU)),
}
ELEMENTWISE = {"default", "sgd", "nesterov", "adam", "adamw"}


def _optimizer(name):
    return default_optimizer(CFG) if OPTIMIZERS[name] is None else OPTIMIZERS[name]


def _reference(name, steps):
    """Leaf by leaf from the workers' own gradients: no row, no layout."""
    segs = _segments()
    loss = chain_loss(segs)
    opt = _optimizer(name)
    params = _params()
    # a state a segment, as the streamed round keeps it: every optimizer here
    # updates leaf by leaf, so that is the state of the whole tree, regrouped
    state = {seg: opt.init(params[seg]) for seg in SHAPES}
    norms = []
    for xs, ys in _batches(steps):
        grads = [jax.grad(loss)(params, xs[i], ys[i]) for i in range(N - B)]

        def aggregate(*leaves):
            honest = jnp.stack([leaf.reshape(-1) for leaf in leaves])
            byz = jnp.broadcast_to(-jnp.mean(honest, axis=0), (B, honest.shape[1]))
            return AGGREGATE(jnp.concatenate([honest, byz])).reshape(leaves[0].shape)

        agg = jax.tree_util.tree_map(aggregate, *grads)
        norms.append(np.sqrt(sum(float(jnp.sum(jnp.square(leaf)))
                                 for leaf in jax.tree_util.tree_leaves(agg))))
        for seg in SHAPES:
            if OPTIMIZERS[name] is None:  # as default_optimizer defines it, written out
                trace = jax.tree_util.tree_map(
                    lambda g, m: g + MU * m, agg[seg], state[seg][0].trace)
                params[seg] = jax.tree_util.tree_map(lambda p, m: p - LR * m, params[seg], trace)
                state[seg] = (state[seg][0]._replace(trace=trace), state[seg][1])
            else:
                updates, state[seg] = opt.update(agg[seg], state[seg], params[seg])
                params[seg] = optax.apply_updates(params[seg], updates)
    return params, state, norms


def _streamed(name, steps):
    bundle = ModelBundle(apply_fn=None, params=_params(), segments=_segments())
    given = None if OPTIMIZERS[name] is None else coordinatewise.leafwise(OPTIMIZERS[name])
    step, state = build_ps_train_step(bundle, AGGREGATE, CFG, attack=SIGN_FLIP, optimizer=given)
    step = jax.jit(step)
    params, norms = bundle.params, []
    for i, (xs, ys) in enumerate(_batches(steps)):
        params, state, metrics = step(params, state, xs, ys, jax.random.PRNGKey(i))
        norms.append(float(metrics["agg_grad_norm"]))
    return params, state, norms


@pytest.fixture(params=["flat", "folded"])
def rows(request, monkeypatch):
    """``folded``: the kernels forced (interpreted here), so every segment's
    row is whole tiles and its tile leaves stand in the row's order."""
    if request.param == "folded":
        monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    return request.param


def _assert_trees_close(got, want, **tol):
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(a, b, **tol)


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_streamed_update_equals_the_leaf_by_leaf_reference(rows, name):
    params, state, norms = _streamed(name, steps=2)
    want_params, want_state, want_norms = _reference(name, steps=2)
    # (Adam divides by the root of a second moment that is near zero where a
    # gradient is: a last-place difference of two aggregates shows a hundredfold)
    tol = {"rtol": 2e-5, "atol": 1e-4 if name in ("adam", "adamw") else 2e-6}
    _assert_trees_close(params, want_params, **tol)
    _assert_trees_close(state, want_state, **tol)
    # the norm may differ by the order of a float32 sum, and by nothing else
    np.testing.assert_allclose(norms, want_norms, rtol=1e-5)


@pytest.mark.parametrize("name", sorted(ELEMENTWISE))
def test_the_rows_order_gives_the_bits_whole_leaves_gave(rows, monkeypatch, name):
    """Elementwise arithmetic on a view is the same arithmetic: parameters
    and state equal, bit for bit where the optimizer is the round's own or
    another of the SGD family, what the update on whole leaves gives (the
    path every other optimizer keeps); the norm to a float32 sum's order."""
    in_rows = _streamed(name, steps=2)
    monkeypatch.setattr(coordinatewise, "is_elementwise", lambda opt, params, state: False)
    whole = _streamed(name, steps=2)
    for got, want in zip(jax.tree_util.tree_leaves(in_rows[:2]),
                         jax.tree_util.tree_leaves(whole[:2]), strict=True):
        if name in ("adam", "adamw"):
            # this CPU compiler contracts Adam's multiply-adds one way in one
            # fusion and another way in another: a unit in the last place
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-7)
        else:
            np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(in_rows[2], whole[2], rtol=1e-6)


def test_which_path_a_step_takes_is_read_off_the_optimizer(monkeypatch):
    """The folded toy's step holds two ``optimization_barrier`` more a
    segment (over what is cut out of the row to be relaid, and over the tile
    views) exactly where the optimizer is elementwise."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    bundle = ModelBundle(apply_fn=None, params=_params(), segments=_segments())
    xs, ys = _batches(1)[0]

    def barriers(name):
        given = None if OPTIMIZERS[name] is None else coordinatewise.leafwise(OPTIMIZERS[name])
        step, state = build_ps_train_step(
            bundle, AGGREGATE, CFG, attack=SIGN_FLIP, optimizer=given)
        jaxpr = jax.make_jaxpr(lambda *a: step(*a))(
            bundle.params, state, xs, ys, jax.random.PRNGKey(0))
        return sum(eqn.primitive.name == "optimization_barrier" for eqn in jaxpr.jaxpr.eqns)

    whole_leaves = barriers("block_rms")
    for name in sorted(ELEMENTWISE):
        assert barriers(name) == whole_leaves + 2 * len(SHAPES), name


@pytest.mark.parametrize("name, elementwise", [
    ("sgd", True), ("momentum", True), ("nesterov", True), ("adam", True), ("adamw", True),
    ("rmsprop", True), ("lion", True),
    ("global_clip", False), ("block_rms", False), ("lars", False), ("adafactor", False),
])
def test_is_elementwise_reads_the_updates_own_jaxpr(name, elementwise):
    optimizers = {
        "sgd": optax.sgd(0.1), "momentum": optax.sgd(0.1, momentum=0.9),
        "nesterov": optax.sgd(0.1, momentum=0.9, nesterov=True), "adam": optax.adam(1e-3),
        "adamw": optax.adamw(1e-3, weight_decay=0.01), "rmsprop": optax.rmsprop(1e-3),
        "lion": optax.lion(1e-4),
        "global_clip": optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1)),
        "block_rms": OPTIMIZERS["block_rms"], "lars": optax.lars(0.1),
        "adafactor": optax.adafactor(1e-3),
    }
    params = {"w": jnp.zeros((256, 256)), "b": jnp.zeros((3,))}
    state = optimizers[name].init(params)
    assert coordinatewise.is_elementwise(optimizers[name], params, state) is elementwise
    # shapes serve as well as arrays
    shapes = jax.eval_shape(lambda: (params, state))
    assert coordinatewise.is_elementwise(optimizers[name], *shapes) is elementwise


def test_a_transpose_is_not_elementwise():
    """Same shape in and out, and still another place."""
    def update(grads, state, params=None):
        return jax.tree_util.tree_map(lambda g: g.T, grads), state

    assert coordinatewise.is_elementwise(
        coordinatewise.Leafwise(lambda params: (), update), {"w": jnp.zeros((8, 8))}, ()) is False


def test_a_segment_is_asked_on_its_own_shapes_and_on_their_tile_views(monkeypatch):
    """A factored second moment is kept for a leaf with two dimensions of 128
    and more, a whole one for smaller leaves: elementwise on the toy's first
    segment (16 x 256) and its head (24 x 5), not on the middle one (256 x 128)."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    bundle = ModelBundle(apply_fn=None, params=_params(), segments=_segments())
    asked = []
    is_elementwise = coordinatewise.is_elementwise

    def listen(opt, params, state):
        asked.append((jax.tree_util.tree_structure(params), is_elementwise(opt, params, state)))
        return asked[-1][1]

    monkeypatch.setattr(coordinatewise, "is_elementwise", listen)
    build_ps_train_step(bundle, AGGREGATE, CFG, attack=SIGN_FLIP,
                        optimizer=coordinatewise.leafwise(
                            optax.chain(optax.scale_by_factored_rms(), optax.scale(-0.1))))
    by_segment = {}
    for structure, answer in asked:
        by_segment.setdefault(structure.num_leaves, []).append(answer)
    # s0_in and s2_head have 2 leaves: each is asked on its leaves, then on its views
    assert by_segment[2] == [True, True, True, True]
    assert by_segment[4] == [False]  # s1_mid: the whole leaves already say no


TILE_SHAPES = [(16, 256), (8, 3, 8, 384), (256, 128), (2, 8, 128), (8, 128)]
NO_VIEW_SHAPES = [(128, 64), (12, 256), (1024,), (24, 5), (3,), (8, 1024, 2)]


@pytest.mark.parametrize("shape", TILE_SHAPES + NO_VIEW_SHAPES, ids=str)
def test_tile_view_and_back(shape):
    leaf = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    view = tile_view(leaf)
    if shape in TILE_SHAPES:
        assert view.shape == (leaf.size // 1024, 8, 128)
    else:
        assert view is leaf
    np.testing.assert_array_equal(leaf_view(view, shape), leaf)


@pytest.mark.parametrize("folded", [False, True], ids=["flat", "folded"])
def test_unravel_tiles_is_the_tile_view_of_unravel(folded):
    shapes = {"a": (16, 256), "b": (24, 5), "c": (256, 128), "d": (128, 64), "e": (3,),
              "f": (8, 16, 384)}
    key = jax.random.PRNGKey(3)
    tree = {k: jax.random.normal(jax.random.fold_in(key, i), s)
            for i, (k, s) in enumerate(shapes.items())}
    d = tree_size(tree)
    width = -(-d // 1024) * 1024 if folded else d + 5
    layout = row_layout(tree, width, folded=folded)
    if not folded:  # no leaf is whole tiles of a row that is not
        assert layout.unravel_tiles is None
        return
    row = layout.ravel(tree)
    got = layout.unravel_tiles(row)
    want = jax.tree_util.tree_map(tile_view, layout.unravel(row))
    for k in shapes:
        np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(leaf_view(got[k], shapes[k]), tree[k])
    # the leaves that are whole tiles come as slices of the folded row
    assert {k for k in shapes if got[k].shape != shapes[k]} == {"a", "c", "f"}
    a = layout.offsets[0] // 1024
    np.testing.assert_array_equal(got["a"], row.reshape(-1, 8, 128)[a:a + 4])
