"""The round that aggregates segment by segment (``parallel/ps.py``,
``_streamed_train_step``): equal to the ``(n, d)`` round on the same
segmented toy bundle for everything ``ops/coordinatewise.py`` lists,
refused for everything else, and absent from a bundle without segments
(whose step lowers to the text it had before the streamed round came)."""

from __future__ import annotations

import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byzpy_tpu.models.bundle import ModelBundle, Segment, chain_loss
from byzpy_tpu.ops import attack_ops, coordinatewise, robust
from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

N, B, BATCH, WIDTH, CLASSES = 8, 2, 16, 24, 5


def _segments():
    def dense(p, x):
        return jnp.tanh(x @ p["kernel"] + p["bias"])

    def counted(p, x):
        y = dense(p, x)
        return y, {"positive": jnp.sum(y > 0)}

    def head(p, x, y):
        logits = x @ p["kernel"] + p["bias"]
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    return (Segment("s0_in", dense), Segment("s1_mid", counted, aux=True),
            Segment("s2_mid", dense), Segment("s3_head", head))


def _params(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    sizes = [(12, WIDTH), (WIDTH, WIDTH), (WIDTH, 40), (40, CLASSES)]
    names = ["s0_in", "s1_mid", "s2_mid", "s3_head"]
    return {
        name: {"kernel": jax.random.normal(k, size) / np.sqrt(size[0]),
               "bias": jnp.full((size[1],), 0.01)}
        for name, k, size in zip(names, keys, sizes)
    }


def _batches(seed=1, steps=2):
    k = jax.random.PRNGKey(seed)
    out = []
    for s in range(steps):
        kx, ky, k = jax.random.split(k, 3)
        out.append((jax.random.normal(kx, (N, BATCH, 12)),
                    jax.random.randint(ky, (N, BATCH), 0, CLASSES)))
    return out


def _bundles():
    segs = _segments()
    streamed = ModelBundle(apply_fn=None, params=_params(), segments=segs)
    whole = ModelBundle(apply_fn=None, params=_params(), loss_fn=chain_loss(segs))
    return streamed, whole


SIGN_FLIP = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
EMPIRE = coordinatewise.RoundAttack(attack_ops.empire, kwargs={"scale": -1.5})

AGGREGATES = {
    "trimmed": partial(robust.trimmed_mean, f=2),
    "median": robust.coordinate_median,
    "meamed": partial(robust.mean_of_medians, f=2),
    "mean": coordinatewise.mean,
}
ATTACKS = {"signflip": (B, SIGN_FLIP), "empire": (B, EMPIRE), "echo": (B, None), "none": (0, None)}


@pytest.mark.parametrize("attack", sorted(ATTACKS))
@pytest.mark.parametrize("agg", sorted(AGGREGATES))
def test_streamed_round_equals_the_n_by_d_round(agg, attack):
    b, attack_fn = ATTACKS[attack]
    cfg = PSStepConfig(n_nodes=N, n_byzantine=b, learning_rate=0.1, momentum=0.9)
    streamed, whole = _bundles()
    results = []
    for bundle in (streamed, whole):
        step, opt = build_ps_train_step(bundle, AGGREGATES[agg], cfg, attack=attack_fn)
        step = jax.jit(step)
        params, seen = bundle.params, []
        for i, (xs, ys) in enumerate(_batches()):
            params, opt, metrics = step(params, opt, xs, ys, jax.random.PRNGKey(i))
            seen.append(metrics)
        results.append((params, opt, seen))
    (p_s, o_s, m_s), (p_w, o_w, m_w) = results
    for got, want in zip(jax.tree_util.tree_leaves(p_s), jax.tree_util.tree_leaves(p_w)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the momentum trace mirrors the parameter tree in both, leaf for leaf
    for got, want in zip(jax.tree_util.tree_leaves(o_s), jax.tree_util.tree_leaves(o_w)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for got, want in zip(m_s, m_w):
        np.testing.assert_allclose(got["honest_loss"], want["honest_loss"], rtol=1e-6)
        np.testing.assert_allclose(got["agg_grad_norm"], want["agg_grad_norm"], rtol=1e-5)
        assert set(want) == {"honest_loss", "agg_grad_norm"}
        assert got["segment_aux"]["s1_mid"]["positive"].shape == (N - b,)


# -- boundaries that are trees ----------------------------------------------


def _tree_segments():
    """A chain whose boundaries are trees, as a model with a second loss term
    needs them: the first link's output is handed on beside the stream (the
    very array, untouched) to a side link that reads it again, and the head
    applies its one matrix to both streams and reports both terms."""
    def dense(p, x):
        return jnp.tanh(x @ p["kernel"] + p["bias"])

    def start(p, e):  # an array in, the pair (stream, e) out
        y = dense(p, e)
        return (y, e), {"positive": jnp.sum(y > 0)}

    def middle(p, pair):
        h, e = pair
        return {"stream": dense(p, h), "kept": e}  # a dict is a tree too

    def side(p, tree):  # hands the stream on untouched and reads both
        h, e = tree["stream"], tree["kept"]
        return h, dense(p, jnp.concatenate([h, jnp.roll(e, -1, axis=0)], axis=-1))

    def head(p, pair, y):
        def term(x):
            logits = x @ p["kernel"] + p["bias"]
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        first, second = term(pair[0]), term(pair[1])
        return first + 0.3 * second, {"first": first, "second": second}

    return (Segment("t0_in", dense), Segment("t1_start", start, aux=True),
            Segment("t2_mid", middle), Segment("t3_side", side),
            Segment("t4_head", head, aux=True))


def _tree_params(seed=0):
    sizes = {"t0_in": (12, WIDTH), "t1_start": (WIDTH, WIDTH), "t2_mid": (WIDTH, 40),
             "t3_side": (40 + WIDTH, 40), "t4_head": (40, CLASSES)}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(sizes))
    return {name: {"kernel": jax.random.normal(k, size) / np.sqrt(size[0]),
                   "bias": jnp.full((size[1],), 0.01)}
            for (name, size), k in zip(sizes.items(), keys)}


@pytest.mark.parametrize("attack", ["signflip", "none"])
@pytest.mark.parametrize("agg", ["trimmed", "median", "mean"])
def test_streamed_round_with_tree_boundaries_equals_the_n_by_d_round(agg, attack):
    b, attack_fn = ATTACKS[attack]
    cfg = PSStepConfig(n_nodes=N, n_byzantine=b, learning_rate=0.1, momentum=0.9)
    segs = _tree_segments()
    results = []
    for bundle in (ModelBundle(apply_fn=None, params=_tree_params(), segments=segs),
                   ModelBundle(apply_fn=None, params=_tree_params(), loss_fn=chain_loss(segs))):
        step, opt = build_ps_train_step(bundle, AGGREGATES[agg], cfg, attack=attack_fn)
        step = jax.jit(step)
        params, seen = bundle.params, []
        for i, (xs, ys) in enumerate(_batches()):
            params, opt, metrics = step(params, opt, xs, ys, jax.random.PRNGKey(i))
            seen.append(metrics)
        results.append((params, opt, seen))
    (p_s, o_s, m_s), (p_w, o_w, m_w) = results
    for got, want in zip(jax.tree_util.tree_leaves((p_s, o_s)),
                         jax.tree_util.tree_leaves((p_w, o_w))):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for got, want in zip(m_s, m_w):
        np.testing.assert_allclose(got["honest_loss"], want["honest_loss"], rtol=1e-6)
        np.testing.assert_allclose(got["agg_grad_norm"], want["agg_grad_norm"], rtol=1e-5)
        terms = got["segment_aux"]["t4_head"]
        assert terms["first"].shape == terms["second"].shape == (N - b,)
        np.testing.assert_allclose(jnp.mean(terms["first"] + 0.3 * terms["second"]),
                                   got["honest_loss"], rtol=1e-6)
        assert got["segment_aux"]["t1_start"]["positive"].shape == (N - b,)


def test_an_array_handed_on_untouched_is_kept_once():
    """The first link's output rides beside the stream through two more
    links: the step keeps it in ONE stack of h rows, not one a boundary."""
    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    bundle = ModelBundle(apply_fn=None, params=_tree_params(), segments=_tree_segments())
    step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP)
    xs, ys = _batches()[0]
    jaxpr = jax.make_jaxpr(step)(bundle.params, opt, xs, ys, jax.random.PRNGKey(0))
    forward = next(eqn for eqn in jaxpr.eqns if eqn.primitive.name in ("while", "scan"))
    kept = [v.aval.shape for v in forward.outvars if len(v.aval.shape) > 1
            and v.aval.shape[0] == N - B and v.aval.dtype == jnp.float32]
    # e (BATCH x WIDTH), the three streams after it (WIDTH, 40, 40): four stacks
    sizes = sorted(int(np.prod(shape[1:])) for shape in kept)
    assert sizes == sorted([BATCH * WIDTH, BATCH * WIDTH, BATCH * 40, BATCH * 40])


def test_a_boundary_that_holds_one_array_twice_is_refused():
    def twice(p, x):
        y = jnp.tanh(x @ p["kernel"] + p["bias"])
        return y, y

    segs = (Segment("s0_in", twice),) + _segments()[1:]
    bundle = ModelBundle(apply_fn=None, params=_params(), segments=segs)
    step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"],
                                    PSStepConfig(n_nodes=N, n_byzantine=B), attack=SIGN_FLIP)
    xs, ys = _batches()[0]
    with pytest.raises(ValueError, match="one array twice"):
        jax.make_jaxpr(step)(bundle.params, opt, xs, ys, jax.random.PRNGKey(0))


def test_streamed_round_with_an_optimizer_marked_leafwise_equals_the_n_by_d_round():
    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    streamed, whole = _bundles()
    adam = optax.adam(1e-2)
    got, want = [], []
    for bundle, optimizer, out in ((streamed, coordinatewise.leafwise(adam), got),
                                   (whole, adam, want)):
        step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP,
                                        optimizer=optimizer)
        params = bundle.params
        for i, (xs, ys) in enumerate(_batches()):
            params, opt, _ = jax.jit(step)(params, opt, xs, ys, jax.random.PRNGKey(i))
        out.extend(jax.tree_util.tree_leaves(params))
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, rtol=2e-5, atol=2e-6)


def test_streamed_step_holds_no_array_of_n_rows_wider_than_a_segment():
    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    streamed, _ = _bundles()
    step, opt = build_ps_train_step(streamed, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP)
    xs, ys = _batches()[0]
    text = jax.jit(step).lower(streamed.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()
    widest = max(sum(leaf.size for leaf in jax.tree_util.tree_leaves(sub))
                 for sub in streamed.params.values())
    widths = [int(w) for w in re.findall(r"tensor<%dx(\d+)xf32>" % N, text)]
    assert widths and max(widths) == widest


@pytest.mark.parametrize("what,kwargs", [
    ("aggregate", {"aggregate": partial(robust.multi_krum, f=2, q=4)}),
    ("aggregate", {"aggregate": lambda x: jnp.mean(x, axis=0)}),
    ("attack", {"attack": lambda honest, key: -jnp.mean(honest, axis=0)}),
    ("optimizer", {"optimizer": optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1))}),
    ("pre_aggregate", {"pre_aggregate": lambda m: m}),
    ("sharded_update", {"sharded_update": "on"}),
])
def test_what_the_table_does_not_list_is_refused_by_name(what, kwargs):
    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    streamed, _ = _bundles()
    kwargs = {"aggregate": AGGREGATES["trimmed"], **kwargs}
    aggregate = kwargs.pop("aggregate")
    with pytest.raises(ValueError, match=r"ops/coordinatewise\.py") as err:
        build_ps_train_step(streamed, aggregate, cfg, **kwargs)
    assert what in str(err.value)


@pytest.mark.parametrize("optimizer", [
    optax.sgd(0.1, momentum=0.9), optax.adam(1e-3), optax.adamw(1e-3),
    optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3)), optax.lamb(1e-3),
])
def test_an_optimizer_streams_only_where_its_caller_marks_it_leafwise(optimizer):
    """Nothing looks inside an optimizer: unmarked it is refused whatever it
    is made of, marked it is taken at the caller's word."""
    assert "optimizer" in coordinatewise.refusal(AGGREGATES["trimmed"], None, optimizer)
    assert not coordinatewise.refusal(
        AGGREGATES["trimmed"], None, coordinatewise.leafwise(optimizer))


def test_a_segmented_bundle_on_a_mesh_runs_the_n_by_d_program():
    from byzpy_tpu.parallel.mesh import node_mesh

    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    streamed, whole = _bundles()
    mesh = node_mesh(4)
    xs, ys = _batches()[0]
    texts = []
    for bundle in (streamed, whole):
        step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP,
                                        mesh=mesh)
        texts.append(_canonical(jax.jit(step).lower(
            bundle.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()))
    assert texts[0] == texts[1]


def test_a_segmented_bundle_checks_its_keys():
    segs = _segments()
    params = _params()
    params["extra"] = params.pop("s2_mid")
    with pytest.raises(ValueError, match="keyed by its segments"):
        ModelBundle(apply_fn=None, params=params, segments=segs)


# -- a bundle without segments runs the program it ran before ---------------

_LOC = re.compile(r"\s*loc\([^\n]*\)|#loc[^\n]*\n")


def _canonical(text: str) -> str:
    return _LOC.sub("", text)


def _unsegmented_texts():
    """The lowered step of two toy rounds of a bundle without segments
    (the sort family under sign flip; Multi-Krum under Empire)."""
    def dense(p, x):
        return jnp.tanh(x @ p["kernel"] + p["bias"])

    def loss_fn(params, x, y):
        h = dense(params["s0_in"], x)
        h = dense(params["s1_mid"], h)
        h = dense(params["s2_mid"], h)
        logits = h @ params["s3_head"]["kernel"] + params["s3_head"]["bias"]
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    out = {}
    for name, aggregate, attack in (
        ("trimmed-signflip", partial(robust.trimmed_mean, f=2),
         lambda honest, key: attack_ops.sign_flip(jnp.mean(honest, axis=0))),
        ("krum-empire", partial(robust.multi_krum, f=2, q=4),
         lambda honest, key: attack_ops.empire(honest)),
    ):
        bundle = ModelBundle(apply_fn=None, params=_params(), loss_fn=loss_fn)
        cfg = PSStepConfig(n_nodes=N, n_byzantine=B, learning_rate=0.005)
        step, opt = build_ps_train_step(bundle, aggregate, cfg, attack=attack)
        xs, ys = _batches()[0]
        text = jax.jit(step, donate_argnums=(0, 1)).lower(
            bundle.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()
        out[name] = hashlib.sha256(_canonical(text).encode()).hexdigest()
    return out


# sha256 of the canonical lowered text, taken on the commit before the
# streamed round (7fbda43) with this file's own function. A PR that changes
# the (n, d) step on purpose records its own.
PARENT_TEXTS = {
    "trimmed-signflip": "0edfed7ebe605b91f5f4cdf66d3aa6f14196a7bfb470f443bbb01b61681fe14e",
    "krum-empire": "426b43f45f7b242f3ddae42123198b70a50d7975092c8ecbe01474f23cdfcab8",
}


@pytest.mark.parametrize("cell", sorted(PARENT_TEXTS))
def test_a_bundle_without_segments_lowers_to_the_text_it_had(cell):
    assert _unsegmented_texts()[cell] == PARENT_TEXTS[cell]


# -- a bundle whose boundaries are single arrays streams the program it streamed ----

def _streamed_texts():
    """The streamed step of the toy segmented bundle and of a toy Nemotron-H
    bundle (every boundary one array), lowered."""
    from byzpy_tpu.models import nemotron_h as nh

    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    out = {}
    streamed, _ = _bundles()
    step, opt = build_ps_train_step(streamed, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP)
    xs, ys = _batches()[0]
    out["toy-segments"] = jax.jit(step).lower(
        streamed.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()
    bundle = nh.nemotron_h_bundle(_toy_nemotron(), seed=0)
    step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP)
    tokens = jnp.zeros((N, 1, 19), jnp.int32)
    out["toy-nemotron"] = jax.jit(step).lower(
        bundle.params, opt, tokens, tokens, jax.random.PRNGKey(1)).as_text()
    return {name: hashlib.sha256(_canonical(text).encode()).hexdigest()
            for name, text in out.items()}


# sha256 of the canonical lowered text, taken on the commit before boundaries
# became trees (c854b01) with this file's own function. "toy-nemotron" was
# taken again at PR 35, which changed one thing in the expert layer's text and
# nothing else: the combine has a backward of its own (a gather where
# automatic differentiation put a scatter-add, and the mask of filled slots
# it goes by); ``tests/test_held_experts_combine.py`` holds the two equal.
# And again at PR 37 (191ff157... before it), which changed one thing in the
# Mamba-2 mixer's text and nothing else: ``nemotron_h.conv_silu``, the
# convolution and its SiLU with a backward of their own, handing out the three
# column blocks; ``tests/test_nemotron_h.py`` holds it to the plain formula.
# And again at PR 39 (38fcba49... before it; with the parent's
# ``parallel/moe.py`` under this PR's tree it still holds, so moving
# ``conv_silu`` to ``models/layers.py`` changed nothing), which changed the
# expert layer's text alone: a round places its readers by flat index and, where
# that at least halves the columns, reads back by a token's picks;
# ``tests/test_held_experts_combine.py`` holds the layer to the one it was.
# And again at PR 40 (0914aa04... before it), which changed the expert layer's
# text alone: a round's rows go back to the tokens by one read a column in
# float32, as the combine's forward and as the dispatch gather's own backward
# (no ``(T, k, D)`` array, no scatter-add of rows), a column a pick in every
# model; ``tests/test_held_experts_combine.py`` holds it to the one it was.
PARENT_STREAMED_TEXTS = {
    "toy-segments": "fef5d5b8f8531f18c4a22b78b0228563705fa3cc64282f66187af19329f058ed",
    "toy-nemotron": "096a8c43ea20e23734bdcfdae98cae66626b4f3cb089f09496dc149ac04cd2cc",
}


# PR 42 changed ``round.update`` alone: an elementwise optimizer (the default
# is) updates a segment whose row is whole tiles in the row's own order. The
# toy segments' rows are no whole tiles, so that text stands; the toy
# Nemotron's embedding and head segments are, and with the optimizer sent down
# the other path (whole leaves, as before) its text is still the parent's.
ROW_ORDER_STREAMED_TEXTS = {
    "toy-segments": PARENT_STREAMED_TEXTS["toy-segments"],
    "toy-nemotron": "fa69c81d10845f6b28e2fbed62ef6ac4ba7a7e5bd1dc82dd983340b6205322bb",
}


@pytest.mark.parametrize("cell", sorted(PARENT_STREAMED_TEXTS))
def test_single_array_boundaries_stream_the_text_they_streamed(monkeypatch, cell):
    monkeypatch.setattr(coordinatewise, "is_elementwise", lambda opt, params, state: False)
    assert _streamed_texts()[cell] == PARENT_STREAMED_TEXTS[cell]


@pytest.mark.parametrize("cell", sorted(ROW_ORDER_STREAMED_TEXTS))
def test_single_array_boundaries_stream_the_text_of_the_rows_order(cell):
    assert _streamed_texts()[cell] == ROW_ORDER_STREAMED_TEXTS[cell]


# -- the byzantine rows formed in the sort kernel's body (PR 43) --------------

MIMIC = coordinatewise.RoundAttack(attack_ops.mimic, kwargs={"epsilon": 1})
LITTLE = coordinatewise.RoundAttack(attack_ops.little, kwargs={"f": B, "n_total": N})


class Keyed(coordinatewise.RoundAttack):
    """A round attack that does read the key (which side the flip goes)."""

    def __call__(self, honest, key):
        return super().__call__(honest, key) * jax.random.rademacher(key, (), jnp.float32)


def _kernel_calls(jaxpr, found=None):
    """The sort family's pallas_calls under ``jaxpr``, by name -> how many."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            if name.startswith("sorted_reduce"):
                found[name] = found.get(name, 0) + 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _kernel_calls(inner, found)
    return found


def _stepped(aggregate, attack, b, rounds=3):
    cfg = PSStepConfig(n_nodes=N, n_byzantine=b, learning_rate=0.1, momentum=0.9)
    streamed, _ = _bundles()
    step, opt = build_ps_train_step(streamed, aggregate, cfg, attack=attack)
    xs, ys = _batches()[0]
    calls = _kernel_calls(jax.make_jaxpr(step)(
        streamed.params, opt, xs, ys, jax.random.PRNGKey(0)).jaxpr)
    step = jax.jit(step)
    params, norms = streamed.params, []
    for i, (xs, ys) in enumerate(_batches(steps=rounds)):
        params, opt, metrics = step(params, opt, xs, ys, jax.random.PRNGKey(i))
        norms.append(metrics["agg_grad_norm"])
    return calls, params, opt, norms


@pytest.mark.parametrize("attack", ["signflip", "empire", "mimic"])
@pytest.mark.parametrize("agg", ["trimmed", "median"])
def test_rows_formed_in_the_kernel_step_the_round_that_writes_them(monkeypatch, agg, attack):
    """Kernels forced (interpreted here): the step whose stacks hold h rows and
    whose one call a segment forms the other b, against today's three sweeps
    (the table's second set emptied), over three rounds. A mean formed in
    another order of addition moves a row by an ulp or two and the aggregate by
    no more (``tests/test_pallas_kernels.py`` has the bound), so the two are
    held as close as the streamed round is held to the (n, d) round above."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    attack_fn = {"signflip": SIGN_FLIP, "empire": EMPIRE, "mimic": MIMIC}[attack]
    calls, *formed = _stepped(AGGREGATES[agg], attack_fn, B)
    assert calls == {"sorted_reduce_stream_attacked": len(_segments())}
    monkeypatch.setattr(coordinatewise, "KERNEL_FORMED_ATTACKS", frozenset())
    calls, *written = _stepped(AGGREGATES[agg], attack_fn, B)
    assert calls == {"sorted_reduce_stream": len(_segments())}
    for got, want in zip(jax.tree_util.tree_leaves(formed[:2]),
                         jax.tree_util.tree_leaves(written[:2])):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(formed[2], written[2], rtol=1e-5)


def test_the_step_that_forms_its_rows_holds_no_array_of_n_rows(monkeypatch):
    """Lowered and compiled: h rows of a segment's width, folded, and nothing
    with a leading n of that width, folded or flat; no op under
    ``round.build_matrix``."""
    monkeypatch.setenv("BYZPY_TPU_PALLAS", "1")
    cfg = PSStepConfig(n_nodes=N, n_byzantine=B)
    streamed, _ = _bundles()
    step, opt = build_ps_train_step(streamed, AGGREGATES["trimmed"], cfg, attack=SIGN_FLIP)
    xs, ys = _batches()[0]
    lowered = jax.jit(step).lower(streamed.params, opt, xs, ys, jax.random.PRNGKey(0))
    text = lowered.as_text()
    assert f"tensor<{N - B}x128x128xf32>" in text
    assert not re.search(r"tensor<(1x)?%dx(128x128|16384)xf32>" % N, text)
    compiled = lowered.compile().as_text()
    assert re.search(r"f32\[%d,128,128\]" % (N - B), compiled)
    assert not re.search(r"f32\[(1,)?%d,(128,128|16384)\]" % N, compiled)
    assert "round.build_matrix" not in compiled and "round.aggregate" in compiled


def _written_texts():
    """The streamed toy step with the kernels forced, lowered, for each way the
    round keeps writing its byzantine rows: an attack the table's second set
    does not name, one that reads the key, no byzantine worker, and the two
    aggregates the sort kernel does not serve."""
    streamed, _ = _bundles()
    xs, ys = _batches()[0]
    out = {}
    for name, agg, attack, b in (
        ("little", "trimmed", LITTLE, B),
        ("keyed", "trimmed", Keyed(attack_ops.sign_flip, of="honest_mean"), B),
        ("echo", "trimmed", None, B),
        ("b0", "trimmed", None, 0),
        ("mean", "mean", SIGN_FLIP, B),
        ("meamed", "meamed", SIGN_FLIP, B),
    ):
        step, opt = build_ps_train_step(
            streamed, AGGREGATES[agg], PSStepConfig(n_nodes=N, n_byzantine=b), attack=attack)
        text = jax.jit(step).lower(streamed.params, opt, xs, ys, jax.random.PRNGKey(0)).as_text()
        out[name] = hashlib.sha256(_canonical(text).encode()).hexdigest()
    return out


# sha256 of the canonical lowered text, taken on the commit before the kernel
# could form rows (6612e71) with this file's own function under
# BYZPY_TPU_PALLAS=1: what the new form does not serve is the round it was.
PARENT_WRITTEN_TEXTS = {
    "little": "b7f0424a47a680efded6007147a436d0852b67d0f087bf8cbee819caf5594a89",
    "keyed": "e173199835e2bb903f1eeaa034f6e2f30aeef80ade3ee6b3ed367d9143a21ca2",
    "echo": "51c8a85dc31c4a91962da3cfde2c3673c6966d4df19a482bf0739f364ffeb1b3",
    "b0": "b02096c455bfe2400b8ea09bd3b4491c5bc00be8554398f96761baefff72e86a",
    "mean": "348dbed3646d169438e155cde98f51efcb0c5253c369c8756797ff2478f8f086",
    "meamed": "575782b1355a0853b7cd4947a3b0920cfaf9b8a19d51ae4e9fcec128df66ab18",
}


@pytest.fixture(scope="module")
def written_texts():
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("BYZPY_TPU_PALLAS", "1")
        return _written_texts()


@pytest.mark.parametrize("case", sorted(PARENT_WRITTEN_TEXTS))
def test_what_the_kernel_does_not_form_is_written_as_it_was(written_texts, case):
    assert written_texts[case] == PARENT_WRITTEN_TEXTS[case]


# -- the streamed round's scopes: catalogued, held by byzlint, in the text ---

_STREAM_SCOPES = ["round.segment_fwd", "round.segment_recompute", "round.segment_bwd",
                  "round.fwdbwd", "round.build_matrix", "round.aggregate", "round.update"]
_MODEL_SCOPES = ["model.ssm_scan", "model.attention", "model.moe_route", "model.moe_experts"]


def _toy_nemotron():
    from byzpy_tpu.models import nemotron_h as nh

    return nh.NemotronHConfig(
        hidden_size=32, pattern="M*E", vocab_size=64, mamba_num_heads=4, mamba_head_dim=8,
        ssm_state_size=16, n_groups=2, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, query_block=8, n_routed_experts=16,
        num_experts_per_tok=3, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, held_experts=(4, 4))


@pytest.fixture(scope="module")
def streamed_op_names():
    from byzpy_tpu.models import nemotron_h as nh

    bundle = nh.nemotron_h_bundle(_toy_nemotron(), seed=0)
    step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"],
                                    PSStepConfig(n_nodes=N, n_byzantine=B), attack=SIGN_FLIP)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (N, 1, 19), 0, 64)
    text = jax.jit(step).lower(bundle.params, opt, tokens, tokens,
                               jax.random.PRNGKey(1)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", _STREAM_SCOPES + _MODEL_SCOPES)
def test_streamed_step_holds_each_scope_and_the_catalog_lists_it(streamed_op_names, scope):
    from byzpy_tpu.observability import catalog

    assert scope in catalog.SCOPES
    assert any(f"/{scope}/" in name + "/" or f"({scope})" in name or f"({scope}/" in name
               for name in streamed_op_names)


@pytest.fixture(scope="module")
def tree_streamed_op_names():
    """The streamed step of a toy GLM-4.7-Flash bundle (tree boundaries)."""
    from byzpy_tpu.models import glm4_moe_lite as glm

    bundle = glm.glm47_flash_ep8(
        0, hidden_size=32, num_hidden_layers=2, vocab_size=64, num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=12, qk_nope_head_dim=6, qk_rope_head_dim=2, v_head_dim=8,
        query_block=8, intermediate_size=48, n_routed_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=24, held_experts=(4, 4))
    step, opt = build_ps_train_step(bundle, AGGREGATES["trimmed"],
                                    PSStepConfig(n_nodes=N, n_byzantine=B), attack=SIGN_FLIP)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (N, 1, 19), 0, 64)
    text = jax.jit(step).lower(bundle.params, opt, tokens, tokens,
                               jax.random.PRNGKey(1)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", ["model.attention", "model.mla_latent", "model.mtp",
                                   "model.moe_route", "model.moe_experts"])
def test_tree_streamed_step_holds_each_model_scope_in_all_three_passes(
        tree_streamed_op_names, scope):
    from byzpy_tpu.observability import catalog

    assert scope in catalog.SCOPES
    for a_pass in ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd"):
        assert any(a_pass in name and scope in name for name in tree_streamed_op_names), a_pass
    # the latent's scope stands inside attention's
    if scope == "model.mla_latent":
        assert all("model.attention" in name for name in tree_streamed_op_names
                   if "model.mla_latent" in name)


def test_round_fwdbwd_is_the_innermost_round_scope_of_every_pass(streamed_op_names):
    # chipbench/scope_join.py labels an op by the LAST round.* segment of its
    # path: the accepted readers of round.fwdbwd read the streamed round so
    innermost = re.compile(r"round\.[A-Za-z0-9_]+")
    passes = [name for name in streamed_op_names if "round.segment_" in name]
    assert passes
    assert {innermost.findall(name)[-1] for name in passes} == {"round.fwdbwd"}


def test_byzlint_metric_contract_is_silent_on_the_streamed_rounds_modules():
    import os

    from byzpy_tpu.analysis import scan_paths
    from byzpy_tpu.analysis.rules import METRIC_CONTRACT

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(root, "byzpy_tpu", *parts) for parts in (
        ("parallel", "ps.py"), ("parallel", "moe.py"), ("models", "nemotron_h.py"),
        ("models", "glm4_moe_lite.py"), ("models", "layers.py"), ("ops", "coordinatewise.py"))]
    result = scan_paths(paths, select=[METRIC_CONTRACT])
    assert [f.message for f in result.findings if f.rule == METRIC_CONTRACT] == []
