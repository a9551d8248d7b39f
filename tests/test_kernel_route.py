"""One route to a kernel.

``pallas_kernels.pallas_serves`` is the only place that decides whether
the Pallas route serves an array, and a wrapper's tile is its ``tile=``
argument or its family's heuristic. Held here:

* the gate's table, condition by condition, against an oracle written
  from the conditions' definitions;
* every public entry point that has a kernel takes the gate's answer:
  its jaxpr holds a ``pallas_call`` exactly when the gate says so, one
  input on each side of the floor, with no variable set on a (pretended)
  TPU, and the answer is a fact of the call, never of an earlier trace;
* the tile every wrapper resolves at the benchmark's shapes divides
  ``aligned_width``, so a matrix allocated at that width is read in
  place;
* the ``BYZPY_TPU_*`` names the package mentions are the rows of
  ``docs/performance.md``'s table, no more and no fewer.

Nothing here runs a kernel: the gate reads types (``ShapeDtypeStruct``),
``jax.make_jaxpr`` traces, and tiles are caught at the jitted call.
"""

from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from byzpy_tpu.ops import pallas_kernels as pk
from byzpy_tpu.ops import preagg, robust
from byzpy_tpu.ops.attack_ops import sign_flip as _SIGN_FLIP
from byzpy_tpu.ops.coordinatewise import RoundAttack as _ROUND_ATTACK
from byzpy_tpu.parallel import quantization as qz
from byzpy_tpu.pre_aggregators import NearestNeighborMixing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOOR, MEAMED_FLOOR, MEAMED_CAP = pk.MIN_PALLAS_DIM, pk.MEAMED_MIN_DIM, pk.MEAMED_MAX_DIM


@pytest.fixture
def backend(monkeypatch):
    """``backend("tpu" | "cpu", flag)``: what ``_on_tpu`` answers and what
    ``BYZPY_TPU_PALLAS`` holds (``None``: unset)."""

    def choose(platform, flag=None):
        monkeypatch.setattr(pk, "_on_tpu", lambda: platform == "tpu")
        if flag is None:
            monkeypatch.delenv("BYZPY_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("BYZPY_TPU_PALLAS", flag)

    return choose


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# (i) the gate's table
# ---------------------------------------------------------------------------


def _oracle(shape, dtype, *, platform, flag, stream=False, min_dim=FLOOR, max_dim=None):
    """The gate's conditions, written out from their definitions."""
    if len(shape) != (3 if stream else 2):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype("float32"), jnp.dtype("bfloat16"), jnp.dtype("float16")):
        return False
    n, d = shape[-2:]
    if flag == "0" or n > 128:
        return False
    if flag != "1" and not (platform == "tpu" and d >= min_dim):
        return False
    return max_dim is None or d <= max_dim


@pytest.mark.parametrize("flag", [None, "0", "1"], ids=["unset", "0", "1"])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("n", [128, 129])
@pytest.mark.parametrize(
    "d, floors",
    [(FLOOR - 1, {}), (FLOOR, {}),
     (MEAMED_FLOOR - 1, {"min_dim": MEAMED_FLOOR, "max_dim": MEAMED_CAP}),
     (MEAMED_FLOOR, {"min_dim": MEAMED_FLOOR, "max_dim": MEAMED_CAP}),
     (MEAMED_CAP + 1, {"min_dim": MEAMED_FLOOR, "max_dim": MEAMED_CAP})],
    ids=["under-floor", "at-floor", "under-meamed-floor", "at-meamed-floor", "over-meamed-cap"],
)
def test_gate_floor_rows_backend_and_switch(backend, flag, platform, n, d, floors):
    backend(platform, flag)
    want = _oracle((n, d), jnp.float32, platform=platform, flag=flag, **floors)
    assert pk.pallas_serves(_sds((n, d)), **floors) is want
    # the generic floor is the default, and aligned_width follows the gate
    if not floors:
        assert pk.use_pallas_for(n, d) is want
        assert pk.aligned_width(n, d) == (-(-d // 16384) * 16384 if want else d)


@pytest.mark.parametrize("stream", [False, True], ids=["matrix", "stream"])
@pytest.mark.parametrize("rank", [1, 2, 3, 4])
def test_gate_rank(backend, rank, stream):
    backend("tpu")
    shape = (2, 2, 8, FLOOR)[4 - rank:]
    want = _oracle(shape, jnp.float32, platform="tpu", flag=None, stream=stream)
    assert want is (rank == (3 if stream else 2))
    assert pk.pallas_serves(_sds(shape), stream=stream) is want


@pytest.mark.parametrize(
    "dtype", ["float32", "bfloat16", "float16", "float64", "int32", "float8_e4m3fn"])
@pytest.mark.parametrize("flag", [None, "1"], ids=["unset", "1"])
def test_gate_dtype(backend, dtype, flag):
    """Forcing the route does not widen the dtypes: the kernels take
    32-bit and 16-bit floats, nothing else."""
    backend("tpu", flag)
    want = _oracle((8, FLOOR), dtype, platform="tpu", flag=flag)
    assert want is (dtype in ("float32", "bfloat16", "float16"))
    assert pk.pallas_serves(_sds((8, FLOOR), jnp.dtype(dtype))) is want


def _asked_while_tracing(fn_of_tracer, x):
    """The gate's answer for the tracer ``fn_of_tracer`` hands it."""
    seen = []

    def ask(a):
        seen.append(pk.pallas_serves(a))
        return a

    jax.make_jaxpr(fn_of_tracer(ask))(x)
    (answer,) = seen
    return answer


@pytest.mark.parametrize(
    "mesh_kind, want",
    [("none", True), ("auto", False), ("manual", True), ("explicit-replicated", True),
     ("explicit-sharded", False), ("one-device", True)],
)
def test_gate_mesh(backend, devices, mesh_kind, want):
    """A device-sharded operand stays on XLA (a ``pallas_call`` there
    gathers the whole matrix onto every chip): no mesh, one device, a
    ``shard_map`` body's local block and an explicitly replicated operand
    pass; an Auto mesh hides the spec and a sharded spec says no."""
    backend("cpu", "1")
    x = jnp.ones((8, 1024), jnp.float32)
    if mesh_kind == "none":
        assert pk.pallas_serves(x) is want
        assert _asked_while_tracing(lambda ask: ask, x) is want
        return
    if mesh_kind == "one-device":
        mesh = jax.make_mesh((1,), ("feat",), axis_types=(AxisType.Auto,), devices=devices[:1])
        placed = jax.device_put(x, NamedSharding(mesh, P(None, "feat")))
        assert _asked_while_tracing(lambda ask: ask, placed) is want
        return
    explicit = mesh_kind.startswith("explicit")
    mesh = jax.make_mesh((8,), ("feat",), devices=devices[:8],
                         axis_types=(AxisType.Explicit if explicit else AxisType.Auto,))
    spec = P(None, None) if mesh_kind == "explicit-replicated" else P(None, "feat")
    placed = jax.device_put(x, NamedSharding(mesh, spec))
    if mesh_kind == "manual":
        def body(ask):
            return jax.shard_map(ask, mesh=mesh, in_specs=spec, out_specs=spec)

        assert _asked_while_tracing(body, placed) is want
    elif explicit:
        with jax.set_mesh(mesh):
            assert pk.pallas_serves(placed) is want
            assert _asked_while_tracing(lambda ask: ask, placed) is want
    else:
        assert pk.pallas_serves(placed) is want
        assert _asked_while_tracing(lambda ask: ask, placed) is want


def test_targets_tpu_is_the_backend_question(backend):
    backend("tpu", "0")  # the switch is about the kernels, not the backend
    assert pk.targets_tpu() is True
    backend("cpu", "1")
    assert pk.targets_tpu() is False


# ---------------------------------------------------------------------------
# (ii) every public entry point takes the gate's answer
# ---------------------------------------------------------------------------

N = 8
_NNM = NearestNeighborMixing(f=2)

_MEAMED = {"min_dim": MEAMED_FLOOR, "max_dim": MEAMED_CAP}

#: name -> (function of one array, stream?, the floors its gate is asked
#: with, the floors a second ask on each round's matrix is made with)
ENTRY_POINTS = {
    "coordinate_median": (robust.coordinate_median, False, {}),
    "trimmed_mean": (partial(robust.trimmed_mean, f=2), False, {}),
    # past its cap MeaMed sorts through the network where the generic gate holds
    "mean_of_medians": (partial(robust.mean_of_medians, f=2), False, _MEAMED, {}),
    "multi_krum": (partial(robust.multi_krum, f=2, q=4), False, {}),
    "krum": (partial(robust.krum, f=2), False, {}),
    "nnm_multi_krum": (partial(robust.nnm_multi_krum, f_nnm=2, f=2, q=4), False, {}),
    "clipped_multi_krum": (partial(robust.clipped_multi_krum, tau=1.0, f=2, q=4), False, {}),
    "arc_multi_krum": (partial(robust.arc_multi_krum, f_arc=2, f=2, q=4), False, {}),
    "geometric_median": (partial(robust.geometric_median, max_iter=4), False, {}),
    "centered_clipping": (partial(robust.centered_clipping, c_tau=1.0, M=2), False, {}),
    "cge": (partial(robust.cge, f=2), False, {}),
    "monna": (partial(robust.monna, f=2), False, {}),
    "multi_krum_from_gram": (
        lambda x: robust.multi_krum_from_gram(x, jnp.zeros((N, N), jnp.float32), f=2, q=4),
        False, {}),
    "preagg.nnm": (partial(preagg.nnm, f=2), False, {}),
    "NearestNeighborMixing": (_NNM._transform_matrix, False, {}),
    "coordinate_median_stream": (robust.coordinate_median_stream, True, {}),
    "trimmed_mean_stream": (partial(robust.trimmed_mean_stream, f=2), True, {}),
    # one launch for the stream from the generic floor; under it, MeaMed's
    # own floor round by round
    "mean_of_medians_stream": (partial(robust.mean_of_medians_stream, f=2), True,
                               {"max_dim": MEAMED_CAP}, _MEAMED),
    "multi_krum_stream": (partial(robust.multi_krum_stream, f=2, q=4), True, {}),
    "nnm_multi_krum_stream": (
        partial(robust.nnm_multi_krum_stream, f_nnm=2, f=2, q=4), True, {}),
    "clipped_multi_krum_stream": (
        partial(robust.clipped_multi_krum_stream, tau=1.0, f=2, q=4), True, {}),
    "arc_multi_krum_stream": (
        partial(robust.arc_multi_krum_stream, f_arc=2, f=2, q=4), True, {}),
    "cge_stream": (partial(robust.cge_stream, f=2), True, {}),
    "monna_stream": (partial(robust.monna_stream, f=2), True, {}),
    "NearestNeighborMixing.stream": (_NNM._transform_stream_matrix, True, {}),
}


def _has_kernel(fn, x):
    # a fresh function each time: make_jaxpr keeps the trace of one it has seen
    return "pallas_call" in str(jax.make_jaxpr(lambda a: fn(a))(x))


@pytest.mark.parametrize("name", sorted(ENTRY_POINTS))
def test_entry_point_takes_the_gates_answer(backend, name):
    """On a TPU with no variable set: the kernel at the family's floor,
    XLA one column under it, and in both the gate's own answer."""
    fn, stream, floors, *second = ENTRY_POINTS[name]
    backend("tpu")
    floor = min(asked.get("min_dim", FLOOR) for asked in (floors, *second))
    answers = []
    for d in (floor, floor - 1):
        x = _sds((2, N, d) if stream else (N, d))
        serves = pk.pallas_serves(x, stream=stream, **floors) or any(
            pk.pallas_serves(_sds((N, d)), **asked) for asked in second)
        assert _has_kernel(fn, x) is serves, (name, d)
        answers.append(serves)
    assert answers == [True, False]


@pytest.mark.parametrize("name", ["trimmed_mean_attacked", "coordinate_median_attacked"])
def test_the_attacked_entry_points_take_the_gates_answer(backend, name):
    """The forms that are handed the honest rows alone ask the same gate for
    the ``(h + b, d)`` matrix nobody builds: at the floor the kernel that
    forms the other ``b`` rows, one column under it a refusal that sends the
    caller to the matrix (the streamed round asks ``attacked_serves`` first
    and never gets there)."""
    from byzpy_tpu.ops import attack_ops, coordinatewise

    b = 2
    flip = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
    kwargs = {"f": 2} if name == "trimmed_mean_attacked" else {}
    fn = partial(getattr(robust, name), attack=flip, b=b, **kwargs)
    backend("tpu")
    honest = _sds((N - b, FLOOR))
    assert robust.attacked_serves(honest, b) is pk.pallas_serves(_sds((N, FLOOR))) is True
    assert "name=sorted_reduce_stream_attacked" in str(jax.make_jaxpr(lambda a: fn(a))(honest))
    under = _sds((N - b, FLOOR - 1))
    assert robust.attacked_serves(under, b) is pk.pallas_serves(_sds((N, FLOOR - 1))) is False
    with pytest.raises(ValueError, match="no kernel serves"):
        jax.make_jaxpr(lambda a: fn(a))(under)
    # the table names both, and sees through the aggregate's partial
    form = coordinatewise.attacked_in_kernel(
        partial(robust.trimmed_mean, f=2) if kwargs else robust.coordinate_median, flip)
    assert form.func is getattr(robust, name) and form.keywords == {**kwargs, "attack": flip}
    assert coordinatewise.attacked_in_kernel(partial(robust.mean_of_medians, f=2), flip) is None
    assert coordinatewise.attacked_in_kernel(
        robust.coordinate_median, lambda honest, key: -honest[0]) is None


def test_mean_of_medians_past_its_cap_sorts_through_the_generic_gate(backend):
    """MeaMed asks twice: its fused kernel between its own floor and cap,
    and past the cap the sort network wherever the generic gate holds."""
    backend("tpu")
    fn = partial(robust.mean_of_medians, f=2)
    past = _sds((N, MEAMED_CAP + 128))
    assert not pk.pallas_serves(past, **_MEAMED)
    assert pk.pallas_serves(past)
    jaxpr = str(jax.make_jaxpr(fn)(past))
    assert "sort_columns" in jaxpr and "meamed_stream" not in jaxpr
    assert "meamed_stream" in str(jax.make_jaxpr(fn)(_sds((N, MEAMED_CAP))))
    # an integer matrix is promoted inside the XLA program and sorted there
    assert not _has_kernel(fn, _sds((N, MEAMED_CAP + 128), jnp.int32))


@pytest.mark.parametrize("name", ["trimmed_mean", "mean_of_medians", "multi_krum",
                                  "geometric_median", "preagg.nnm", "trimmed_mean_stream"])
def test_flipping_the_switch_changes_the_very_next_dispatch(monkeypatch, name):
    """The stale-closure pitfall: a decision read inside a jitted function
    is frozen into its first trace. The gate is asked in the Python
    wrapper, so the same shape under another ``BYZPY_TPU_PALLAS`` takes
    the other route at once, and back (``preagg.nnm`` asked inside its
    own jit until the gate had one home)."""
    fn, stream = ENTRY_POINTS[name][:2]
    x = jnp.ones((2, 9, 384) if stream else (9, 384), jnp.float32)
    routes = []
    for flag in ("0", "1", "0", "1"):
        monkeypatch.setenv("BYZPY_TPU_PALLAS", flag)
        routes.append(_has_kernel(fn, x))
    assert routes == [False, True, False, True]


# ---------------------------------------------------------------------------
# (iii) the resolved tile divides the aligned width
# ---------------------------------------------------------------------------


class _Tile(Exception):
    def __init__(self, tile):
        self.tile = tile


def _raise_tile(*args, tile, **kwargs):
    raise _Tile(tile)


def _w(name, **kwargs):
    return lambda x: getattr(pk, name)(x, interpret=False, **kwargs)


#: jitted call the tile is caught at -> the wrapper, called as on a TPU
WRAPPERS = {
    "_sort_columns_call": lambda x: pk.sort_columns(x[0], interpret=False),
    "_gram_pallas_call": lambda x: pk.gram_pallas(x[0], interpret=False),
    "_sorted_reduce_stream_call": _w("sorted_reduce_stream_pallas", mode="trimmed", f=2),
    # six of the eight rows handed over, the other two formed: asked with n = 8
    "_sorted_reduce_stream_attacked_call": lambda x: pk.sorted_reduce_stream_pallas(
        x[:, :-2], mode="trimmed", f=2, interpret=False, b=2,
        attack=_ROUND_ATTACK(_SIGN_FLIP, of="honest_mean")),
    "_weighted_center_step_call": lambda x: pk.weighted_center_step_pallas(
        x[0], jnp.zeros((x.shape[-1],), x.dtype), mode="clip", c_tau=1.0, interpret=False),
    "_meamed_stream_call": _w("meamed_stream_pallas", f=2),
    "_selection_mean_stream_call": _w("selection_mean_stream_pallas", f=2, q=4),
    "_selection_from_gram_call": lambda x: pk.selection_mean_from_gram_pallas(
        x[0], jnp.zeros((x.shape[1],) * 2, jnp.float32), f=2, q=4, interpret=False),
    "_nnm_stream_call": _w("nnm_stream_pallas", f=2),
    "_clip_selection_mean_stream_call": _w(
        "clip_selection_mean_stream_pallas", tau=1.0, f=2, q=4),
    "_arc_selection_mean_stream_call": _w(
        "arc_selection_mean_stream_pallas", f_arc=2, f=2, q=4),
    "_nnm_selection_mean_stream_call": _w(
        "nnm_selection_mean_stream_pallas", f_nnm=2, f=2, q=4),
    "_ragged_segment_sum_call": lambda x: pk.ragged_segment_sum_pallas(
        x[0], jnp.zeros((1, x.shape[1]), jnp.float32), interpret=False),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "call, n, d",
    [(call, n, d) for call in sorted(WRAPPERS) for n, d in [(8, 11_190_272), (64, 1_048_576)]
     if not (call == "_meamed_stream_call" and d > MEAMED_CAP)],  # never dispatched past its cap
)
def test_resolved_tile_divides_the_aligned_width(backend, monkeypatch, call, n, d, dtype):
    """At the cells' shape and at 64 x 1M no wrapper pads a matrix that
    was allocated ``aligned_width`` wide: the tile it resolves (its
    heuristic, or its inline default) divides that width."""
    backend("tpu")
    assert pk.aligned_width(n, d) == d  # both are aligned already
    monkeypatch.setattr(pk, call, _raise_tile)
    with pytest.raises(_Tile) as caught:
        jax.eval_shape(WRAPPERS[call], _sds((1, n, d), jnp.dtype(dtype)))
    tile = caught.value.tile
    assert tile % 128 == 0 and d % tile == 0, (call, tile)


@pytest.mark.parametrize("rows", [8, 64])
def test_quant_tile_divides_the_aligned_width(rows):
    for d in (11_190_272, 1_048_576):
        tile = qz._whole_blocks_tile(qz._auto_quant_tile(rows, d, qz.DEFAULT_BLOCK),
                                     qz.DEFAULT_BLOCK)
        assert tile % qz.DEFAULT_BLOCK == 0 and d % tile == 0


def test_a_wrappers_tile_argument_wins(monkeypatch):
    """``tile=`` is the one way to give a wrapper a tile (the tests' small
    tiles go through it); nothing else a process can set reaches it."""
    monkeypatch.setenv("BYZPY_TPU_TILE_SELECTION", "256")  # once a switch: now ignored
    monkeypatch.setattr(pk, "_selection_mean_stream_call", _raise_tile)
    xs = _sds((1, 8, 4096))
    with pytest.raises(_Tile) as caught:
        jax.eval_shape(lambda x: pk.selection_mean_stream_pallas(x, f=2, q=4), xs)
    assert caught.value.tile == pk._auto_selection_tile(4096, 8, 4)
    with pytest.raises(_Tile) as caught:
        jax.eval_shape(lambda x: pk.selection_mean_stream_pallas(x, f=2, q=4, tile=512), xs)
    assert caught.value.tile == 512


# ---------------------------------------------------------------------------
# (iv) the switches the package reads are the ones the docs list
# ---------------------------------------------------------------------------


def _names_in_package():
    names = set()
    for dirpath, _, files in os.walk(os.path.join(REPO, "byzpy_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name), encoding="utf-8") as fh:
                    names.update(re.findall(r"BYZPY_TPU_[A-Z0-9_]*", fh.read()))
    return names


def _names_in_docs_table():
    with open(os.path.join(REPO, "docs", "performance.md"), encoding="utf-8") as fh:
        text = fh.read()
    section = text.partition("## Every `BYZPY_TPU_*` variable")[2].partition("\n## ")[0]
    assert section, "docs/performance.md lost its table of BYZPY_TPU_* variables"
    return set(re.findall(r"^\| `(BYZPY_TPU_[A-Z0-9_]*)`", section, flags=re.M))


def test_every_switch_is_in_the_docs_table_and_none_lingers():
    in_package, in_docs = _names_in_package(), _names_in_docs_table()
    assert in_package - in_docs == set(), "read under byzpy_tpu/, missing from the table"
    assert in_docs - in_package == set(), "in the table, read nowhere under byzpy_tpu/"


def test_the_gate_reads_one_variable():
    """``BYZPY_TPU_PALLAS`` is the only name ``ops/pallas_kernels.py``
    takes from the environment."""
    with open(os.path.join(REPO, "byzpy_tpu", "ops", "pallas_kernels.py"), encoding="utf-8") as fh:
        text = fh.read()
    assert set(re.findall(r"environ\.get\(\s*f?\"([^\"]*)\"", text)) == {"BYZPY_TPU_PALLAS"}
    assert "getenv" not in text
    # the other two it names are in the s4 kernels' error message
    assert set(re.findall(r"BYZPY_TPU_[A-Z0-9_]*", text)) == {
        "BYZPY_TPU_PALLAS", "BYZPY_TPU_RAGGED_PALLAS", "BYZPY_TPU_SUBINT8_PALLAS"}
