"""Pallas TPU kernels for the hot robust-aggregation primitives.

Two workloads dominate (SURVEY §7 "hard parts"):

* **coordinate-wise selection** over a ``(n, d)`` gradient matrix with small
  ``n`` (8–128 nodes) and huge ``d`` (10^6+). XLA's general sort is built
  for large sort axes; for small ``n`` a Batcher merge-exchange network
  (~n/2·log²n compare–exchanges) of vectorized min/max on VPU lane vectors
  sorts every column in VMEM without materializing argsorts — one HBM
  read, one write. Measured on v5e at d=1M: 1.3–2.9× over XLA's sort for
  n=16..128. (Reference equivalent: ``np.partition`` medians over shm
  chunks, ``byzpy/aggregators/coordinate_wise/median.py:160-171``.)
* **pairwise squared distances** for Krum/NNM/MDA: a tiled self-Gram
  ``x @ x.T`` accumulated over feature tiles on the MXU, fused with the
  norm/±2ab expansion so the ``(n, n)`` result leaves VMEM exactly once.
  (Reference equivalent: the Gram trick at ``krum.py:31-58``.)

On a TPU every kernel is compiled by Mosaic; the Pallas interpreter
serves CPU processes only (the test mesh, ``tests/test_pallas_kernels.py``)
and cannot be requested on a TPU (:func:`_resolve_interpret`).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

_LANES = 128
_SUBLANES = 8
# What the stream kernels take, and so what the dispatch gate admits
# (16-bit floats up-convert per block in VMEM); f64, fp8 and integer
# matrices stay on XLA.
_KERNEL_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _on_tpu() -> bool:
    """Whether kernels dispatched now target a TPU. An active
    ``jax.default_device`` context (e.g. ``utils.placement`` routing a
    small host-resident aggregate to the CPU backend) overrides the
    process default. Any platform other than ``tpu``/``cpu`` raises:
    neither Mosaic nor the interpreter is known to be right there, and
    guessing silently is how a full-size kernel ends up interpreted."""
    dev = jax.config.jax_default_device
    if dev is not None:
        # jax accepts both Device objects and platform strings here.
        platform = dev if isinstance(dev, str) else dev.platform
    else:
        platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"byzpy_tpu Pallas kernels support the tpu and cpu platforms, "
            f"not {platform!r}"
        )
    return platform == "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` flag every kernel wrapper hands ``pallas_call``:
    Mosaic on a TPU, the Pallas interpreter on a CPU. Asking for the
    interpreter on a TPU is an error, not a slow path."""
    on_tpu = _on_tpu()
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise RuntimeError(
            "interpret=True on a TPU: Pallas kernels are compiled by Mosaic "
            "there; the interpreter is for CPU processes only"
        )
    return bool(interpret)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Column sorting network (small n, huge d)
# ---------------------------------------------------------------------------


def batcher_pairs(n: int):
    """Compare–exchange pairs of Batcher's merge-exchange sort for any n
    (Knuth TAOCP 5.2.2 Algorithm M): ~n/2·log²n exchanges vs the n²/2 of
    odd–even transposition."""
    pairs = []
    t = max(1, (n - 1).bit_length())
    p = 1 << (t - 1)
    while p > 0:
        q = 1 << (t - 1)
        r = 0
        d = p
        while True:
            for i in range(n - d):
                if (i & p) == r:
                    pairs.append((i, i + d))
            if q == p:
                break
            d = q - p
            q >>= 1
            r = p
        p >>= 1
    return pairs


def _float_sort_keys(block: Array) -> Array:
    """Monotone int32 sort keys for an f32 block: canonicalize NaN, bitcast,
    flip the magnitude bits of negatives. Self-inverse (`_keys_to_float`);
    reproduces ``jnp.sort``'s total order -inf < finite < +inf < NaN."""
    blk = jnp.where(jnp.isnan(block), jnp.full_like(block, jnp.nan), block)
    keys = jax.lax.bitcast_convert_type(blk, jnp.int32)
    return jnp.where(keys < 0, keys ^ jnp.int32(0x7FFFFFFF), keys)


def _keys_to_float(keys: Array, dtype) -> Array:
    keys = jnp.where(keys < 0, keys ^ jnp.int32(0x7FFFFFFF), keys)
    return jax.lax.bitcast_convert_type(keys, dtype)


def _batcher_network(rows):
    """``rows`` (equal-shaped arrays, one per worker) put in ascending
    order elementwise by Batcher's network of min/max. The shape of a row
    is the caller's: every exchange is dense over it."""
    rows = list(rows)
    for i, j in batcher_pairs(len(rows)):
        rows[i], rows[j] = jnp.minimum(rows[i], rows[j]), jnp.maximum(rows[i], rows[j])
    return rows


def _batcher_sort_rows(keys: Array, n_rows: int) -> Array:
    """Sort each column of ``keys`` (first axis ascending) via Batcher's
    network of elementwise min/max; ``n_rows`` is static."""
    return jnp.stack(_batcher_network([keys[i] for i in range(n_rows)]))


def _sort_columns_kernel(x_ref, out_ref, *, n_rows: int, is_float: bool):
    """Sort each column of the (n_rows, TILE) block ascending via Batcher's
    sorting network. The network is branch-free, unrolled at trace time
    (n_rows is static), and every compare–exchange is a VPU min/max on a
    (TILE,) lane vector.

    Float blocks sort on a monotone int32 key instead of raw float min/max:
    IEEE min/max have no total order over non-finite values (a single NaN
    poisons every exchange it touches, and ``finfo.max`` padding used to
    displace ``+inf``). The key map — canonicalize NaN, bitcast, flip the
    magnitude bits of negatives — is its own inverse and reproduces
    ``jnp.sort``'s total order (-inf < finite < +inf < NaN) with the O(n)
    transform paid once per element, keeping the O(n log^2 n) exchanges on
    cheap integer min/max.
    """
    block = x_ref[:]
    keys = _float_sort_keys(block) if is_float else block
    keys = _batcher_sort_rows(keys, n_rows)
    out_ref[:] = _keys_to_float(keys, block.dtype) if is_float else keys


def _auto_tile(n_pad: int) -> int:
    """Feature-tile width for ``sort_columns``: targets ~1 MiB f32
    blocks. Wide tiles amortize per-grid-step overhead for small n (n=8
    wants 8192); narrower ones keep VMEM sane as n grows (n=128 measured
    best at 1024–2048)."""
    return max(512, min(8192, _round_up(262144 // n_pad, _LANES)))


def sort_columns(
    x: Array, *, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> Array:
    """Columns of ``x`` (shape ``(n, d)``) sorted ascending along axis 0.

    Matches ``jnp.sort``'s value ordering including non-finite values
    (-inf < finite < +inf < NaN; divergences are bit-level only: -0.0 keys
    strictly before +0.0 where the stable ``jnp.sort`` preserves input
    order, and NaN payload/sign bits are canonicalized to the quiet +NaN).
    Pads ``n`` up to a sublane multiple with
    NaN rows for floats (the largest sort key — they sink to the bottom and
    are sliced off; ``iinfo.max`` for ints) and ``d`` up to a lane-aligned
    tile. 16-bit floats sort through an exact f32 round-trip: the kernel's
    int32 key path needs 32-bit rows, and every bf16/f16 value is exactly
    representable in f32.
    """
    interpret = _resolve_interpret(interpret)
    dtype = x.dtype
    is_float = bool(jnp.issubdtype(dtype, jnp.floating))
    if dtype in (jnp.bfloat16, jnp.float16):
        return sort_columns(
            x.astype(jnp.float32), tile=tile, interpret=interpret
        ).astype(dtype)
    if is_float and dtype != jnp.float32:
        return jnp.sort(x, axis=0)  # f64 etc.: no 64-bit key path on TPU
    n, d = x.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_tile(n_pad)
    return _sort_columns_call(x, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _sort_columns_call(x: Array, *, tile: int, interpret: bool) -> Array:
    n, d = x.shape
    is_float = bool(jnp.issubdtype(x.dtype, jnp.floating))
    dtype = x.dtype
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    big = jnp.asarray(jnp.nan if is_float else jnp.iinfo(dtype).max, dtype)
    xp = jnp.full((n_pad, d_pad), big, dtype)
    xp = xp.at[:n, :d].set(x)

    out = pl.pallas_call(
        functools.partial(_sort_columns_kernel, n_rows=n_pad, is_float=is_float),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), dtype),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((n_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (n_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="sort_columns",
    )(xp)
    return out[:n, :d]


def median_pallas(
    x: Array, *, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> Array:
    """Coordinate-wise median via the sorting network (matches
    ``jnp.median(x, axis=0)``, including NaN propagation: NaNs sort last, so
    a column contains one iff its bottom sorted row is NaN)."""
    n = x.shape[0]
    s = sort_columns(x, tile=tile, interpret=interpret)
    lo, hi = (n - 1) // 2, n // 2
    # Output dtype matched to jnp.median by construction (original dtype for
    # floats, a float dtype for ints — float64 for int64 under x64).
    out_dtype = jax.eval_shape(
        lambda a: jnp.median(a, axis=0), jax.ShapeDtypeStruct(x.shape, x.dtype)
    ).dtype
    if jnp.issubdtype(x.dtype, jnp.floating):
        # midpoint in the input dtype, exactly as jnp.median: for f16 this
        # overflows to inf for half-max magnitudes — so does the oracle.
        med = (s[lo] + s[hi]) * jnp.asarray(0.5, x.dtype)
        return jnp.where(jnp.isnan(s[n - 1]), jnp.asarray(jnp.nan, out_dtype), med)
    return (s[lo].astype(out_dtype) + s[hi].astype(out_dtype)) * 0.5


def trimmed_mean_pallas(
    x: Array, *, f: int, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> Array:
    """Coordinate-wise trimmed mean via the sorting network (matches the
    sort-and-slice in ``ops.robust.trimmed_mean``)."""
    n = x.shape[0]
    if not 0 <= 2 * f < n:
        raise ValueError(f"trim parameter f must satisfy 0 <= 2f < n (got n={n}, f={f})")
    s = sort_columns(x, tile=tile, interpret=interpret)
    return jnp.mean(s[f : n - f], axis=0)


# ---------------------------------------------------------------------------
# Tiled pairwise squared distances (fused Gram accumulation)
# ---------------------------------------------------------------------------


def _gram_kernel(x_ref, out_ref):
    """Accumulate this feature-tile's contribution to the (n, n) Gram
    matrix. Grid steps run sequentially on TPU, so += over the shared
    output block is safe; step 0 initializes."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    xt = x_ref[:]
    out_ref[:] += jax.lax.dot_general(
        xt, xt,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def gram_pallas(
    x: Array, *, tile: Optional[int] = None, interpret: Optional[bool] = None
) -> Array:
    """``x @ x.T`` accumulated in f32 over lane-aligned feature tiles
    (1024 columns unless ``tile`` says otherwise)."""
    interpret = _resolve_interpret(interpret)
    return _gram_pallas_call(x, tile=tile or 1024, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gram_pallas_call(x: Array, *, tile: int, interpret: bool) -> Array:
    n, d = x.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:n, :d].set(x)

    out = pl.pallas_call(
        _gram_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((n_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec(
            (n_pad, n_pad), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="gram_pallas",
    )(xp)
    return out[:n, :n].astype(
        jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype
    )


def pairwise_sq_dists_pallas(
    x: Array, *, tile: int = 1024, interpret: Optional[bool] = None
) -> Array:
    """``(n, n)`` squared Euclidean distances from the tiled Gram kernel
    (matches ``ops.robust.pairwise_sq_dists``)."""
    gram = gram_pallas(x, tile=tile, interpret=interpret)
    norms = jnp.diagonal(gram)[:, None]
    return jnp.maximum(norms + norms.T - 2.0 * gram, 0.0)


# ---------------------------------------------------------------------------
# Fused sorted-reduce (median / trimmed mean without writing the sort back)
# ---------------------------------------------------------------------------

_INF_KEY = 0x7F800000  # sort key of +inf; canonical NaN keys upper-bound it


def _sublane_order_sum(vals):
    """``vals[0] + ... + vals[m-1]`` associated as Mosaic associates a sum
    over the sublanes of (8, 128) tiles, which is how the trimmed sum was
    taken while the window's rows were sublanes of one block: the values
    at window positions c, c + 8, c + 16, ... added in that order (vreg
    after vreg), then the eight partial sums folded 8 -> 4 -> 2 -> 1 (the
    sublane rotate-and-add), a position the window does not reach left
    out. Four values give ``(v0 + v2) + (v1 + v3)``. Where the window was
    not whole vregs (``m`` no multiple of eight, and more than the one
    row that needs no sum) the sublanes past its end entered that sum as
    +0.0, which shows in one case only: a window of nothing but -0.0
    sums to +0.0. Keeping order and sign keeps the aggregate's bits on
    the TPU (PERF.md section 6, PR 29); a plain left-to-right sum is an
    ulp away on a third of the columns."""
    sums = [functools.reduce(jnp.add, vals[c::_SUBLANES])
            for c in range(min(_SUBLANES, len(vals)))]
    width = _SUBLANES
    while width > 1:
        width //= 2
        sums = [sums[c] + sums[c + width] if c + width < len(sums) else sums[c]
                for c in range(min(width, len(sums)))]
    total = sums[0]
    if len(vals) > 1 and len(vals) % _SUBLANES:
        # total + 0.0, which XLA (the interpreter's compiler) folds away
        total = jnp.where(total == 0.0, 0.0, total)
    return total


def _attack_keys(attack, honest: Array, b: int):
    """The sort keys of the ``b`` byzantine workers' rows, formed from the
    block ``honest`` ``(h, r, 128)`` of the honest workers' rows that the
    kernel holds. ``attack(honest, None)`` is traced here, in the kernel's
    body (a mean over the leading axis is a chain of adds on whole vregs),
    and gives one ``(r, 128)`` row that every byzantine worker sends, or
    ``b`` of them; each is rounded to the rows' dtype, as a row written to
    the stack was. A row sent ``b`` times is keyed once."""
    out = jnp.asarray(attack(honest, None))
    rows = [out] if out.ndim == honest.ndim - 1 else [out[i] for i in range(out.shape[0])]
    if len(rows) not in (1, b) or rows[0].shape != honest.shape[1:]:
        raise ValueError(
            f"the attack gives {out.shape} of honest rows {honest.shape}: one row, or one "
            f"for each of the {b} byzantine workers")
    keys = [_float_sort_keys(row.astype(honest.dtype).astype(jnp.float32)) for row in rows]
    return keys * b if len(keys) == 1 else keys


def _sorted_reduce_stream_kernel(x_ref, o_ref, *, n: int, f: int, mode: str, attack=None):
    """Per block of folded rows: key-sort the ``n`` workers' values of
    every coordinate in VMEM and emit ONLY the reduction — the coordinate
    median or the f-trimmed mean — so the sorted matrix never returns to
    HBM. Traffic per round: 1 read of ``x`` + a (1, d) write, vs
    sort_columns' read + full write + the reduction's re-read.

    With ``attack`` the block holds the honest workers' rows alone, fewer
    than ``n``, and the others' keys are formed here from the block
    (:func:`_attack_keys`): the byzantine rows exist in VMEM for the
    length of one block and nowhere else. From the first compare-exchange
    on, the kernel is the same on the same ``n`` keys.

    The block is ``(1, n, r, 128)``: worker ``i``'s part of it,
    ``x_ref[0, i]``, is ``r / 8`` whole (8, 128) vregs, so the key
    transform, each compare-exchange and the trimmed sum are dense
    elementwise ops over full vregs, and ``n`` is whatever it is (no
    rows padded to a sublane multiple, none to mask). A column contains
    a NaN iff the last sorted key is a NaN key. Means/midpoints
    accumulate in f32 and cast to the output dtype at the end (the
    midpoint is computed in the output dtype to match ``jnp.median``
    bit-for-bit on 16-bit floats); the trimmed sum keeps the order it
    had on the TPU as a reduction over sublanes
    (:func:`_sublane_order_sum`)."""
    keys = [_float_sort_keys(x_ref[0, i].astype(jnp.float32)) for i in range(x_ref.shape[1])]
    if attack is not None:
        keys += _attack_keys(attack, x_ref[0], n - len(keys))
    srt = _batcher_network(keys)
    if mode == "median":
        lo, hi = (n - 1) // 2, n // 2
        vlo = _keys_to_float(srt[lo], jnp.float32).astype(o_ref.dtype)
        vhi = _keys_to_float(srt[hi], jnp.float32).astype(o_ref.dtype)
        out = (vlo + vhi) * jnp.asarray(0.5, o_ref.dtype)
        has_nan = srt[n - 1] > _INF_KEY
        out = jnp.where(has_nan, jnp.asarray(jnp.nan, o_ref.dtype), out)
    else:  # trimmed mean of sorted rows [f, n - f)
        vals = [_keys_to_float(k, jnp.float32) for k in srt[f:n - f]]
        out = (_sublane_order_sum(vals) / (n - 2 * f)).astype(o_ref.dtype)
    o_ref[0] = out


def sorted_reduce_stream_pallas(
    xs: Array,
    *,
    mode: str = "median",
    f: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    attack: Optional[Callable] = None,
    b: int = 0,
) -> Array:
    """Coordinate-wise median (``mode='median'``) or f-trimmed mean
    (``mode='trimmed'``) over ``K`` stacked rounds ``xs: (K, n, d)`` in
    one kernel launch, returning ``(K, d)``. Float dtypes only (16-bit
    floats up-convert per-block in VMEM — half the HBM traffic of a
    pre-pass conversion).

    With ``attack`` (hashable, ``(honest, key) -> rows``, the key unread)
    ``xs`` is the ``(K, h, d)`` honest rows alone and the result is that
    of the ``n = h + b`` rows whose last ``b`` are the attack's, which the
    kernel forms in its body block by block from the honest rows it
    reads anyway (:func:`_attack_keys`): nobody writes them, and the
    kernel reads ``h`` rows where it read ``n``. The attack is traced on
    ``(h, r, 128)`` blocks, so its column j may read column j of the
    honest rows alone, it must lower in Mosaic, and it must keep a column
    of zeros at zero (the wrapper's pad columns and the caller's are not
    masked): ``ops/coordinatewise.KERNEL_FORMED_ATTACKS`` declares which
    do. The call is a kernel of its own name,
    ``sorted_reduce_stream_attacked``; without ``attack`` nothing differs
    from before.

    The kernel reads FOLDED rows: the wrapper reshapes its argument to
    ``(K, n, d_pad / 128, 128)``, so that a worker's row is whole
    (8, 128) tiles and not one sublane of every tile of an ``(n, d)``
    matrix (where every op of the network would run on an eighth of a
    vreg). A caller that holds its rows folded already and hands over
    ``stack.reshape(n, d_pad)`` pays nothing: the two reshapes cancel in
    the compiler and the kernel's operand is the caller's buffer (the
    one-device round of ``parallel.ps.build_ps_train_step``). A caller
    that holds an ``(n, d)`` matrix pays XLA's one relayout pass in
    front of the kernel (and, for an unaligned ``d``, the zero pad as a
    pass of its own in front of that). The result ``(K, d_pad / 128,
    128)`` is the flat ``(K, d_pad)`` vector in memory. ``tile`` stays a
    column count (the block holds ``tile / 128`` sublane rows of each
    worker); the heuristic's is, for Mosaic, rounded up to whole native
    tiles of those rows: 1024 columns of f32, 2048 of a 16-bit dtype; a
    ``tile`` given by the caller that is not such a multiple raises
    there. The interpreter takes any multiple of 128."""
    if mode not in {"median", "trimmed"}:
        raise ValueError(f"unknown mode {mode!r}")
    if (attack is None) != (b == 0):
        raise ValueError(f"an attack and the b > 0 rows it forms go together (got b={b})")
    K, held, d = xs.shape
    n = held + b  # the network's working set is n rows whichever way they came
    if mode == "trimmed" and not 0 <= 2 * f < n:
        raise ValueError(f"f must satisfy 0 <= 2f < n (got n={n}, f={f})")
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    interpret = _resolve_interpret(interpret)
    # a block holds tile / 128 sublane rows of each worker, in whole
    # native tiles: (8, 128) of f32 (Mosaic refuses fewer rows), (16, 128)
    # of a 16-bit dtype (eight compile, as half-filled tiles); or the
    # whole array, however short
    whole = _LANES * _SUBLANES * (4 // xs.dtype.itemsize)
    if tile is None:
        # sort happens on f32 rows in VMEM regardless of input dtype
        tile = _auto_sort_tile(d, n)
        if not interpret:
            # a narrower tile than Mosaic's block can be (the
            # heuristic's for an odd d)
            tile = _round_up(tile, whole)
    elif tile % _LANES:
        raise ValueError(f"tile must be a multiple of {_LANES} columns (got {tile})")
    elif not interpret and tile % whole and tile < d:
        raise ValueError(
            f"a {xs.dtype} block of {tile} columns is {tile // _LANES} sublane rows a "
            f"worker; Mosaic is given whole tiles: a multiple of {whole} columns"
        )
    if attack is not None:
        return _sorted_reduce_stream_attacked_call(
            xs, mode=mode, f=f, tile=tile, interpret=interpret, attack=attack, b=b
        )
    return _sorted_reduce_stream_call(
        xs, mode=mode, f=f, tile=tile, interpret=interpret
    )


def _sorted_reduce_stream_blocks(xs: Array, tile: int):
    """What both sorted-reduce calls hand ``pallas_call``: ``xs``
    ``(K, rows_held, d)`` zero-padded to whole tiles and folded, and the
    grid, block specs and output shape for it."""
    K, held, d = xs.shape
    d_pad = _round_up(max(d, 1), tile)
    if d_pad != d:
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, d_pad - d)))
    rows, r = d_pad // _LANES, tile // _LANES
    return xs.reshape(K, held, rows, _LANES), dict(
        out_shape=jax.ShapeDtypeStruct((K, rows, _LANES), xs.dtype),
        grid=(K, rows // r),
        in_specs=[
            pl.BlockSpec(
                (1, held, r, _LANES), lambda k, c: (k, 0, c, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, r, _LANES), lambda k, c: (k, c, 0), memory_space=pltpu.VMEM
        ),
    )


@functools.partial(jax.jit, static_argnames=("mode", "f", "tile", "interpret"))
def _sorted_reduce_stream_call(
    xs: Array, *, mode: str, f: int, tile: int, interpret: bool
) -> Array:
    K, n, d = xs.shape
    folded, blocks = _sorted_reduce_stream_blocks(xs, tile)
    out = pl.pallas_call(
        functools.partial(_sorted_reduce_stream_kernel, n=n, f=f, mode=mode),
        **blocks,
        interpret=interpret,
        name="sorted_reduce_stream",
    )(folded)
    return out.reshape(K, -1)[:, :d]


@functools.partial(
    jax.jit, static_argnames=("mode", "f", "tile", "interpret", "attack", "b"))
def _sorted_reduce_stream_attacked_call(
    xs: Array, *, mode: str, f: int, tile: int, interpret: bool, attack: Callable, b: int
) -> Array:
    """:func:`_sorted_reduce_stream_call` on the ``(K, h, d)`` honest rows,
    the other ``b`` formed in the kernel's body: the same grid and the same
    output, a block of ``h`` rows in place of ``n``."""
    K, h, d = xs.shape
    folded, blocks = _sorted_reduce_stream_blocks(xs, tile)
    out = pl.pallas_call(
        functools.partial(
            _sorted_reduce_stream_kernel, n=h + b, f=f, mode=mode, attack=attack),
        **blocks,
        interpret=interpret,
        name="sorted_reduce_stream_attacked",
    )(folded)
    return out.reshape(K, -1)[:, :d]


# ---------------------------------------------------------------------------
# Fused MeaMed (mean-around-median) kernel
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Fused weighted-center step (Weiszfeld / centered-clipping iterations)
# ---------------------------------------------------------------------------


def _weighted_center_step_kernel(
    x_ref, z_ref, o_ref, dist2_ref, w_ref, alpha_ref, *,
    n_pad: int, n_real: int, mode: str, eps: float, c_tau: float,
):
    """One iteration of a center-seeking aggregator in two HBM sweeps.

    Phase 0 per tile: accumulate each row's squared distance to the
    current center ``z`` into the ``(n, 1)`` scratch. Between phases:
    derive per-row weights from the distances —

    * ``mode='weiszfeld'``: ``w_i = (1/max(dist_i, eps)) / sum_j(...)``,
      ``alpha = 0``  (z_new = weighted mean; Weiszfeld step)
    * ``mode='clip'``: ``w_i = min(1, c_tau/max(dist_i, eps)) / n``,
      ``alpha = 1 - sum_i w_i``  (z_new = z + mean_i clip(x_i - z);
      Karimireddy et al. 2021)

    Phase 1 per tile: ``z_new = alpha * z + sum_i w_i x_i``. The XLA loop
    body pays ~4 passes (materialized ``x - z``, its norm read, the
    weighted-sum read); this kernel pays exactly 2 reads of ``x`` plus
    two (1, d) reads of ``z`` and one (1, d) write per iteration.
    Non-finite rows follow the XLA formulas bit-for-formula (an all-inf
    row gives dist=inf -> w=0, and 0*inf = NaN in both paths)."""
    p = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        @pl.when(c == 0)
        def _():
            dist2_ref[:] = jnp.zeros_like(dist2_ref)

        diff = x_ref[:].astype(jnp.float32) - z_ref[:].astype(jnp.float32)
        dist2_ref[:] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when((p == 1) & (c == 0))
    def _():
        row_i = lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
        dist = jnp.sqrt(dist2_ref[:])
        # Mosaic cannot store (or reliably load) scalars in VMEM — keep
        # alpha as a (1, 1) vector value end to end (scalar-indexed
        # ``alpha_ref[0, 0] = ...`` fails real lowering; interpret mode
        # accepted it silently).
        if mode == "weiszfeld":
            w = 1.0 / jnp.maximum(dist, eps)
            w = jnp.where(row_i < n_real, w, 0.0)
            w_ref[:] = w / jnp.sum(w)
            alpha_ref[:, :] = jnp.zeros((1, 1), jnp.float32)
        else:  # clip
            w = jnp.minimum(1.0, c_tau / jnp.maximum(dist, eps)) / n_real
            w = jnp.where(row_i < n_real, w, 0.0)
            w_ref[:] = w
            alpha_ref[:, :] = 1.0 - jnp.sum(w, axis=0, keepdims=True)

    @pl.when(p == 1)
    def _():
        zt = z_ref[:].astype(jnp.float32)
        xt = x_ref[:].astype(jnp.float32)
        out = alpha_ref[0:1, 0:1] * zt + jnp.sum(
            xt * w_ref[:], axis=0, keepdims=True
        )
        o_ref[:] = out.astype(o_ref.dtype)


def weighted_center_step_pallas(
    x: Array,
    z: Array,
    *,
    mode: str = "weiszfeld",
    eps: float = 1e-12,
    c_tau: float = 1.0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """One fused Weiszfeld / centered-clipping iteration: ``x`` ``(n, d)``,
    center ``z`` ``(d,)`` -> new center ``(d,)``. See the kernel docstring;
    ``ops.robust.geometric_median`` / ``centered_clipping`` call this
    inside their ``lax`` loops when the dispatch gate allows."""
    if mode not in {"weiszfeld", "clip"}:
        raise ValueError(f"unknown mode {mode!r}")
    n, d = x.shape
    if z.shape != (d,):
        raise ValueError(f"z must have shape ({d},), got {z.shape}")
    if x.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {x.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(x.dtype).itemsize)
    return _weighted_center_step_call(
        x, z, mode=mode, eps=eps, c_tau=c_tau, tile=tile, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("mode", "eps", "c_tau", "tile", "interpret")
)
def _weighted_center_step_call(
    x: Array, z: Array, *, mode: str, eps: float, c_tau: float, tile: int,
    interpret: bool,
) -> Array:
    n, d = x.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = x
        zp = z[None, :]
    else:
        xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:n, :d].set(x)
        zp = jnp.zeros((1, d_pad), z.dtype).at[0, :d].set(z)

    out = pl.pallas_call(
        functools.partial(
            _weighted_center_step_kernel, n_pad=n_pad, n_real=n, mode=mode,
            eps=eps, c_tau=c_tau,
        ),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), x.dtype),
        grid=(2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (n_pad, tile), lambda p, c: (0, c), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tile), lambda p, c: (0, c), memory_space=pltpu.VMEM
            ),
        ],
        # ``c * p`` parks the output on block (0, 0) through phase 0 (see
        # _nnm_stream_kernel's out_specs note).
        out_specs=pl.BlockSpec(
            (1, tile), lambda p, c: (0, c * p), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, 1), jnp.float32),
            pltpu.VMEM((n_pad, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="weighted_center_step",
    )(xp, zp)
    return out[0, :d]


# Dispatch-gate cap for meamed_stream_pallas (the tested envelope of the
# sort-kernel family; the single-phase kernel has no (1, d) scratch, so
# this is no longer a VMEM constraint — the headline 1M-dim shape sits
# well inside it either way)
MEAMED_MAX_DIM = 1 << 21


def _meamed_stream_kernel(
    x_ref, o_ref, *, n_pad: int, n_real: int, f: int,
):
    """ONE sweep per round: the whole column block computes locally.

    The ``k = n - f`` values closest to the median are a contiguous
    window of the sorted column, so a single key-sort yields BOTH
    statistics: the median (middle rows) and the cut deviation (minimum
    over window starts ``s`` of ``max(med - xs[s], xs[s+k-1] - med)`` —
    the k-th smallest ``|x - med|``, bit-identical to sorting the
    deviations since the window edges reuse the same f32 subtractions).
    Threshold-select against the cut with stable ties in node order via
    a triangular-matmul cumulative count — exactly
    ``ops.robust.mean_of_medians``'s rule. Total traffic: 1 read of
    ``x`` + a (1, d) write (the previous two-phase kernel paid 2 reads
    and a SECOND Batcher sort of the deviations; the XLA path pays ~4
    passes). A column containing NaN emits NaN (median semantics),
    matching the reference's propagation."""
    k = n_real - f
    tile = x_ref.shape[-1]
    row_i = lax.broadcasted_iota(jnp.int32, (n_pad, tile), 0)
    maxkey = jnp.iinfo(jnp.int32).max

    blk = x_ref[0].astype(jnp.float32)
    keys = jnp.where(row_i >= n_real, maxkey, _float_sort_keys(blk))
    srt = _batcher_sort_rows(keys, n_pad)
    lo, hi = (n_real - 1) // 2, n_real // 2
    if lo == hi:
        med = _keys_to_float(srt[lo], jnp.float32)  # odd n: no overflow
    else:
        # 0.5*a + 0.5*b: summing two near-max values first overflows
        med = (
            _keys_to_float(srt[lo], jnp.float32) * 0.5
            + _keys_to_float(srt[hi], jnp.float32) * 0.5
        )
    has_nan = srt[n_real - 1] > _INF_KEY
    med = jnp.where(has_nan, jnp.nan, med)

    # window-minimum cut: rows s in [0, n_real - k] are valid window
    # starts; their edges xs[s], xs[s+k-1] never touch pad rows
    # (s + k - 1 <= n_real - 1), so decoding pad keys is irrelevant.
    xsf = _keys_to_float(srt, jnp.float32)
    upper = jnp.concatenate(
        [xsf[k - 1:], jnp.full((k - 1, tile), jnp.inf, jnp.float32)], axis=0
    )
    radius = jnp.maximum(med[None, :] - xsf, upper - med[None, :])
    radius = jnp.where(row_i > n_real - k, jnp.inf, radius)
    dev_all = jnp.abs(blk - med[None, :])
    dev_all = jnp.where(row_i >= n_real, jnp.nan, dev_all)
    # non-finite median: inf - inf = NaN poisons the window arithmetic;
    # there every deviation is inf-or-NaN, so the k-th smallest is inf
    # iff >= k deviations are non-NaN (see ops.robust.mean_of_medians)
    cut_nonfinite = jnp.where(
        jnp.sum(jnp.where(jnp.isnan(dev_all), 0.0, 1.0), axis=0) >= k,
        jnp.inf, jnp.nan,
    )
    cut = jnp.where(
        jnp.isfinite(med), jnp.min(radius, axis=0), cut_nonfinite
    )

    # threshold-select on the ORIGINAL block (still in VMEM) with the
    # stable node-order tie rule, in float space — the cut value is
    # identical to the sorted-deviation cut, so comparisons agree exactly
    dev = jnp.abs(blk - med[None, :])
    dev = jnp.where(row_i >= n_real, jnp.inf, dev)
    sel = _stable_threshold_select(dev, cut, k=k)
    total = jnp.sum(jnp.where(sel, blk, 0.0), axis=0) / k
    out = jnp.where(jnp.isnan(cut) | jnp.isnan(med), jnp.nan, total)
    o_ref[0] = out[None, :].astype(o_ref.dtype)


def meamed_stream_pallas(
    xs: Array,
    *,
    f: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """MeaMed over ``K`` stacked rounds ``xs: (K, n, d)`` in one fused
    launch, returning ``(K, d)`` — equals ``ops.robust.mean_of_medians``
    per round. Float dtypes. Single-phase: each column block is read
    from HBM exactly ONCE (median, window-minimum cut, and the selected
    mean all compute from one in-VMEM sort — see the kernel docstring);
    ``MEAMED_MAX_DIM`` is retained as a dispatch-gate cap for parity
    with the other fused kernels' tested envelope."""
    K, n, d = xs.shape
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    if d > MEAMED_MAX_DIM:
        raise ValueError(
            f"meamed_stream_pallas requires d <= {MEAMED_MAX_DIM} (got {d}): "
            "use ops.robust.mean_of_medians (the XLA path) beyond that"
        )
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        # sort-aware budget; the kernel additionally keeps the original
        # block, the decoded sorted floats, and the deviation/mask
        # temporaries live across the sort, so budget 3 extra copies
        tile = _auto_sort_tile(d, n_pad, copies=13)
    return _meamed_stream_call(xs, f=f, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f", "tile", "interpret"))
def _meamed_stream_call(
    xs: Array, *, f: int, tile: int, interpret: bool
) -> Array:
    K, n, d = xs.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(_meamed_stream_kernel, n_pad=n_pad, n_real=n, f=f),
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda k, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile), lambda k, c: (k, 0, c), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="meamed_stream",
    )(xp)
    return out[:, 0, :d]


# ---------------------------------------------------------------------------
# Fused selection-mean (Multi-Krum / CGE / MoNNA in one kernel launch)
# ---------------------------------------------------------------------------


def _gram_norms_d2(g, *, n_pad: int):
    """(norms, d2) from the f32 Gram block, entirely in VMEM."""
    row_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    col_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    norms = jnp.sum(jnp.where(row_i == col_i, g, 0.0), axis=0)  # (n_pad,)
    d2 = jnp.maximum(norms[:, None] + norms[None, :] - 2.0 * g, 0.0)
    return norms, d2


def _padded_sort_keys(d2, *, n_pad: int, n_real: int):
    """int32 sort keys for ``d2`` with padded rows/columns forced to the
    absolute max key: pads must sink below every real entry, NaN included
    (canonical-NaN keys are strictly below int32 max), so they can never
    be selected while any real row remains."""
    row_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    col_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    pad = (row_i >= n_real) | (col_i >= n_real)
    keys = _float_sort_keys(d2)
    return jnp.where(pad, jnp.iinfo(jnp.int32).max, keys)


def _stable_threshold_select(vals, cut, *, k: int):
    """Boolean mask selecting, per column, everything strictly below
    ``cut`` plus enough entries AT the cut — filled in ROW order — to
    reach ``k`` total: the stable-argsort tie rule, without a gather.
    The row-order fill is a lower-triangular ones matmul (exact for 0/1
    counts in f32 at n <= 128). Works in any totally-ordered value
    space (int sort keys or raw floats) as long as ``vals`` carries pad
    masking that sorts past every real entry."""
    n_pad = vals.shape[0]
    below = vals < cut[None, :]
    at_f = jnp.where(vals == cut[None, :], 1.0, 0.0)
    row_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    col_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    tri = jnp.where(row_i >= col_i, 1.0, 0.0)
    csum_at = jax.lax.dot_general(
        tri, at_f, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    quota = jnp.asarray(float(k), jnp.float32) - jnp.sum(
        jnp.where(below, 1.0, 0.0), axis=0
    )
    return below | ((at_f > 0.5) & (csum_at <= quota[None, :]))


def _stable_k_select_mask(keys, *, n_pad: int, k: int):
    """Boolean mask of the ``k`` smallest-key entries per column of the
    ``(n_pad, cols)`` sorted-key problem, stable ties in row order
    (see :func:`_stable_threshold_select`). ``keys`` must already carry
    the pad masking (``_padded_sort_keys``); returns ``(sel, cut)``
    where ``cut`` is the per-column k-th smallest key (a NaN key iff
    fewer than ``k`` finite entries exist)."""
    srt = _batcher_sort_rows(keys, n_pad)
    cut = srt[k - 1]
    return _stable_threshold_select(keys, cut, k=k), cut


def _accumulate_gram(x_block, gram_ref, c):
    """Phase-0 body shared by the fused kernels: zero the scratch on the
    round's first chunk, then accumulate this feature tile's Gram
    contribution on the MXU (f32 accumulation; each tile of ``x`` is read
    from HBM exactly once — XLA's einsum streams ``x`` twice, as lhs and
    rhs: 0.91 ms vs the 0.31 ms one-read floor at 64x1M f32 on v5e)."""
    @pl.when(c == 0)
    def _():
        gram_ref[:] = jnp.zeros_like(gram_ref)

    gram_ref[:] += jax.lax.dot_general(
        x_block, x_block,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _selection_scores(g, *, mode: str, n_pad: int, n_real: int, f: int,
                      reference_index: int):
    """Per-node scores from the f32 Gram block ``g`` (``(n_pad, n_pad)``),
    entirely in VMEM. Padded rows are neutralized by the caller's ranking
    (they rank strictly last); here they only need to not pollute real
    nodes' scores."""
    norms, d2 = _gram_norms_d2(g, n_pad=n_pad)
    if mode == "cge":
        return norms
    if mode == "monna":
        return d2[reference_index]
    # krum: sum of the n_real - f - 1 smallest off-diagonal distances per
    # column (d2 is symmetric, so column sums == the reference's row sums;
    # ref: byzpy/aggregators/geometric_wise/krum.py:183-190).
    keys = _padded_sort_keys(d2, n_pad=n_pad, n_real=n_real)
    srt = _keys_to_float(_batcher_sort_rows(keys, n_pad), jnp.float32)
    return jnp.sum(srt[1:n_real - f], axis=0)


def _selection_weights(scores, *, n_pad: int, n_real: int, q: int):
    """``(n_pad, 1)`` array of 1/q weights on the ``q`` lowest-score rows,
    ties broken by row index, NaN scores last — exactly
    ``ops.robust.ranked_mean``'s ordering, with padded rows ranking after
    real NaN rows. All broadcasts stay in f32/int32 space: Mosaic cannot
    insert a minor dim on 1-bit (bool) vectors."""
    idx = lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)[0]
    isnan = jnp.isnan(scores) | (idx >= n_real)
    isn_f = jnp.where(isnan, 1.0, 0.0)
    s = jnp.where(isnan, jnp.zeros_like(scores), scores)
    isn_col = isn_f[:, None] > 0.5  # (n, 1) via f32 minor-dim insert
    isn_row = isn_f[None, :] > 0.5
    s_col = s[:, None]
    s_row = s[None, :]
    nan_lt = (~isn_row) & isn_col
    nan_eq = isn_row == isn_col
    lt = nan_lt | (nan_eq & (s_row < s_col))
    eq = nan_eq & (s_row == s_col)
    row_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
    col_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
    rank = jnp.sum(jnp.where(lt | (eq & (col_i < row_i)), 1, 0), axis=1)
    return jnp.where(rank[:, None] < q, 1.0 / q, 0.0)


def _auto_selection_tile(d: int, n_pad: int = 64, itemsize: int = 4) -> int:
    """Largest lane-aligned feature tile that divides ``d`` (so the kernel
    reads the caller's buffer with zero pad copies — a pad copy costs a
    full extra HBM read+write, ~0.6 ms at 64x1M f32, comparable to the
    whole fused aggregate) while the double-buffered input block stays
    inside the ~16 MiB scoped-VMEM budget. Falls back to 4096 + padding
    when ``d`` has no lane-aligned divisor. 16384 measured best at 64x1M
    on v5e (within noise of 8192)."""
    budget = 12 * 1024 * 1024  # leave scoped-VMEM headroom for out + scratch
    for t in (16384, 8192, 4096, 2048, 1024, 512, 256, 128):
        if d % t == 0 and 2 * n_pad * t * itemsize <= budget:
            return t
    return 4096


def _auto_sort_tile(
    d: int, n_pad: int, extra_bytes: int = 0, copies: int = 10
) -> int:
    """Feature tile for the SORT-based kernels (sorted-reduce, MeaMed).

    A Batcher network's live working set is far larger than the input
    block — the f32 up-cast, int32 keys, and the network's stage
    temporaries put Mosaic's measured scoped-stack allocation at ~8-9x
    ``n_pad * tile * 4`` (34.35 MiB at 64x16384, observed on v5e; the
    compile-time scoped-VMEM limit is 16 MiB, and interpret mode never
    checks it). Budget ``copies`` block copies (default 10; kernels that
    keep extra block-sized temporaries alive across the sort pass more)
    plus the caller's ``extra_bytes`` against a 14 MiB cap."""
    budget = 14 * 1024 * 1024 - extra_bytes
    candidates = (16384, 8192, 4096, 2048, 1024, 512, 256, 128)
    for t in candidates:
        if d % t == 0 and copies * n_pad * t * 4 <= budget:
            return t
    # No exact divisor fits: take the largest budget-fitting tile and let
    # the caller pad d up to it (a pad copy beats hundreds of tiny
    # grid steps).
    for t in candidates:
        if copies * n_pad * t * 4 <= budget:
            return t
    return 128


def _selection_mean_stream_kernel(
    x_ref, o_ref, gram_ref, w_ref, *, n_pad: int, n_real: int, f: int, q: int,
    mode: str, reference_index: int,
):
    """Two HBM sweeps per round inside ONE kernel launch, over a grid of
    ``(K, 2, C)`` (round, phase, feature-chunk).

    Phase 0: accumulate the f32 Gram of each feature tile into VMEM
    scratch — each tile of ``x`` is read from HBM exactly once (XLA's
    einsum streams ``x`` twice, as lhs and rhs; measured 0.91 ms vs the
    0.31 ms one-read floor for 64x1M f32 on v5e).

    Phase 1, first step: derive scores -> ranks -> 1/q weights from the
    completed Gram, all on (n, n)-sized VMEM data. Remaining phase-1
    steps: stream ``x`` a second time computing the weighted mean per
    tile. Per-round HBM traffic = 2 reads of ``x`` + the (1, d) output —
    the floor for any score-then-select aggregator, with zero
    intermediate round-trips.

    Rounds are independent: scratch re-initializes at each round's first
    step, and blocks are read directly from the stacked ``(K, n, d)`` HBM
    array, so no per-round slice/pad copies exist anywhere (an XLA-level
    ``scan`` over rounds materializes each 256 MB slice before a kernel
    can see it — measured 1.23 vs 0.85 ms/round at 64x1M f32)."""
    p = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _accumulate_gram(x_ref[0], gram_ref, c)

    @pl.when((p == 1) & (c == 0))
    def _():
        scores = _selection_scores(
            gram_ref[:], mode=mode, n_pad=n_pad, n_real=n_real, f=f,
            reference_index=reference_index,
        )
        w_ref[:] = _selection_weights(scores, n_pad=n_pad, n_real=n_real, q=q)

    @pl.when(p == 1)
    def _():
        w = w_ref[:]
        xt = jnp.where(w > 0.0, x_ref[0].astype(jnp.float32), 0.0)
        o_ref[0] = jnp.sum(xt * w, axis=0, keepdims=True).astype(o_ref.dtype)


def selection_mean_stream_pallas(
    xs: Array,
    *,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused score-select-average over a stream ``xs`` of ``(K, n, d)``
    stacked gradient matrices: returns ``(K, d)`` aggregates, equal to
    ``jax.vmap(lambda x: selection_mean_pallas(x, ...))(xs)``, in one
    kernel launch with exactly ``2 K`` HBM reads of the data and zero
    intermediate copies. This is the training-loop / replay shape of
    ``selection_mean_pallas`` — see that kernel for the per-round
    algorithm and ``ops.robust.aggregate_stream`` for when a stream of
    rounds per dispatch is the right shape."""
    if mode not in {"krum", "cge", "monna"}:
        raise ValueError(f"unknown mode {mode!r}")
    K, n, d = xs.shape
    if mode == "krum" and not (0 <= f < n - 1 and 1 <= q <= n - f):
        raise ValueError(f"invalid (n={n}, f={f}, q={q}) for krum")
    if not 1 <= q <= n:
        raise ValueError(f"q must be in [1, n] (got q={q}, n={n})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index out of range (got {reference_index})")
    interpret = _resolve_interpret(interpret)
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(xs.dtype).itemsize)
    return _selection_mean_stream_call(
        xs, f=f, q=q, mode=mode, reference_index=reference_index, tile=tile,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("f", "q", "mode", "reference_index", "tile", "interpret"),
)
def _selection_mean_stream_call(
    xs: Array, *, f: int, q: int, mode: str, reference_index: int, tile: int,
    interpret: bool,
) -> Array:
    K, n, d = xs.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs  # already aligned: the kernel reads the caller's buffer
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(
            _selection_mean_stream_kernel, n_pad=n_pad, n_real=n, f=f, q=q,
            mode=mode, reference_index=reference_index,
        ),
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, 2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda k, p, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        # ``c * p`` parks the output on block (k, 0, 0) through phase 0 —
        # no HBM output traffic during the Gram sweep (see
        # _nnm_stream_kernel's out_specs note).
        out_specs=pl.BlockSpec(
            (1, 1, tile), lambda k, p, c: (k, 0, c * p), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="selection_mean_stream",
    )(xp)
    return out[:, 0, :d]


def selection_mean_pallas(
    x: Array,
    *,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused score-select-average over ``x`` (``(n, d)``): equals

    * ``mode='krum'``:  ``ops.robust.multi_krum(x, f=f, q=q)``
    * ``mode='cge'``:   ``ops.robust.cge(x, f=n-q)`` (scores = sq. norms)
    * ``mode='monna'``: ``ops.robust.monna`` (scores = sq. dists to
      ``reference_index``)

    in one kernel launch reading ``x`` from HBM exactly twice. bf16/f16
    inputs accumulate in f32 (MXU-native) and return in the input dtype.
    Implemented as the K=1 case of ``selection_mean_stream_pallas`` (the
    leading-axis expand is metadata-only, no copy).
    """
    n, d = x.shape  # also rejects non-2D inputs before the reshape
    del n, d
    return selection_mean_stream_pallas(
        x[None], f=f, q=q, mode=mode, reference_index=reference_index,
        tile=tile, interpret=interpret,
    )[0]


def _selection_from_gram_kernel(
    x_ref, g_ref, o_ref, w_ref, *, n_pad: int, n_real: int, f: int, q: int,
    mode: str, reference_index: int,
):
    """Scores -> ranks -> 1/q weights from a PRECOMPUTED Gram (first
    step, all on (n, n) VMEM data), then one weighted-mean sweep of
    ``x``: exactly ONE HBM read of the data plus a (1, d) write — the
    floor for a finalize whose Gram already exists. The XLA finalize
    (``ops.robust.multi_krum_from_gram`` -> ``ranked_mean``) pays a
    masked (n, d) copy plus the contraction read."""
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _():
        scores = _selection_scores(
            g_ref[:].astype(jnp.float32), mode=mode, n_pad=n_pad,
            n_real=n_real, f=f, reference_index=reference_index,
        )
        w_ref[:] = _selection_weights(scores, n_pad=n_pad, n_real=n_real, q=q)

    w = w_ref[:]
    xt = jnp.where(w > 0.0, x_ref[:].astype(jnp.float32), 0.0)
    o_ref[:] = jnp.sum(xt * w, axis=0, keepdims=True).astype(o_ref.dtype)


def selection_mean_from_gram_pallas(
    x: Array,
    gram: Array,
    *,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused scores→selection→weighted-mean over ``x`` ``(n, d)`` given
    its PRECOMPUTED ``(n, n)`` Gram matrix — the finalize step of the
    streaming Multi-Krum fold, where each arriving gradient already
    contributed its Gram row (``aggregators.geometric_wise.krum``).
    Equals ``ops.robust.multi_krum_from_gram(x, gram, f=f, q=q)`` for
    ``mode='krum'`` (selection ties to documented tolerance: scores sum
    identical values in a different reduction order). One HBM read of
    ``x`` + a (1, d) write; pairwise distances never materialize in HBM
    at all."""
    if mode not in {"krum", "cge", "monna"}:
        raise ValueError(f"unknown mode {mode!r}")
    n, d = x.shape
    if gram.shape != (n, n):
        raise ValueError(f"gram must have shape ({n}, {n}), got {gram.shape}")
    if mode == "krum" and not (0 <= f < n - 1 and 1 <= q <= n - f):
        raise ValueError(f"invalid (n={n}, f={f}, q={q}) for krum")
    if not 1 <= q <= n:
        raise ValueError(f"q must be in [1, n] (got q={q}, n={n})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index out of range (got {reference_index})")
    if x.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {x.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(x.dtype).itemsize)
    return _selection_from_gram_call(
        x, gram, f=f, q=q, mode=mode, reference_index=reference_index,
        tile=tile, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("f", "q", "mode", "reference_index", "tile", "interpret"),
)
def _selection_from_gram_call(
    x: Array, gram: Array, *, f: int, q: int, mode: str,
    reference_index: int, tile: int, interpret: bool,
) -> Array:
    n, d = x.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = x
    else:
        xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:n, :d].set(x)
    # zero-pad the Gram: padded rows/cols are neutralized downstream
    # (_padded_sort_keys for krum distances, the idx >= n_real rank rule
    # for cge/monna), so they can never be selected
    gp = jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(
        gram.astype(jnp.float32)
    )

    out = pl.pallas_call(
        functools.partial(
            _selection_from_gram_kernel, n_pad=n_pad, n_real=n, f=f, q=q,
            mode=mode, reference_index=reference_index,
        ),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), x.dtype),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec(
                (n_pad, tile), lambda c: (0, c), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (n_pad, n_pad), lambda c: (0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, tile), lambda c: (0, c), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[pltpu.VMEM((n_pad, 1), jnp.float32)],
        interpret=interpret,
        name="selection_from_gram",
    )(xp, gp)
    return out[0, :d]


# ---------------------------------------------------------------------------
# Fused Nearest-Neighbor Mixing (pre-aggregator) kernel
# ---------------------------------------------------------------------------


def _nnm_weights(g, *, n_pad: int, n_real: int, k: int):
    """Selection state from the Gram block, all ``(n_pad, ...)`` f32:

    * ``mask_clean[j, i]`` — 1 iff row ``j`` is among the ``k`` nearest of
      mixing-row ``i`` (self included, stable ties by row index) AND row
      ``j`` is finite. Selection ranks in int32 key space, so NaN/inf
      distances order exactly like a stable argsort (NaN last, ties by
      index; the one divergence is -0.0 keying strictly before +0.0, as
      documented on ``sort_columns``). Padded rows carry the absolute max
      key — strictly after canonical-NaN keys — so they can never be
      selected while any real row remains.
    * ``taint[j]`` — 1 iff row ``j``'s squared norm is non-finite (its
      data must be zeroed before the mixing dot: 0-weight times NaN
      poisons a contraction).
    * ``sel_taint[i]`` — 1 iff mixing-row ``i`` selected a tainted row
      (its output becomes NaN; see ``ops.preagg.nnm`` for the semantics).
    """
    norms, d2 = _gram_norms_d2(g, n_pad=n_pad)
    keys = _padded_sort_keys(d2, n_pad=n_pad, n_real=n_real)
    sel, _cut = _stable_k_select_mask(keys, n_pad=n_pad, k=k)
    mask = jnp.where(sel, 1.0, 0.0)
    taint = jnp.where(jnp.isfinite(norms), 0.0, 1.0)
    sel_taint = jnp.where(
        jnp.sum(mask * taint[:, None], axis=0) > 0.5, 1.0, 0.0
    )
    mask_clean = mask * (1.0 - taint)[:, None]
    return mask_clean, taint, sel_taint


def _nnm_stream_kernel(
    x_ref, o_ref, gram_ref, w_ref, t_ref, *, n_pad: int, n_real: int, k: int
):
    """NNM with the same two-sweep structure as
    ``_selection_mean_stream_kernel``, but an ``(n, n)`` selection MASK
    instead of a weight vector: phase 1 computes ``mask.T @ x / k`` per
    feature tile on the MXU. HBM traffic per round = 2 reads of ``x`` + 1
    write of the mixed (n, d) output; the XLA path pays 4 passes (einsum
    Gram reads ``x`` twice, the mixing matmul once, plus the output) and
    a scatter-built mask (ref: ``byzpy/pre_aggregators/nnm.py:50-95``).
    ``t_ref`` holds [taint, sel_taint] columns for the non-finite rule."""
    p = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _accumulate_gram(x_ref[0], gram_ref, c)

    @pl.when((p == 1) & (c == 0))
    def _():
        mask_clean, taint, sel_taint = _nnm_weights(
            gram_ref[:], n_pad=n_pad, n_real=n_real, k=k
        )
        w_ref[:] = mask_clean
        t_ref[0, :] = taint
        t_ref[1, :] = sel_taint

    @pl.when(p == 1)
    def _():
        taint_col = t_ref[0, :][:, None]  # f32 minor-dim insert
        xt = jnp.where(taint_col > 0.5, 0.0, x_ref[0].astype(jnp.float32))
        # This dot FORMS THE OUTPUT (unlike the Gram, whose ~2^-9 MXU
        # default-precision error only perturbs distance near-ties), so
        # it must not truncate xt to bf16: on real Mosaic the MXU's
        # default single-pass multiply showed 3.3e-3 max error vs the
        # gather+mean oracle at 16x524288 f32. HIGHEST (bf16x6) restores
        # full f32 fidelity; the mask side is 0/1 and exact either way.
        mixed = jax.lax.dot_general(
            w_ref[:], xt,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        sel_taint_col = t_ref[1, :][:, None]
        out = jnp.where(sel_taint_col > 0.5, jnp.nan, mixed / k)
        o_ref[0] = out.astype(o_ref.dtype)


def nnm_stream_pallas(
    xs: Array,
    *,
    f: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Nearest-Neighbor Mixing over ``K`` stacked rounds ``xs: (K, n, d)``
    in one fused kernel launch; equals ``jax.vmap(lambda x:
    ops.preagg.nnm(x, f=f))(xs)``. See ``nnm_pallas`` for the K=1 form."""
    K, n, d = xs.shape
    if not 0 <= f < n:
        raise ValueError(f"f must satisfy 0 <= f < n (got n={n}, f={f})")
    interpret = _resolve_interpret(interpret)
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        # doubled itemsize: unlike the selection kernels, the (n, tile)
        # OUTPUT block is as large as the input block, so both count
        # against the scoped-VMEM budget
        tile = _auto_selection_tile(d, n_pad, 2 * jnp.dtype(xs.dtype).itemsize)
    return _nnm_stream_call(xs, f=f, tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f", "tile", "interpret"))
def _nnm_stream_call(
    xs: Array, *, f: int, tile: int, interpret: bool
) -> Array:
    K, n, d = xs.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(_nnm_stream_kernel, n_pad=n_pad, n_real=n, k=n - f),
        out_shape=jax.ShapeDtypeStruct((K, n_pad, d_pad), xs.dtype),
        grid=(K, 2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda kk, p, c: (kk, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        # Output map parks on block (kk, 0, 0) through all of phase 0
        # (``c * p`` = 0 there): Mosaic only DMAs a block when its index
        # changes between steps, so the Gram phase writes NOTHING to HBM
        # — without this the kernel paid a full garbage (n, d) output
        # pass during phase 0 (4 HBM sweeps, measured slower than XLA's
        # einsum path at 64x1M; 3 sweeps beat it). Block (kk, 0, 0) is
        # fully overwritten by the phase-1 c=0 step before its index
        # ever advances, so the parked visits never leak garbage.
        out_specs=pl.BlockSpec(
            (1, n_pad, tile), lambda kk, p, c: (kk, 0, c * p),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((2, n_pad), jnp.float32),
        ],
        interpret=interpret,
        name="nnm_stream",
    )(xp)
    return out[:, :n, :d]


def nnm_pallas(
    x: Array, *, f: int, tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Fused NNM over one ``(n, d)`` round (K=1 stream; the expand is
    metadata-only)."""
    n, d = x.shape
    del n, d
    return nnm_stream_pallas(x[None], f=f, tile=tile, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Fused NNM -> selection-mean pipeline kernel (pre-aggregate + aggregate)
# ---------------------------------------------------------------------------


def _nan_if_picked(w_sel, flag):
    """``(1, 1)`` f32: NaN when any SELECTED row (``w_sel`` ``(n, 1)``
    > 0) carries ``flag`` (``(n,)`` 0/1), else 0 — added to the weight
    column it poisons the whole output, as selecting a NaN row would.
    Column vectors and a keepdims sublane reduction: Mosaic has no
    lowering for the full reduction of a 1-D vector to a scalar (the
    form this replaced: "Not implemented: Offset change" on
    ``vector.multi_reduction`` of ``vector<1xNxf32>``), and none for
    broadcasting a 1-bit predicate."""
    picked = jnp.sum(
        jnp.where((w_sel > 0.0) & (flag[:, None] > 0.5), 1.0, 0.0),
        axis=0, keepdims=True,
    )
    return jnp.where(picked > 0.5, jnp.nan, 0.0)


def _nnm_selection_stream_kernel(
    x_ref, o_ref, gram_ref, w_ref, t_ref, *,
    n_pad: int, n_real: int, k_nnm: int, f_sel: int, q: int, mode: str,
    reference_index: int,
):
    """The canonical robust pipeline — Nearest-Neighbor Mixing feeding a
    score-select-average aggregator (NNM was designed as exactly this
    pre-mixer; ref: ``byzpy/pre_aggregators/nnm.py`` +
    ``aggregators/geometric_wise/krum.py``) — in the SAME two HBM sweeps
    a lone aggregator needs.

    The trick: the mixed matrix never has to exist. With ``A`` the
    (source, mixer) 0/1 selection mask and ``x̃`` the taint-zeroed data,
    ``mixed = Aᵀ x̃ / k``, so the mixed rows' Gram is
    ``Gm = Aᵀ G̃ A / k²`` — computable from the raw Gram entirely in
    VMEM — and the final mean of the ``q`` selected mixed rows collapses
    to source-space weights ``w_eff = A w_sel / k``. Phase 1 therefore
    streams ``x`` once with a weight VECTOR, identical in cost to
    ``_selection_mean_stream_kernel``. The two-step path pays ~5 sweeps
    (NNM's 2 reads + (n, d) write, then the aggregator re-reading the
    mixed matrix twice); this kernel pays 2 reads + a (1, d) write.

    Non-finite rule matches the two-step composition: mixed rows that
    selected a tainted source are NaN rows downstream — their Gm
    rows/columns are set NaN so distances/norms/ranking poison exactly
    like the materialized NaN rows would; if such a row is nonetheless
    selected (NaN scores rank last, so only when q exceeds the finite
    count), the output is NaN (folded into ``w_eff``)."""
    p = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _accumulate_gram(x_ref[0], gram_ref, c)

    @pl.when((p == 1) & (c == 0))
    def _():
        mask_clean, taint, sel_taint = _nnm_weights(
            gram_ref[:], n_pad=n_pad, n_real=n_real, k=k_nnm
        )
        g = gram_ref[:]
        bad_src = (taint[:, None] > 0.5) | (taint[None, :] > 0.5)
        g = jnp.where(bad_src, 0.0, g)  # Gram of the taint-zeroed data
        # Gm = Aᵀ G̃ A / k² — (n, n) VMEM matmuls; HIGHEST keeps the
        # derived distances closest to the analytic composition (cheap
        # at this size; the big data-streaming dots are elsewhere)
        ga = jax.lax.dot_general(
            g, mask_clean,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        gm = jax.lax.dot_general(
            mask_clean, ga,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) / jnp.asarray(float(k_nnm * k_nnm), jnp.float32)
        bad_mix = (sel_taint[:, None] > 0.5) | (sel_taint[None, :] > 0.5)
        gm = jnp.where(bad_mix, jnp.nan, gm)
        scores = _selection_scores(
            gm, mode=mode, n_pad=n_pad, n_real=n_real, f=f_sel,
            reference_index=reference_index,
        )
        w_sel = _selection_weights(scores, n_pad=n_pad, n_real=n_real, q=q)
        w_eff = jax.lax.dot_general(
            mask_clean, w_sel,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ) / jnp.asarray(float(k_nnm), jnp.float32)
        w_ref[:] = w_eff + _nan_if_picked(w_sel, sel_taint)
        t_ref[0, :] = taint

    @pl.when(p == 1)
    def _():
        taint_col = t_ref[0, :][:, None]
        xt = jnp.where(taint_col > 0.5, 0.0, x_ref[0].astype(jnp.float32))
        o_ref[0] = jnp.sum(xt * w_ref[:], axis=0, keepdims=True).astype(
            o_ref.dtype
        )


def _clip_selection_stream_kernel(
    x_ref, o_ref, gram_ref, w_ref, t_ref, *,
    n_pad: int, n_real: int, tau: float, f_sel: int, q: int, mode: str,
    reference_index: int, pre: str = "clip", cut_off: int = 0,
):
    """Static L2 clipping feeding a score-select-average aggregator, in
    two HBM sweeps — the diagonal instance of the same Gram-collapse
    that fuses NNM (``_nnm_selection_stream_kernel``): clipping is the
    row scaling ``x' = diag(c) x`` with ``c_i = min(1, τ/‖x_i‖)`` and
    the norms ARE the Gram diagonal, so the clipped Gram is
    ``c_i c_j G_ij`` in VMEM and the selected mean collapses to weights
    ``w_sel ⊙ c``. Non-finite rule: a NaN norm propagates NaN through
    its factor (rows rank last, NaN output if selected, matching the
    materialized path); an inf norm clips to factor 0 — its Gm row is
    NaN (0·inf), ranks last, and selection of it emits a whole-NaN
    output (the materialized path is NaN only at the non-finite
    coordinates; documented deviation, same class as NNM's PARITY
    note). An inf norm is ambiguous from the Gram alone: it can also
    arise from a FINITE row whose squared norm overflows f32
    (‖x‖ > ~1.8e19). The materialized path clips such a row to the
    all-zero vector (which then competes in scoring near the origin);
    this kernel excludes it like non-finite data. The InfAttack-style
    case is the security-relevant one and matches; the finite-overflow
    divergence is pinned in tests."""
    p = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _accumulate_gram(x_ref[0], gram_ref, c)

    @pl.when((p == 1) & (c == 0))
    def _():
        g = gram_ref[:]
        row_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)
        col_i = lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 1)
        norms2 = jnp.sum(jnp.where(row_i == col_i, g, 0.0), axis=0)
        norms = jnp.sqrt(jnp.maximum(norms2, 0.0))
        if pre == "clip":
            threshold = jnp.asarray(tau, jnp.float32)
        else:  # arc: threshold = sorted(real norms)[cut_off - 1]
            # stable rank in int32 key space (jnp.sort total order incl.
            # non-finite); padded rows carry the max key so they rank
            # strictly after every real norm and never shift the cut
            keys = _float_sort_keys(norms)
            idx = lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)[0]
            keys = jnp.where(idx >= n_real, jnp.iinfo(jnp.int32).max, keys)
            kr = keys[:, None]
            kc = keys[None, :]
            ir = idx[:, None]
            ic = idx[None, :]
            rank = jnp.sum(
                jnp.where((kc < kr) | ((kc == kr) & (ic < ir)), 1, 0), axis=1
            )
            # exactly one row has the cut rank; all other summands are 0.
            # Kept (1,)-shaped: Mosaic bitcasts want vectors, not scalars.
            th_key = jnp.sum(
                jnp.where(rank == cut_off - 1, keys, jnp.zeros_like(keys)),
                keepdims=True,
            )
            threshold = _keys_to_float(th_key, jnp.float32)
        cfac = jnp.minimum(
            1.0, threshold / jnp.maximum(norms, 1e-12)
        )
        gm = cfac[:, None] * cfac[None, :] * g
        scores = _selection_scores(
            gm, mode=mode, n_pad=n_pad, n_real=n_real, f=f_sel,
            reference_index=reference_index,
        )
        w_sel = _selection_weights(scores, n_pad=n_pad, n_real=n_real, q=q)
        bad = jnp.where(jnp.isfinite(norms), 0.0, 1.0)
        # zero bad rows' weights BEFORE scaling: an unselected NaN-norm
        # row otherwise contributes 0 * NaN = NaN to the weighted sum
        w_eff = jnp.where(bad[:, None] > 0.5, 0.0, w_sel * cfac[:, None])
        w_ref[:] = w_eff + _nan_if_picked(w_sel, bad)
        t_ref[0, :] = bad

    @pl.when(p == 1)
    def _():
        bad_col = t_ref[0, :][:, None]
        xt = jnp.where(bad_col > 0.5, 0.0, x_ref[0].astype(jnp.float32))
        o_ref[0] = jnp.sum(xt * w_ref[:], axis=0, keepdims=True).astype(
            o_ref.dtype
        )


def clip_selection_mean_stream_pallas(
    xs: Array,
    *,
    tau: float,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Static clipping + score-select-average over ``K`` stacked rounds
    ``xs: (K, n, d)`` in ONE fused launch; equals
    ``selection_mean(clip_rows(x, threshold=tau), f=f, q=q)`` per round
    at 2 HBM reads + a (1, d) write. See
    ``_clip_selection_stream_kernel`` (and its non-finite note)."""
    if mode not in {"krum", "cge", "monna"}:
        raise ValueError(f"unknown mode {mode!r}")
    K, n, d = xs.shape
    if not tau > 0:
        raise ValueError(f"tau must be positive (got {tau})")
    if mode == "krum" and not (0 <= f < n - 1 and 1 <= q <= n - f):
        raise ValueError(f"invalid (n={n}, f={f}, q={q}) for krum")
    if not 1 <= q <= n:
        raise ValueError(f"q must be in [1, n] (got q={q}, n={n})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index out of range (got {reference_index})")
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(xs.dtype).itemsize)
    return _clip_selection_mean_stream_call(
        xs, tau=tau, f=f, q=q, mode=mode, reference_index=reference_index,
        tile=tile, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tau", "f", "q", "mode", "reference_index", "tile", "interpret"
    ),
)
def _clip_selection_mean_stream_call(
    xs: Array, *, tau: float, f: int, q: int, mode: str,
    reference_index: int, tile: int, interpret: bool,
) -> Array:
    K, n, d = xs.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(
            _clip_selection_stream_kernel, n_pad=n_pad, n_real=n,
            tau=float(tau), f_sel=f, q=q, mode=mode,
            reference_index=reference_index,
        ),
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, 2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda k, p, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile), lambda k, p, c: (k, 0, c * p),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, 1), jnp.float32),
            pltpu.VMEM((1, n_pad), jnp.float32),
        ],
        interpret=interpret,
        name="clip_selection_mean_stream",
    )(xp)
    return out[:, 0, :d]


def arc_selection_mean_stream_pallas(
    xs: Array,
    *,
    f_arc: int,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Adaptive Robust Clipping + score-select-average over ``K`` stacked
    rounds in ONE fused launch; equals
    ``selection_mean(arc_clip(x, f=f_arc), f=f, q=q)`` per round. ARC's
    factors are norm-derived like static clipping's — the data-dependent
    threshold (the ``cut_off``-th smallest norm) computes by stable rank
    counting in int32 key space inside VMEM — so the same Gram-collapse
    applies (see ``_clip_selection_stream_kernel``, ``pre='arc'``)."""
    if mode not in {"krum", "cge", "monna"}:
        raise ValueError(f"unknown mode {mode!r}")
    K, n, d = xs.shape
    if not 0 <= f_arc <= n:
        raise ValueError(f"f_arc must satisfy 0 <= f_arc <= n (got {f_arc})")
    if mode == "krum" and not (0 <= f < n - 1 and 1 <= q <= n - f):
        raise ValueError(f"invalid (n={n}, f={f}, q={q}) for krum")
    if not 1 <= q <= n:
        raise ValueError(f"q must be in [1, n] (got q={q}, n={n})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index out of range (got {reference_index})")
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(xs.dtype).itemsize)
    return _arc_selection_mean_stream_call(
        xs, f_arc=f_arc, f=f, q=q, mode=mode,
        reference_index=reference_index, tile=tile, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "f_arc", "f", "q", "mode", "reference_index", "tile", "interpret"
    ),
)
def _arc_selection_mean_stream_call(
    xs: Array, *, f_arc: int, f: int, q: int, mode: str,
    reference_index: int, tile: int, interpret: bool,
) -> Array:
    from .preagg import arc_cut_off

    K, n, d = xs.shape
    cut_off = arc_cut_off(n, f_arc)  # 1-based rank of the threshold norm
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(
            _clip_selection_stream_kernel, n_pad=n_pad, n_real=n,
            tau=0.0, f_sel=f, q=q, mode=mode,
            reference_index=reference_index, pre="arc", cut_off=cut_off,
        ),
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, 2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda k, p, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tile), lambda k, p, c: (k, 0, c * p),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, 1), jnp.float32),
            pltpu.VMEM((1, n_pad), jnp.float32),
        ],
        interpret=interpret,
        name="arc_selection_mean_stream",
    )(xp)
    return out[:, 0, :d]


def nnm_selection_mean_stream_pallas(
    xs: Array,
    *,
    f_nnm: int,
    f: int,
    q: int,
    mode: str = "krum",
    reference_index: int = 0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """NNM pre-aggregation + score-select-average aggregation over ``K``
    stacked rounds ``xs: (K, n, d)`` in ONE fused launch, returning
    ``(K, d)``; equals ``selection_mean(nnm(x, f=f_nnm), f=f, q=q)`` per
    round at 2 HBM reads + a (1, d) write — the two-step path moves ~5
    full-matrix passes. See ``_nnm_selection_stream_kernel``.

    16-bit inputs: the two-step path rounds the MATERIALIZED mixed
    matrix back to the input dtype before scoring, while this kernel
    scores from the full-f32 derived Gram — strictly higher fidelity,
    but a near-tie in krum scores (within ~2^-8 relative for bf16) may
    select a different row than the rounded two-step would. f32 inputs
    match the composition to float precision."""
    if mode not in {"krum", "cge", "monna"}:
        raise ValueError(f"unknown mode {mode!r}")
    K, n, d = xs.shape
    if not 0 <= f_nnm < n:
        raise ValueError(f"f_nnm must satisfy 0 <= f_nnm < n (got {f_nnm})")
    if mode == "krum" and not (0 <= f < n - 1 and 1 <= q <= n - f):
        raise ValueError(f"invalid (n={n}, f={f}, q={q}) for krum")
    if not 1 <= q <= n:
        raise ValueError(f"q must be in [1, n] (got q={q}, n={n})")
    if not 0 <= reference_index < n:
        raise ValueError(f"reference_index out of range (got {reference_index})")
    if xs.dtype not in _KERNEL_DTYPES:
        raise ValueError(f"unsupported dtype {xs.dtype}")
    interpret = _resolve_interpret(interpret)
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    if tile is None:
        tile = _auto_selection_tile(d, n_pad, jnp.dtype(xs.dtype).itemsize)
    return _nnm_selection_mean_stream_call(
        xs, f_nnm=f_nnm, f=f, q=q, mode=mode,
        reference_index=reference_index, tile=tile, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "f_nnm", "f", "q", "mode", "reference_index", "tile", "interpret"
    ),
)
def _nnm_selection_mean_stream_call(
    xs: Array, *, f_nnm: int, f: int, q: int, mode: str,
    reference_index: int, tile: int, interpret: bool,
) -> Array:
    K, n, d = xs.shape
    n_pad = max(_SUBLANES, _round_up(n, _SUBLANES))
    d_pad = _round_up(max(d, 1), tile)
    if (n_pad, d_pad) == (n, d):
        xp = xs
    else:
        xp = jnp.zeros((K, n_pad, d_pad), xs.dtype).at[:, :n, :d].set(xs)

    out = pl.pallas_call(
        functools.partial(
            _nnm_selection_stream_kernel, n_pad=n_pad, n_real=n,
            k_nnm=n - f_nnm, f_sel=f, q=q, mode=mode,
            reference_index=reference_index,
        ),
        out_shape=jax.ShapeDtypeStruct((K, 1, d_pad), xs.dtype),
        grid=(K, 2, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (1, n_pad, tile), lambda k, p, c: (k, 0, c),
                memory_space=pltpu.VMEM,
            )
        ],
        # phase-parked output (see _nnm_stream_kernel's out_specs note)
        out_specs=pl.BlockSpec(
            (1, 1, tile), lambda k, p, c: (k, 0, c * p),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((n_pad, n_pad), jnp.float32),
            pltpu.VMEM((n_pad, 1), jnp.float32),
            pltpu.VMEM((1, n_pad), jnp.float32),
        ],
        interpret=interpret,
        name="nnm_selection_mean_stream",
    )(xp)
    return out[:, 0, :d]


# ---------------------------------------------------------------------------
# Ragged segment sum (flat multi-cohort batches, serving tier)
# ---------------------------------------------------------------------------


def _ragged_segment_sum_kernel(
    fill_ref, w_ref, x_ref, out_ref, *, rows_tile: int
):
    """One (row-tile, feature-tile) step of the ragged segment sum:
    accumulate ``Wᵀ @ x`` for this row tile into the shared
    ``(C_pad, tile)`` output block (``W`` columns are the per-cohort
    weight vectors — selection/window masks with their reciprocal
    weights baked in). The batch's actual fill (total occupied rows,
    scalar-prefetched so it is known before the body runs) gates the
    accumulation — row tiles past the fill are pure capacity padding
    and skip their MXU work entirely, the Ragged-Paged-Attention
    economics: compute follows the DATA, the compiled shape only
    bounds it. Grid steps run sequentially on TPU, so ``+=`` over the
    shared block is safe; the first row tile initializes."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(i * rows_tile < fill_ref[0])
    def _():
        # this dot FORMS THE OUTPUT: at the MXU's default precision the
        # rows are rounded to bf16 (4.6e-3 max error at 64x65,536 on
        # v5e against the f32 einsum; see _nnm_stream_kernel)
        out_ref[:] += jax.lax.dot_general(
            w_ref[:], x_ref[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


def ragged_segment_sum_pallas(
    x: Array,
    weights: Array,
    *,
    fill: Optional[Array] = None,
    rows_tile: Optional[int] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Per-cohort weighted row sums over a flat ragged batch:
    ``out[c] = Σ_r weights[c, r]·x[r]`` for ``x: (R, d)`` and
    ``weights: (C, R)`` (one weight row per cohort — zero outside the
    cohort's block, window/selection masks with reciprocal weights
    baked in by the caller). This is the contraction every ragged
    aggregate ends in, tiled over (row tiles × feature tiles) with the
    batch ``fill`` (an int32 scalar, default ``R``) scalar-prefetched
    so capacity row tiles skip their MXU work — the padding a dense
    program would pay for is skipped, not multiplied. The
    weight-transpose dot mirrors
    the XLA fallback's per-cohort einsum contraction row-for-row;
    interpret mode reproduces it bit-for-bit, Mosaic is ulp-level
    (1.8e-7 max against the f32 einsum at 64x65,536 on v5e, PR 21,
    once the dot asks for ``HIGHEST``) — so the serving ragged door
    keeps the XLA program authoritative for its bit-parity contract and
    routes here only on explicit opt-in (``BYZPY_TPU_RAGGED_PALLAS=1``;
    see ``serving.ragged``). ``chip_smoke.py`` compiles and checks it on
    every run; it has no chip time yet (ROADMAP S4)."""
    interpret = _resolve_interpret(interpret)
    n, d = x.shape
    n_cohorts = weights.shape[0]
    if tile is None:
        tile = max(_LANES, min(4096, _round_up(d, _LANES)))
    if rows_tile is None:
        rows_tile = max(_SUBLANES, min(256, _round_up(n, _SUBLANES)))
    if fill is None:
        fill = jnp.asarray([n], jnp.int32)
    else:
        fill = jnp.asarray(fill, jnp.int32).reshape((1,))
    return _ragged_segment_sum_call(
        x, weights, fill, n_cohorts=int(n_cohorts),
        rows_tile=int(rows_tile), tile=int(tile), interpret=bool(interpret),
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_cohorts", "rows_tile", "tile", "interpret"),
)
def _ragged_segment_sum_call(
    x: Array,
    weights: Array,
    fill: Array,
    *,
    n_cohorts: int,
    rows_tile: int,
    tile: int,
    interpret: bool,
) -> Array:
    n, d = x.shape
    n_pad = _round_up(max(n, 1), rows_tile)
    d_pad = _round_up(max(d, 1), tile)
    c_pad = max(_SUBLANES, _round_up(n_cohorts, _SUBLANES))
    xp = jnp.zeros((n_pad, d_pad), jnp.float32).at[:n, :d].set(
        x.astype(jnp.float32)
    )
    ohp = jnp.zeros((n_pad, c_pad), jnp.float32).at[:n, :n_cohorts].set(
        weights.T.astype(jnp.float32)
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // rows_tile, d_pad // tile),
        # index maps receive the scalar-prefetch ref as a trailing arg
        in_specs=[
            pl.BlockSpec(
                (rows_tile, c_pad), lambda i, j, fill: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (rows_tile, tile), lambda i, j, fill: (i, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (c_pad, tile), lambda i, j, fill: (0, j), memory_space=pltpu.VMEM
        ),
    )
    out = pl.pallas_call(
        functools.partial(_ragged_segment_sum_kernel, rows_tile=rows_tile),
        out_shape=jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_segment_sum",
    )(fill, ohp, xp)
    return out[:n_cohorts, :d].astype(x.dtype)


def _ragged_segment_sum_dequant_kernel(
    fill_ref, w_ref, c_ref, s_ref, out_ref, *,
    rows_tile: int, block: int, blocks_per_tile: int, mode: str, fp_dtype,
):
    """Fused-dequant twin of :func:`_ragged_segment_sum_kernel`: the row
    tile arrives as WIRE codes (int8 codes / fp8 bit patterns / packed
    s4 nibbles) plus its ``(rows_tile, blocks_per_tile)`` f32 scale
    block, expands to f32 inside the tile (cast + blockwise scale
    multiply — both IEEE-exact, matching the host codec bit-for-bit),
    and feeds the same transposed-weights MXU contraction. Quantized
    rows thus reach the accumulate at wire width: a feature tile moves
    tile bytes (int8/fp8) or tile/2 bytes (s4) plus tile/block scale
    floats instead of 4·tile f32 bytes."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(i * rows_tile < fill_ref[0])
    def _():
        codes = c_ref[:]
        if mode == "s4":
            lo = codes & jnp.uint8(0xF)
            hi = codes >> 4
            vals = jnp.stack([lo, hi], axis=-1).reshape(
                rows_tile, blocks_per_tile * block
            ).astype(jnp.float32) - 8.0
        elif mode == "int8":
            vals = codes.astype(jnp.float32)
        else:
            vals = lax.bitcast_convert_type(codes, fp_dtype).astype(
                jnp.float32
            )
        x = (
            vals.reshape(rows_tile, blocks_per_tile, block)
            * s_ref[:, :blocks_per_tile][:, :, None]
        ).reshape(rows_tile, blocks_per_tile * block)
        out_ref[:] += jax.lax.dot_general(
            w_ref[:], x,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


def ragged_segment_sum_dequant_pallas(
    codes: Array,
    scales: Array,
    weights: Array,
    *,
    mode: str,
    block: int,
    d: int,
    fill: Optional[Array] = None,
    rows_tile: Optional[int] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """:func:`ragged_segment_sum_pallas` consuming still-compressed
    wire rows: ``out[c] = Σ_r weights[c, r] · dequant(codes[r],
    scales[r])[:d]`` without ever materializing the ``(R, d)`` f32
    matrix — dequantization happens per (row-tile × feature-tile)
    inside the kernel, next to the MXU accumulate (the EQuARX stance:
    codes travel, f32 exists only tile-local). ``codes`` is ``(R,
    ncodes)`` wire layout (``d`` int8 codes / fp8 bit patterns, or
    ``nb·block/2`` packed s4 nibble bytes), ``scales`` ``(R, nb)`` f32;
    ``fill`` is the batch's occupied-row count, scalar-prefetched so
    capacity row tiles skip both the dequant and the MXU work. The
    feature tile is rounded up to a whole number of codec blocks so a
    scale block never straddles tiles. The XLA mirror
    (``ops.ragged.flat_dequantize`` + the einsum contraction) is
    authoritative for the serving tier's bit-parity contract; this
    kernel is the same explicit opt-in as the dense ragged kernel
    (``BYZPY_TPU_RAGGED_PALLAS=1``), interpret-exact on CPU. On a v5e
    (PR 21) the int8 and fp8 modes compile and agree to 2.4e-7
    (``chip_smoke.py`` checks both); ``s4`` does not lower — Mosaic:
    "Unsupported cast: uint8 -> float32" — and raises
    (:func:`s4_kernels_unsupported`, ROADMAP S4)."""
    interpret = _resolve_interpret(interpret)
    if mode == "s4" and not interpret:
        raise s4_kernels_unsupported("uint8 -> float32")
    n, ncodes = codes.shape
    nb = scales.shape[1]
    n_cohorts = weights.shape[0]
    if mode == "s4" and block % 2:
        raise ValueError("s4 fused dequant requires an even block")
    if tile is None:
        tile = max(_LANES, min(4096, _round_up(d, _LANES)))
    # a feature tile must hold whole codec blocks (the scale block
    # boundary) AND whole lanes; round up to the lcm of both
    lcm = block * _LANES // math.gcd(block, _LANES)
    tile = min(_round_up(int(tile), lcm), _LANES * block)
    if rows_tile is None:
        rows_tile = max(_SUBLANES, min(256, _round_up(n, _SUBLANES)))
    if fill is None:
        fill = jnp.asarray([n], jnp.int32)
    else:
        fill = jnp.asarray(fill, jnp.int32).reshape((1,))
    return _ragged_segment_sum_dequant_call(
        codes, scales, weights, fill, mode=mode, block=int(block),
        d=int(d), n_cohorts=int(n_cohorts), rows_tile=int(rows_tile),
        tile=int(tile), interpret=bool(interpret),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mode", "block", "d", "n_cohorts", "rows_tile", "tile", "interpret"
    ),
)
def _ragged_segment_sum_dequant_call(
    codes: Array,
    scales: Array,
    weights: Array,
    fill: Array,
    *,
    mode: str,
    block: int,
    d: int,
    n_cohorts: int,
    rows_tile: int,
    tile: int,
    interpret: bool,
) -> Array:
    n, ncodes = codes.shape
    nb = scales.shape[1]
    n_pad = _round_up(max(n, 1), rows_tile)
    d_pad = _round_up(max(d, 1), tile)
    c_pad = max(_SUBLANES, _round_up(n_cohorts, _SUBLANES))
    codes_per_tile = tile // 2 if mode == "s4" else tile
    cw_pad = (d_pad // tile) * codes_per_tile
    nb_pad = d_pad // block
    from ..parallel.quantization import _scales_lane_dense

    cp = jnp.zeros((n_pad, cw_pad), codes.dtype).at[:n, :ncodes].set(codes)
    sp = jnp.zeros((n_pad, nb_pad), jnp.float32).at[:n, :nb].set(
        scales.astype(jnp.float32)
    )
    # one 128-lane group of scales per feature tile (a (rows, tile //
    # block) block is not a legal Mosaic block shape)
    sp = _scales_lane_dense(sp, tile // block)
    ohp = jnp.zeros((n_pad, c_pad), jnp.float32).at[:n, :n_cohorts].set(
        weights.T.astype(jnp.float32)
    )
    if mode == "s4":
        fp_dtype = None
    elif mode == "int8":
        fp_dtype = None
    else:
        import ml_dtypes

        fp_dtype = (
            ml_dtypes.float8_e4m3fn if mode == "fp8"
            else ml_dtypes.float8_e5m2
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // rows_tile, d_pad // tile),
        in_specs=[
            pl.BlockSpec(
                (rows_tile, c_pad), lambda i, j, fill: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (rows_tile, codes_per_tile), lambda i, j, fill: (i, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (rows_tile, _LANES), lambda i, j, fill: (i, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (c_pad, tile), lambda i, j, fill: (0, j), memory_space=pltpu.VMEM
        ),
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_segment_sum_dequant_kernel,
            rows_tile=rows_tile, block=block,
            blocks_per_tile=tile // block, mode=mode, fp_dtype=fp_dtype,
        ),
        out_shape=jax.ShapeDtypeStruct((c_pad, d_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_segment_sum_dequant",
    )(fill, ohp, cp, sp)
    return out[:n_cohorts, :d]


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------

# Batcher network measured on v5e vs XLA sort at d=1M f32: n=8 1.06x,
# n=16 1.30x, n=32 1.54x, n=64 1.87x, n=128 2.9x — the win grows with n
# over this range (XLA's sort cost climbs faster than n·log²n). At small d
# the padding copy + grid overhead eat the win, so dispatch needs d large.
MAX_NETWORK_ROWS = 128
MIN_PALLAS_DIM = 256 * 1024
# MeaMed's fused kernel amortizes differently from the single-sort
# kernels: its XLA fallback moves a large multiple of the read-once
# traffic floor (sort + window + masked selection, ~4 passes by the
# kernel docstrings' bandwidth model) where the fused kernel reads the
# matrix exactly once. The committed floor is 1/4 of the generic
# MIN_PALLAS_DIM — a model estimate, not a chip measurement (ROADMAP D5).
MEAMED_MIN_DIM = 1 << 16


def s4_kernels_unsupported(cast: str) -> NotImplementedError:
    """The error every s4 Pallas switch raises on a TPU: the nibble
    kernels (pack/unpack through ``uint8``) do not lower on the Mosaic of
    jax 0.9.0 / libtpu 0.0.34. There is no fallback behind the opt-in —
    the XLA codec is the default and stays one ``unset`` away."""
    return NotImplementedError(
        f"the s4 Pallas kernels do not compile on this TPU toolchain "
        f"(Mosaic: 'Unsupported cast: {cast}'); unset "
        f"BYZPY_TPU_SUBINT8_PALLAS / BYZPY_TPU_RAGGED_PALLAS for s4 "
        f"traffic (the XLA codec serves it) — ROADMAP S4"
    )


def sharding_allows_pallas(x: Array) -> bool:
    """A ``pallas_call`` is an opaque custom call to GSPMD: feeding it a
    device-sharded operand forces XLA to all-gather the full matrix onto
    every chip, defeating the feature-axis sharding design (local matmul
    + psum of the (n, n) block — see ``ops.robust``'s module docstring).
    Dispatch is therefore allowed only when the trace-time mesh is empty
    or single-device, fully manual (inside ``shard_map`` shapes are
    already per-shard and the kernel runs on local data), or the spec is
    provably replicated under explicit-sharding axes. Auto-mode
    multi-device meshes hide the real spec at trace time, so they stay
    on XLA."""
    from jax.sharding import AxisType

    sharding = jax.typeof(x).sharding
    mesh = sharding.mesh
    if mesh.size <= 1:  # no mesh in scope (size 0) or one device
        return True
    axis_types = set(mesh.axis_types)
    if axis_types == {AxisType.Manual}:
        return True
    if AxisType.Auto in axis_types:
        return False
    return all(p is None for p in sharding.spec)


def use_pallas_for(n: int, d: int, *, min_dim: Optional[int] = None) -> bool:
    """The shape-and-backend part of :func:`pallas_serves`: true when the
    Pallas path should serve an ``(n, d)`` matrix on this backend.
    ``min_dim`` overrides the generic dispatch floor for kernels with a
    different amortization profile (``MEAMED_MIN_DIM``).
    ``BYZPY_TPU_PALLAS`` — the only environment variable the dispatch
    reads — forces the answer: ``0`` never, ``1`` wherever ``n`` fits
    the network (any ``d``, any backend: how the CPU tests and the toy
    cell reach the interpreted kernels), anything else this rule."""
    import os

    flag = os.environ.get("BYZPY_TPU_PALLAS", "auto")
    if flag == "0":
        return False
    if flag == "1":
        return n <= MAX_NETWORK_ROWS
    floor = MIN_PALLAS_DIM if min_dim is None else min_dim
    return _on_tpu() and n <= MAX_NETWORK_ROWS and d >= floor


def pallas_serves(
    x: Array,
    *,
    stream: bool = False,
    min_dim: int = MIN_PALLAS_DIM,
    max_dim: Optional[int] = None,
) -> bool:
    """THE dispatch gate: does the Pallas route serve this array?

    ``x`` is an ``(n, d)`` matrix, or with ``stream`` a ``(K, n, d)``
    stack of rounds (a concrete array, a tracer or a
    ``ShapeDtypeStruct``: only its type is read). True when it is a
    float32 / bfloat16 / float16 array of that rank, ``n`` fits the
    sorting network (``MAX_NETWORK_ROWS``), ``d`` is at or above the
    family's floor (``min_dim``; and at most ``max_dim`` where a family
    has a cap: MeaMed passes both of its own) on a TPU — or
    ``BYZPY_TPU_PALLAS`` forces the route, see :func:`use_pallas_for` —
    and the operand is not device-sharded
    (:func:`sharding_allows_pallas`). Every public entry point of
    ``ops.robust``, ``ops.preagg`` and the pre-aggregators asks this
    and nothing else, in its Python wrapper, so the answer is a fact of
    the call and never of an inner trace
    (``tests/test_kernel_route.py`` holds both)."""
    return bool(
        x.ndim == (3 if stream else 2)
        and x.dtype in _KERNEL_DTYPES
        and use_pallas_for(x.shape[-2], x.shape[-1], min_dim=min_dim)
        and (max_dim is None or x.shape[-1] <= max_dim)
        and sharding_allows_pallas(x)
    )


def targets_tpu() -> bool:
    """Whether programs dispatched now run on a TPU — the backend
    question for callers outside the kernel modules that choose between
    two XLA programs by backend and have no array to show the gate (the
    coordinate-wise aggregators' ragged sort strategy)."""
    return _on_tpu()


# The widest feature tile the stream kernels' heuristics try
# (``_auto_sort_tile``, ``_auto_selection_tile``); every narrower
# candidate divides it (``tests/test_kernel_route.py``).
_WIDEST_TILE = 16384


def aligned_width(n: int, d: int) -> int:
    """The column count at which to allocate an ``(n, ·)`` float matrix
    with ``d`` real columns so that a stream kernel reads it in place.

    ``d`` where :func:`pallas_serves` refuses an ``(n, d)`` float32
    matrix here; else ``d`` rounded up to the widest candidate tile, so
    that whichever tile a kernel's heuristic can afford divides the
    width and its wrapper takes the ``xp = xs`` path instead of a
    zero-padded copy of the whole matrix.
    The caller keeps the extra columns exactly zero (every shipped
    aggregator maps all-zero columns to zero and leaves row norms and
    Gram blocks unchanged) and cuts the result back to ``d``.

    A rounded-up width is a multiple of 1024, so a row of it FOLDS:
    ``(width / 128, 128)`` is whole (8, 128) tiles of f32, the same
    bytes in the same order as the flat row. A caller that keeps its
    rows folded, stacked ``(n, width / 128, 128)``, writes and reads a
    row as whole tiles, and the sort family's kernel
    (:func:`sorted_reduce_stream_pallas`) reads that stack as it is; as
    rows of an ``(n, width)`` matrix they are one sublane of every tile
    each (``docs/performance.md``, "A folded row")."""
    if not pallas_serves(jax.ShapeDtypeStruct((n, d), jnp.float32)):
        return d
    return _round_up(d, _WIDEST_TILE)


__all__ = [
    "sort_columns",
    "median_pallas",
    "trimmed_mean_pallas",
    "weighted_center_step_pallas",
    "gram_pallas",
    "pairwise_sq_dists_pallas",
    "meamed_stream_pallas",
    "arc_selection_mean_stream_pallas",
    "clip_selection_mean_stream_pallas",
    "nnm_pallas",
    "nnm_stream_pallas",
    "nnm_selection_mean_stream_pallas",
    "ragged_segment_sum_dequant_pallas",
    "ragged_segment_sum_pallas",
    "s4_kernels_unsupported",
    "selection_mean_from_gram_pallas",
    "selection_mean_pallas",
    "sorted_reduce_stream_pallas",
    "selection_mean_stream_pallas",
    "sharding_allows_pallas",
    "use_pallas_for",
    "pallas_serves",
    "targets_tpu",
    "aligned_width",
]
