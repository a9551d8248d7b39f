"""Gradient <-> matrix conversion utilities.

The reference framework flattens lists of per-node gradient tensors into an
``(n, d)`` matrix backed by POSIX shared memory before fanning work out to
pool workers (ref: ``byzpy/aggregators/coordinate_wise/_tiling.py:18-38``,
``byzpy/engine/storage/shared_store.py``).  On TPU there is no host-side
shared-memory dance: gradients are JAX pytrees (or arrays) and the stacked
matrix is a single device array that jitted aggregation kernels consume
directly — sharding it over a mesh replaces chunking it over workers.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


def stack_gradients(
    gradients: Sequence[Any] | jnp.ndarray,
) -> Tuple[jnp.ndarray, Callable[[jnp.ndarray], Any]]:
    """Stack a sequence of gradient pytrees/arrays into an ``(n, d)`` matrix.

    Accepts:

    * a sequence of same-structure pytrees (dicts/lists of arrays, flax
      parameter trees, plain arrays of any rank), or
    * an already-stacked 2-D array (returned unchanged).

    Returns ``(matrix, unravel)`` where ``unravel(row)`` maps a flat ``(d,)``
    vector back to the structure/shape of a single input gradient.
    """
    if isinstance(gradients, jnp.ndarray) or hasattr(gradients, "ndim"):
        arr = jnp.asarray(gradients)
        if arr.ndim != 2:
            raise ValueError(
                f"stacked gradient array must be 2-D (n, d); got shape {arr.shape}"
            )
        return arr, lambda row: row
    if len(gradients) == 0:
        raise ValueError("gradients must be a non-empty sequence")

    flat0, unravel = ravel_pytree(gradients[0])
    d = flat0.shape[0]
    rows = [flat0]
    for g in gradients[1:]:
        flat, _ = ravel_pytree(g)
        if flat.shape[0] != d:
            raise ValueError(
                f"all gradients must flatten to the same length (got {flat.shape[0]} != {d})"
            )
        rows.append(flat)
    matrix = jnp.stack(rows, axis=0)
    if not jnp.issubdtype(matrix.dtype, jnp.floating):
        matrix = matrix.astype(jnp.float32)
    return matrix, unravel


def unstack_rows(matrix: jnp.ndarray, unravel: Callable[[jnp.ndarray], Any]) -> List[Any]:
    """Split an ``(n, d)`` matrix back into a list of per-node gradients."""
    return [unravel(matrix[i]) for i in range(matrix.shape[0])]


def tree_size(tree: Any) -> int:
    """Total number of elements across all leaves of a pytree."""
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def ravel_pytree_fn(
    example: Any,
) -> Tuple[Callable[[Any], jnp.ndarray], Callable[[jnp.ndarray], Any]]:
    """``(ravel, unravel)`` closures for pytrees shaped like ``example``.

    Both are trace-safe, so jitted training steps can flatten per-node
    gradient trees into rows of the aggregation matrix and back.
    """
    _, unravel = ravel_pytree(example)

    def ravel(tree: Any) -> jnp.ndarray:
        return ravel_pytree(tree)[0]

    return ravel, unravel


# A folded row is (width / 128, 128) in whole (8, 128) tiles: 1024 columns.
_TILE = 1024


def _tile_order(shape: Tuple[int, ...]) -> Tuple[int, int] | None:
    """``(R, C)`` where a leaf of this shape lies on the TPU as (8, 128)
    tiles of an ``(R, C)`` matrix that a row-major flattening would have
    to relay: its minor dimension ``C`` is more than one tile wide, and
    its other dimensions together are whole tiles high. ``None``
    elsewhere (a row-major leaf whose ``C`` is 128 is those tiles
    already; a narrower one has no dense tiles to keep)."""
    if len(shape) < 2:
        return None
    cols = shape[-1]
    rows = math.prod(shape[:-1])
    if cols % 128 or cols <= 128 or rows % 8:
        return None
    return rows, cols


def _is_tiles(shape: Tuple[int, ...]) -> bool:
    """Whether a tile leaf of this shape and its stretch of a folded row
    are the same (8, 128) tiles in the same order, so that one is a view
    of the other: it keeps the order of its tiles (:func:`_tile_order`),
    or it is row-major, one tile wide and whole tiles high. (A narrower
    leaf is padded to 128 lanes on the TPU: its stretch of the row has to
    be relaid whichever side moves.)"""
    if _tile_order(shape) is not None:
        return True
    return len(shape) >= 2 and shape[-1] == 128 and math.prod(shape[:-1]) % 8 == 0


def tile_view(leaf: jnp.ndarray) -> jnp.ndarray:
    """``leaf`` as ``(size / 1024, 8, 128)``, its (8, 128) tiles in the
    order a folded row holds them (:func:`row_layout`): on the TPU a view
    of the same bytes, no copy. A leaf that is not such tiles
    (:func:`_is_tiles`) comes back as it is."""
    shape = tuple(leaf.shape)
    if not (leaf.size and _is_tiles(shape)):  # (such tiles are whole multiples of 1024)
        return leaf
    order = _tile_order(shape)
    if order is not None:
        rows, cols = order
        leaf = leaf.reshape(rows // 8, 8, cols // 128, 128).transpose(0, 2, 1, 3)
    return leaf.reshape(-1, 8, 128)


def tile_views(tree: Any, beside: Any = None) -> Tuple[Any, Any]:
    """Every leaf of ``tree`` as its :func:`tile_view`, and ``beside`` as
    it is, the views and ``beside`` (nothing else) behind ONE
    ``optimization_barrier``. Without a barrier the compiler moves a
    unary op on a view (momentum times its decay) to the leaf's side of
    the view, where it becomes a pass of its own. With ``beside`` (what
    the views are about to be read with: the row) in the same barrier
    the views exist only once it does, so nothing of them is fetched into
    fast memory while the loops that make the row still run (PR 42: a
    barrier of the views alone cost the Nemotron cell's loops 1.5 %). A
    leaf that has no view is handed on outside the barrier."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    views = [tile_view(leaf) for leaf in leaves]
    seen = [at for at, (view, leaf) in enumerate(zip(views, leaves)) if view is not leaf]
    held, beside = jax.lax.optimization_barrier(([views[at] for at in seen], beside))
    for at, view in zip(seen, held):
        views[at] = view
    return treedef.unflatten(views), beside


def leaf_view(tiles: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """:func:`tile_view`'s inverse: the leaf of ``shape`` whose view
    ``tiles`` is (``tiles`` itself where the leaf has no such view)."""
    shape = tuple(shape)
    if tuple(tiles.shape) == shape:
        return tiles
    order = _tile_order(shape)
    if order is not None:
        rows, cols = order
        tiles = tiles.reshape(rows // 8, cols // 128, 8, 128).transpose(0, 2, 1, 3)
    return tiles.reshape(shape)


@dataclass(frozen=True)
class RowLayout:
    """Where each leaf of a parameter tree sits in a ``width``-column row
    of the round's gradient matrix (:func:`row_layout`)."""

    width: int
    d: int
    dtype: Any
    offsets: Tuple[int, ...]  # first column of each piece ``place`` returns
    place: Callable[..., List[jnp.ndarray]]
    ravel: Callable[..., jnp.ndarray]
    unravel: Callable[[jnp.ndarray], Any]
    # ``unravel`` with every leaf that has a tile_view as that view; None
    # where rows are not folded (no leaf is whole tiles of such a row)
    unravel_tiles: Optional[Callable[[jnp.ndarray], Any]] = None

    @property
    def tile_leaves(self) -> int:
        """Leaves placed on their own (every piece but the last)."""
        return len(self.offsets) - 1

    @property
    def placed_share(self) -> float:
        """Share of the ``d`` real columns written leaf by leaf."""
        return self.offsets[-1] / self.d if self.d else 0.0


def row_layout(example: Any, width: int, *, folded: bool) -> RowLayout:
    """The order of a row's columns for trees shaped like ``example``.

    A row is ``width >= d`` columns: the ``d`` parameters first, an
    exactly-zero tail after them. Where rows are not ``folded`` the order
    is ``ravel_pytree``'s and the row is one piece. Where they are (a row
    is kept ``(width / 128, 128)``, whole (8, 128) tiles), every *tile
    leaf* (size a multiple of 1024) comes first, in tree order, each at
    its running offset (a multiple of 1024 too, so a leaf is whole tiles
    of the row), and all other leaves follow, ravelled in tree order
    together with the tail. A tile leaf ``(..., C)`` with ``C`` a multiple
    of 128 above 128 keeps the order of its TPU tiles (of 8 rows x 128
    columns, row of tiles after row of tiles), every other one is
    row-major: either way the bytes of the gradient as the backward pass
    leaves them, so a worker's loop writes the leaf into its place with
    no relayout and no row-wide ``concatenate`` in front.

    The order is fixed by the tree and ``width`` alone, the same for
    every row, and its two maps are inverse: ``unravel(ravel(t)[:d])`` is
    ``t``. ``place(tree, cast=None)`` returns the row's pieces as 1-D
    arrays (first columns: ``offsets``; the last piece is the ravelled
    rest with the tail), ``ravel`` joins them into the ``(width,)`` row,
    and ``unravel`` takes the first ``d`` columns back to the tree.
    ``unravel_tiles`` is ``unravel`` with every leaf that is whole tiles of
    the row in the row's own order (:func:`tile_view`) left as that view,
    ``(size / 1024, 8, 128)``: a slice of the folded row along its major
    dimension, which a reader takes in place, where ``unravel``'s slice of
    the flat row stands before a change of layout and is copied out first
    (``None`` where rows are not folded). All are trace-safe.
    """
    leaves, treedef = jax.tree_util.tree_flatten(example)
    shapes = [tuple(leaf.shape) for leaf in leaves]
    sizes = [math.prod(shape) for shape in shapes]
    dtypes = [leaf.dtype for leaf in leaves]
    del leaves  # the closures below outlive this call: they keep shapes, not arrays
    d = sum(sizes)
    dtype = jnp.result_type(*dtypes) if dtypes else jnp.float32
    if width < d:
        raise ValueError(f"a row of {width} columns cannot hold {d} parameters")
    tile_at = [k for k, size in enumerate(sizes) if folded and size and size % _TILE == 0]
    rest_at = [k for k in range(len(sizes)) if k not in tile_at]
    offsets = tuple(itertools.accumulate((sizes[k] for k in tile_at), initial=0))
    placed = offsets[-1]
    # (leaf, first column, its (R, C) where it keeps the order of its tiles)
    tiles = [(k, first, _tile_order(shapes[k])) for k, first in zip(tile_at, offsets)]
    _, unravel_rest = ravel_pytree([jnp.zeros(shapes[k], dtypes[k]) for k in rest_at])

    def place(tree: Any, cast: Any = None) -> List[jnp.ndarray]:
        got = treedef.flatten_up_to(tree)
        pieces = []
        for k, _, order in tiles:
            leaf = got[k]
            if order is not None:
                rows, cols = order
                leaf = leaf.reshape(rows // 8, 8, cols // 128, 128).transpose(0, 2, 1, 3)
            pieces.append(leaf.reshape(-1).astype(cast or dtype))
        rest = [got[k] for k in rest_at]
        if width != d:
            # the zero tail rides the ravel's own concatenate (padding the
            # ravelled row afterwards costs a copy of it)
            rest.append(jnp.zeros((width - d,), dtype))
        flat = ravel_pytree(rest)[0]
        pieces.append(flat if cast is None else flat.astype(cast))
        return pieces

    def ravel(tree: Any, cast: Any = None) -> jnp.ndarray:
        pieces = place(tree, cast)
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    def unravel(flat: jnp.ndarray) -> Any:
        got: List[Any] = [None] * len(shapes)
        for k, first, order in tiles:
            leaf = flat[first : first + sizes[k]]
            if order is not None:
                rows, cols = order
                leaf = leaf.reshape(rows // 8, cols // 128, 8, 128).transpose(0, 2, 1, 3)
            got[k] = leaf.reshape(shapes[k]).astype(dtypes[k])
        for k, leaf in zip(rest_at, unravel_rest(flat[placed:d])):
            got[k] = leaf.astype(dtypes[k])
        return jax.tree_util.tree_unflatten(treedef, got)

    def unravel_tiles(flat: jnp.ndarray) -> Any:
        folded_row = flat.reshape(-1, 8, 128)
        cut = [folded_row[first // _TILE : (first + sizes[k]) // _TILE] for k, first, _ in tiles]
        # What is no tiles of the row has to be relaid, and is cut out of the
        # row FIRST: behind a barrier, or the compiler turns the cut of a leaf
        # `(R, C)` with `C` a divisor of the width into a relayout of the whole
        # row to `C` columns (of 128 lanes each) and a cut of that
        apart = [at for at, (k, _, _) in enumerate(tiles) if not _is_tiles(shapes[k])]
        held = jax.lax.optimization_barrier(([cut[at] for at in apart], flat[placed:d]))
        for at, piece in zip(apart, held[0]):
            cut[at] = piece.reshape(shapes[tiles[at][0]])
        got: List[Any] = [None] * len(shapes)
        for (k, _, _), leaf in zip(tiles, cut):
            got[k] = leaf.astype(dtypes[k])
        for k, leaf in zip(rest_at, unravel_rest(held[1])):
            got[k] = leaf.astype(dtypes[k])
        return jax.tree_util.tree_unflatten(treedef, got)

    return RowLayout(width=width, d=d, dtype=dtype, offsets=tuple(offsets),
                     place=place, ravel=ravel, unravel=unravel,
                     unravel_tiles=unravel_tiles if folded else None)
