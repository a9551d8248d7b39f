"""Block-causal grouped-query attention of one sequence as Pallas TPU
kernels: a forward kernel and the two kernels of its backward.

``softmax(scale q k^T + mask) v``, the mask causal (``j <= i``) or causal
inside a sliding window (``0 <= i - j < W``), for ``H`` query heads that share
``kv_heads`` key/value heads, on the projections as they leave the matrix
products: ``q (T, H * qk_dim)``, ``k (T, kv_heads * qk_dim)``,
``v (T, kv_heads * v_dim)``, the output ``(T, H * v_dim)``. Queries and keys
have one width a head and values (with the output, its cotangent and the
softmax's row sums) another, each whole lanes: latent attention's 192 / 128
comes with its queries and keys zero-padded to 256 (a contraction over 192
costs the 128-wide MXU two passes as 256 does) and its values at 128, so
``p v``, ``do v^T`` and ``p^T do`` are half what a padded value would cost.
A head NARROWER than a lane tile (64, 32) is taken as it is: such heads
lie two (four) to a tile, a grid step is the key/value heads of one tile
with all their query heads, and a query head's tile is turned inside the
kernel until its values stand under the lanes of the key/value head it
reads, every other lane zero (:func:`_head`), so a product with the whole
key or value block serves that head alone. Nothing is padded, transposed
or repeated in HBM on the way in or out.

* The grid of every kernel is (key/value head, pair), and the pairs are the
  (query block, key block) pairs AT OR UNDER the diagonal, listed in Python
  and handed to the index maps as prefetched scalars: a pair wholly above
  the diagonal is never visited, and the mask is computed only in the pairs
  the diagonal crosses. With a ``window`` shorter than the sequence the pairs
  wholly OLDER than the window are left out the same way, the window's edge is
  masked only in the pairs it crosses, a kernel lowers one body for each
  combination of the two masks that its pairs hold, and the calls carry names
  of their own (``window_attention_fwd / _dq / _dkv``: the same bodies, another
  count of operations). ``window=None`` is the program it always was.
* Scores, probabilities, the running maximum, the running sum and the
  output accumulator live in VMEM (online softmax). What the forward writes
  to HBM is the output and ONE log-sum-exp a query row a head,
  ``(kv_heads, H / kv_heads, T)`` float32.
* The query heads of a group are folded into the rows of the query block,
  ``(H / kv_heads * block_q, head_dim)`` against ``(block_k, head_dim)``, so
  a key/value block is loaded once for all of them and the MXU's weights
  (the key block) serve sixteen times the rows. Where a key/value head has
  ONE query head (latent attention trained in its uncompressed form) there
  is nothing to fold, and the query block itself is four times as long
  (:func:`_blocks`).
* The backward recomputes a pair's probabilities from q, k and the
  log-sum-exp: one kernel for dq (the forward's walk), one for dk and dv
  (key block outermost, the sum over a group's query heads taken inside
  the kernel, in the transposed form ``k q^T`` so that no tile is
  transposed).

Precision: everything stored or carried is the operands' dtype (float32
where the model is float32) or float32; exponentials, maxima and sums are
float32. The products are ``lax.dot_general`` at ``Precision.DEFAULT``
with a float32 result, which is what XLA gives the same contractions of
the ``lax.map`` route on a TPU: the MXU takes bfloat16 operands and
accumulates in float32.

Mosaic on a TPU, the Pallas interpreter on a CPU
(:func:`~byzpy_tpu.ops.pallas_kernels._resolve_interpret`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

Array = jnp.ndarray

_LANES = _pk._LANES
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_FIRST, _LAST, _MASKED, _EDGED = 1, 2, 4, 8  # a pair's flags
# rows of the folded query block that one pass of a step's loop handles:
# the score tile alive at once is (_CHUNK_ROWS, block_k) float32
_CHUNK_ROWS = 1024
# The blocks (:func:`_blocks`), measured on a v5e at 4096 positions.
# Sixteen query heads a group at head_dim 128 (32 / 2 heads, PR 33), query
# block 256: forward 2.13 ms at key block 512, 1.31 at 1024, 1.62 at 2048;
# dq 1.44 at 512, 1.53 at 1024; dk / dv 1.71 at 512, 3.32 at 1024. ONE
# query head a group at head_dim 256 (20 / 20 heads, latent attention, PR
# 34), query block x key block: forward 2.47 at 256 x 1024, 1.61 at 512 x
# 1024, 1.45 at 1024 x 1024, 1.69 at 2048 x 1024; dq 2.76 at 256 x 512,
# 1.93 at 1024 x 512, 1.86 at 1024 x 1024, 2.23 at 2048 x 512; dk / dv 2.88
# at 256 x 512, 2.48 at 512 x 512, 2.39 at 1024 x 1024, 2.85 at 2048 x 512.
# EIGHT query heads a group at head_dim 256 (16 / 2 heads, PR 39): forward
# 1.19 at 512 x 1024 (the rule's), 1.13 at 512 x 512, 1.14 at 256 x 512,
# 1.19 at 256 x 1024, 1.21 at 128 x 1024, 1.24 at 1024 x 512, 1.44 at 512 x
# 2048; dq 1.40 at 512 x 512 (the rule's, the least), 1.41 at 256 x 512, 1.44
# at 128 x 512, 1.51 at 512 x 1024, 1.55 at 512 x 256; dk / dv 1.72 at 512 x
# 512 (the rule's, the least), 1.73 at 256 x 512, 1.79 at 512 x 256, 1.85 at
# 128 x 512, 2.78 at 512 x 1024, 2.79 at 1024 x 512; a query block of 1024
# (8,192 folded rows of 256) does not fit VMEM in the forward or in dq. The
# rule stands: its forward is within a twentieth of the least read.
# ONE query head a group at 256 / 128 (32 / 32 heads: keys of 192 padded to
# 256, values of 128) at 1024 POSITIONS, so that a block of 1024 is the whole
# sequence and every pair is on the diagonal (PR 41): forward 0.385 at 1024 x
# 1024 (the rule's), 0.384 at 512 x 1024 (the least), 0.390 at 512 x 512, 0.395
# at 256 x 1024, 0.419 at 1024 x 512, 0.446 at 256 x 256, 0.82 at 1024 x 128;
# dq 0.288 at 1024 x 1024 (the rule's), 0.268 at 512 x 512 (the least), 0.299
# at 1024 x 512, 0.304 at 512 x 1024, 0.368 at 256 x 512; dk / dv 0.425 at
# 1024 x 1024 (the rule's), 0.398 at 512 x 1024 (the least), 0.402 at 256 x
# 1024, 0.405 at 512 x 512, 0.410 at 1024 x 512. With the values padded to 256
# as well: 0.391 / 0.333 / 0.427 at 1024 x 1024. The rule stands: each kernel
# is within a twelfth of its least, 1.5 ms of a step of 597 between them. These
# are host-clock times a call, dispatch included: inside the traced step the
# same three read 0.203 / 0.278 / 0.307 ms (the forward's 10.7 GFLOP at the
# published widths: 27 % of the peak; all three: 30.8 %), so a sweep at this
# length ranks tiles and does not time a kernel.
# HEADS NARROWER THAN A LANE TILE (PR 46): 32 query / 8 key-value heads of 64,
# two key/value heads a tile, so a grid step holds EIGHT query heads against a
# key block of 128 lanes, at 4096 positions (host-clock times a call, 16 tile
# pairs a kernel): forward 1.329 at 512 x 1024 (the rule's, the least with 1024 x
# 1024), 1.352 at 256 x 1024, 1.407 at 128 x 1024, 1.632 at 512 x 2048, 2.331 at
# 512 x 512, 4.39 at 512 x 256; dq 1.460 at 512 x 512 (the rule's, the least),
# 1.502 at 256 x 512, 1.540 at 1024 x 1024, 1.545 at 512 x 1024, 1.586 at 1024 x
# 512, 1.673 at 512 x 256; dk / dv 1.702 at 512 x 512 (the rule's, the least),
# 1.767 at 512 x 256, 1.803 at 256 x 512, 1.843 at 512 x 1024, 1.856 at 1024 x
# 512, 2.73 at 1024 x 1024, 3.17 at 512 x 2048. The rule stands as it is (the
# picks of eight heads a group). Forward and backward of one sequence: 4.52 ms
# as the kernels take the heads, 5.16 with q, k, v zero-padded to 128 a head
# beforehand and 5.71 with the pads and the slice counted (the form to beat),
# 28.1 through the `lax.map` route.
# SEVEN query heads a group at head_dim 128 (28 / 4 heads, PR 49) at 8192
# POSITIONS, causal | inside a window of 4096 (the same bodies; the windowed
# call walks 60 of the causal 72 pairs at 512 x 1024, and three quarters of the
# entries), host-clock ms a call: forward 3.93 | 3.35 at 512 x 1024 (the rule's),
# 3.86 | 3.31 at 1024 x 1024 (the least: 7,168 folded rows, past _FOLDED_ROWS), 3.99
# | 3.39 at 256 x 1024, 4.04 | 3.47 at 128 x 1024, 4.42 | 4.17 at 512 x 2048, 7.10 |
# 5.69 at 512 x 512, 7.18 | 5.77 at 256 x 512; dq 4.57 | 3.68 at 512 x 512 (the rule's,
# the least), 4.62 | 3.91 at 512 x 1024, 4.69 | 3.77 at 256 x 512, 4.68 | 3.93 at 256 x
# 1024, 4.94 | 3.98 at 128 x 512, 5.40 | 4.37 at 512 x 256; dk / dv 5.56 | 4.47 at 512 x
# 512 (the rule's, the least), 5.75 | 11.12 at 512 x 1024 (twice the causal time at
# that tile and NOT the walk's doing: its key-major list is 60 of the causal 72 pairs
# in 8 runs, a key block's run written once, 16 pairs on the diagonal and 8 on the
# edge, as at 512 x 512 where 108 of 136 pairs read 0.80; what Mosaic makes of three
# bodies on a (1024, 896) score tile is not measured, the rule does not pick it), 5.87 |
# 4.70 at 512 x 256, 5.92 | 4.74 at 256 x 512, 5.96 | 4.99 at 256 x 1024, 6.37 | 4.98 at
# 256 x 256, 6.53 | 5.23 at 128 x 512. Seven heads fold 7 x 512 = 3584 rows, which
# _CHUNK_ROWS does not divide: a step's loop takes them 896 at a time
# (:func:`_chunk_rows`). The rule stands as it is (the picks of eight heads a
# group): its forward is within a fiftieth of the least read. Forward and backward
# of one sequence by the rule: 14.56 ms causal, 12.38 windowed (0.85).
# All five regimes: a key block meets up to _FOLDED_ROWS rows of queries (a
# group's heads times the query block), and the backward's kernels, which
# hold two products' tiles a pair, keep heads x query block x key block
# within _BACKWARD_TILE.
_FOLDED_ROWS, _WIDEST_BLOCK, _BACKWARD_TILE = 4096, 1024, 2 ** 21
# head widths under a lane tile that the kernels take unpadded, 128 / width
# heads to a tile
_NARROW_HEADS = (64, 32)
_VMEM_LIMIT = 64 * 1024 * 1024


def causal_attention_serves(x: Array, head_dim: int, v_head_dim: Optional[int] = None) -> bool:
    """THE gate of the attention route: do the kernels serve a sequence
    whose activations are ``x``? On a TPU, for float32 / bfloat16
    activations, BOTH head widths in whole lanes (``head_dim`` of the
    queries and keys, ``v_head_dim`` of the values and the output; left
    out, the values are as wide as the keys) or both ONE width that lies
    two or four to a lane tile (``_NARROW_HEADS``; the caller's key/value
    heads must fill whole tiles, which :func:`causal_attention` checks: it
    is told their number, the gate is not), an operand that is not
    device-sharded (:func:`~byzpy_tpu.ops.pallas_kernels.
    sharding_allows_pallas`). Any length: the wrapper pads it to whole
    blocks. Asked once a call, in Python, by ``models.nemotron_h.
    gqa_attention`` (sixteen query heads a key/value head of 128),
    ``models.layers.mla_attention`` (one query head a key/value head:
    GLM-4.7-Flash's 256 / 256, whose q, k and v are born in these kernels'
    rows where the sequence is long against the latents, and 192 / 128
    with the queries and keys padded to 256, cut and joined on three
    axes), ``models.qwen3_next.gated_attention`` (eight of 256)
    ``models.lfm2_moe.gqa_attention`` (four of 64, two key/value heads a
    tile) and ``models.smallthinker.attention`` (seven of 128, with a window
    in six blocks of eight; :func:`_blocks` has what each regime measured);
    reads no environment variable."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    return bool(
        _pk._on_tpu()
        and x.dtype in (jnp.float32, jnp.bfloat16)
        and ((head_dim % _LANES == 0 and v_head_dim % _LANES == 0)
             or (head_dim in _NARROW_HEADS and v_head_dim == head_dim))
        and _pk.sharding_allows_pallas(x)
    )


def _blocks(t: int, per: int, *, backward: bool) -> Tuple[int, int, int]:
    """``(padded length, block_q, block_k)`` for a sequence of ``t``
    positions whose key/value heads are read by ``per`` query heads each,
    for the forward kernel or the backward's two. The length in whole
    128s; the query block the widest of 1024 ... 128 that divides it and
    folds, with a group's heads, into at most ``_FOLDED_ROWS`` rows (256
    for sixteen heads a group, 1024 for one; a power of two: the mask
    reads a folded row's position with a bitwise and); the key block the
    widest of 1024 ... 128 that divides it, in the backward within
    ``_BACKWARD_TILE`` (512 for sixteen heads a group, 1024 for one). A
    sequence of 1024 positions with one query head a group (32 key/value
    heads of 256 / 128) is ONE pair of 1024 x 1024 a head in all three
    kernels, on the diagonal. The head widths do not enter: the tiles are
    counted in scores, and the widest head (256) fits every regime above.
    Where heads share a lane tile ``per`` counts the query heads of a grid
    step (a tile's key/value heads times their group: 8 for four heads of
    64 a group, 512 x 1024 forward and 512 x 512 backward). Seven heads a
    group get the same picks (3584 folded rows). A window does not enter:
    it shortens the list of pairs, not a pair."""
    t_pad = _pk._round_up(t, _LANES)
    sizes = (1024, 512, 256, 128)
    block_q = next(b for b in sizes
                   if t_pad % b == 0 and (per * b <= _FOLDED_ROWS or b == _LANES))
    widest = min(_WIDEST_BLOCK, _BACKWARD_TILE // (per * block_q)) if backward else _WIDEST_BLOCK
    block_k = next(b for b in sizes if t_pad % b == 0 and (b <= widest or b == _LANES))
    return t_pad, block_q, block_k


def _pairs(n_q: int, n_k: int, block_q: int, block_k: int, *, key_major: bool,
           window: Optional[int] = None):
    """The (query block, key block) pairs at or under the diagonal, as
    three int32 vectors: query block, key block, flags. Query-major (each
    query block's key blocks in turn) for the forward and dq; key-major
    for dk / dv. ``_FIRST`` / ``_LAST`` mark the ends of the outer block's
    run, ``_MASKED`` a pair the diagonal crosses. With a ``window`` (query
    ``i`` reads keys ``j`` with ``0 <= i - j < window``) the pairs wholly
    OLDER than the window are left out as those above the diagonal are: a
    query block's run starts at the key block the window of its first row
    reaches, a key block's run ends at the last query block whose window
    still reaches its last key; ``_EDGED`` marks a pair the window's edge
    crosses (some ``i - j >= window`` in it)."""
    runs = []
    if key_major:
        for kj in range(n_k):
            first_q = (kj * block_k) // block_q
            last_q = n_q - 1 if window is None else min(
                n_q - 1, ((kj + 1) * block_k - 1 + window - 1) // block_q)
            runs.append([(qi, kj) for qi in range(first_q, last_q + 1)])
    else:
        for qi in range(n_q):
            first_k = 0 if window is None else max(0, qi * block_q - (window - 1)) // block_k
            last_k = ((qi + 1) * block_q - 1) // block_k
            runs.append([(qi, kj) for kj in range(first_k, last_k + 1)])
    qs, ks, flags = [], [], []
    for run in runs:
        for i, (qi, kj) in enumerate(run):
            crossed = (kj + 1) * block_k - 1 > qi * block_q
            edged = window is not None and (qi + 1) * block_q - 1 - kj * block_k >= window
            qs.append(qi)
            ks.append(kj)
            flags.append((_FIRST if i == 0 else 0) | (_LAST if i == len(run) - 1 else 0)
                         | (_MASKED if crossed else 0) | (_EDGED if edged else 0))
    return tuple(np.asarray(a, np.int32) for a in (qs, ks, flags))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _eye():
    return (lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            == lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))


def _column_to_row(col, eye):
    """``(n, 1) -> (1, n)`` float32, ``n`` in whole 128s: each 128 rows
    spread along the lanes, kept on the diagonal (``eye``), summed over
    the rows."""
    parts = [
        jnp.sum(jnp.where(eye, jnp.broadcast_to(col[c:c + _LANES, :], (_LANES, _LANES)), 0.0),
                axis=0, keepdims=True)
        for c in range(0, col.shape[0], _LANES)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _row_to_column(row_ref, r, eye):
    """Row ``r`` of a ``(., n)`` block as ``(n, 1)``: :func:`_column_to_row`
    the other way, 128 lanes of the block at a time."""
    parts = [
        jnp.sum(jnp.where(eye, jnp.broadcast_to(row_ref[pl.ds(r, 1), c:c + _LANES],
                                                (_LANES, _LANES)), 0.0),
                axis=1, keepdims=True)
        for c in range(0, row_ref.shape[1], _LANES)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _heads(per: int, body, pack: int = 1):
    """``body(r)`` for every head of a group. Traced once and unrolled where
    it is lowered (so a head's lanes and rows are static slices to Mosaic,
    and one head's products hide the next head's exponentials): sixteen
    traced copies of every per-head body were most of what tracing and
    lowering the kernels cost a step's set-up. Heads that share a lane tile
    (``pack > 1``) are traced one by one: where a head lies in its tile is
    then a Python number."""
    if pack > 1:
        for r in range(per):
            body(r)
        return

    def step(r, carry):
        body(r)
        return carry

    lax.fori_loop(0, per, step, 0, unroll=True)


def _rows(r, block_q: int):
    """Head ``r``'s rows of a folded block."""
    return pl.ds(r * block_q, block_q)


def _lanes(r, head_dim: int):
    """Head ``r``'s lanes of a ``(block_q, per * head_dim)`` block."""
    return pl.ds(r * head_dim, head_dim)


def _lane_group(shape, head_dim: int):
    """Which head of its lane tile a lane belongs to (``head_dim`` a power
    of two under 128)."""
    return lax.shift_right_logical(
        lax.broadcasted_iota(jnp.int32, shape, 1), int(math.log2(head_dim)))


def _head(ref, r, width: int, per: int, pack: int):
    """Head ``r``'s ``(rows, width)`` tile of a block that holds a grid
    step's heads side by side. ``pack == 1``: its own lanes. Heads
    NARROWER than a lane tile lie ``pack`` to a tile of ``width`` = 128
    lanes, and so do the ``pack`` key/value heads of the step in the key
    and value blocks: head ``r`` reads key/value head ``r // (per / pack)``
    of them, so its tile is turned until its values stand under that
    head's lanes and every other lane is zero (float32). A product with
    the whole key (value) block then contracts over, or writes, that head
    alone: nothing is padded in HBM, and no value is sliced off a lane
    boundary."""
    if pack == 1:
        return ref[:, _lanes(r, width)]
    head_dim = width // pack
    tile = ref[:, pl.ds(r // pack * width, width)].astype(jnp.float32)
    want, has = r // (per // pack), r % pack
    if want != has:
        tile = pltpu.roll(tile, ((want - has) * head_dim) % width, 1)
    return jnp.where(_lane_group(tile.shape, head_dim) == want, tile, 0.0)


def _put_heads(dst_ref, tile_of, per: int, width: int, pack: int):
    """:func:`_head` the other way: ``tile_of(r)``, head ``r``'s ``(rows,
    width)`` result (float32; where heads share a tile, good under the
    lanes of the key/value head it read), into its place in a block that
    holds the step's heads side by side."""
    if pack == 1:
        def one(r):
            dst_ref[:, _lanes(r, width)] = tile_of(r).astype(dst_ref.dtype)

        _heads(per, one)
        return
    head_dim = width // pack
    for chunk in range(per // pack):
        out = None
        for has in range(pack):
            r = chunk * pack + has
            want, tile = r // (per // pack), tile_of(r)
            if want != has:
                tile = pltpu.roll(tile, ((has - want) * head_dim) % width, 1)
            tile = jnp.where(_lane_group(tile.shape, head_dim) == has, tile, 0.0)
            out = tile if out is None else out + tile
        dst_ref[:, pl.ds(chunk * width, width)] = out.astype(dst_ref.dtype)


def _fold(dst_ref, src_ref, per: int, block_q: int, head_dim: int, scale: Optional[float],
          pack: int = 1):
    """A ``(block_q, per / pack * head_dim)`` block, a step's heads side by
    side, into ``(per * block_q, head_dim)`` rows, head by head."""
    def one(r):
        part = _head(src_ref, r, head_dim, per, pack)
        if scale is not None:
            part = part.astype(jnp.float32) * scale
        dst_ref[_rows(r, block_q), :] = part.astype(dst_ref.dtype)

    _heads(per, one, pack)


def _visible(position, key, diagonal: bool, edge: Optional[int]):
    """The mask of a pair from its entries' positions and keys: under the
    diagonal where the pair crosses it, inside the window (``edge``: its
    width) where the pair crosses that."""
    seen = None
    if diagonal:
        seen = key <= position
    if edge is not None:
        inside = position - key < edge
        seen = inside if seen is None else seen & inside
    return seen


def _seen(rows0, n_rows: int, block_q: int, block_k: int, qi, kj, diagonal: bool = True,
          edge: Optional[int] = None):
    """The mask of ``n_rows`` folded rows from ``rows0`` against a key
    block: a folded row's position is ``qi * block_q`` + its row within
    its head."""
    row = rows0 + lax.broadcasted_iota(jnp.int32, (n_rows, block_k), 0)
    position = qi * block_q + (row & (block_q - 1))
    key = kj * block_k + lax.broadcasted_iota(jnp.int32, (n_rows, block_k), 1)
    return _visible(position, key, diagonal, edge)


def _chunk_rows(rows: int) -> int:
    """The rows of a folded block that one pass of a step's loop handles:
    ``_CHUNK_ROWS`` where they divide it; for a longer block that they do
    not divide (seven heads a group: 7 x 512 = 3584) its largest divisor in
    whole lane tiles under them (896); else the block at once."""
    if rows % _CHUNK_ROWS == 0 or rows < _CHUNK_ROWS:
        return min(rows, _CHUNK_ROWS)
    return next((c for c in range(_CHUNK_ROWS - _LANES, 0, -_LANES) if rows % c == 0), rows)


def _for_chunks(rows: int, body):
    """``body(first row, rows)`` over a folded block, :func:`_chunk_rows` at
    a time."""
    chunk = _chunk_rows(rows)
    if rows == chunk:
        body(0, rows)
    else:
        def step(c, carry):
            body(pl.multiple_of(c * chunk, chunk), chunk)
            return carry

        lax.fori_loop(0, rows // chunk, step, 0)


def _masked_or_not(flag, step, window: Optional[int] = None, kinds=None):
    """``step(diagonal, edge)``: the diagonal's pairs compute its mask, the
    pairs the window's edge crosses that edge's (``edge`` is the window
    there, else ``None``), the others carry no trace of either. ``kinds``
    (with a window): the flag combinations the call's pairs hold, so that
    no body is lowered for a combination that never comes."""
    if window is None:
        pl.when((flag & _MASKED) != 0)(lambda: step(True, None))
        pl.when((flag & _MASKED) == 0)(lambda: step(False, None))
        return
    for kind in kinds:
        pl.when((flag & (_MASKED | _EDGED)) == kind)(
            functools.partial(step, bool(kind & _MASKED), window if kind & _EDGED else None))


def _kinds(pairs):
    """The mask combinations among a call's pairs, in a fixed order."""
    return tuple(sorted({int(f) & (_MASKED | _EDGED) for f in pairs[2]}))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                qf_ref, m_ref, l_ref, acc_ref,
                *, per, qk_dim, v_dim, pack, scale, block_q, block_k, window=None, kinds=None):
    pair = pl.program_id(1)
    qi, kj, flag = qi_ref[pair], kj_ref[pair], flag_ref[pair]
    rows = per * block_q

    @pl.when((flag & _FIRST) != 0)
    def _():
        _fold(qf_ref, q_ref, per, block_q, qk_dim, scale, pack)
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(diagonal, edge):
        def body(rows0, chunk):
            sl = pl.ds(rows0, chunk)
            s = _dot(qf_ref[sl, :], k_ref[...], _NT)
            if diagonal or edge is not None:
                s = jnp.where(_seen(rows0, chunk, block_q, block_k, qi, kj, diagonal, edge),
                              s, -jnp.inf)
            m_prev = m_ref[sl, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row whose window starts past this key block has seen nothing
            # yet: its maximum is still -inf, and it takes 0 for the exponents
            m_at = m_new if edge is None else jnp.where(m_new == -jnp.inf, 0.0, m_new)
            alpha = jnp.exp(m_prev - m_at)
            p = jnp.exp(s - m_at)
            l_ref[sl, :] = alpha * l_ref[sl, :] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[sl, :] = alpha * acc_ref[sl, :] + _dot(p.astype(v_ref.dtype), v_ref[...], _NN)
            m_ref[sl, :] = m_new

        _for_chunks(rows, body)

    _masked_or_not(flag, step, window, kinds)

    @pl.when((flag & _LAST) != 0)
    def _():
        eye = _eye()

        def one(r):
            head = _rows(r, block_q)
            total = l_ref[head, :]
            if pack == 1:
                o_ref[:, _lanes(r, v_dim)] = (acc_ref[head, :] / total).astype(o_ref.dtype)
            lse_ref[pl.ds(r, 1), :] = _column_to_row(m_ref[head, :] + jnp.log(total), eye)

        _heads(per, one, pack)
        if pack > 1:
            _put_heads(o_ref, lambda r: acc_ref[_rows(r, block_q), :] / l_ref[_rows(r, block_q), :],
                       per, v_dim, pack)


def _grid_spec(pairs, kv_heads, in_specs, out_specs, scratch_shapes):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(kv_heads, len(pairs[0])),
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch_shapes)


def _specs(per: int, qk_dim: int, v_dim: int, block_q: int, block_k: int, pack: int = 1):
    """Block specs by the pair's query and key block: of the queries (and
    their cotangent), the keys, the values, the output (and its cotangent)
    and a per-row statistic. Queries and keys are ``qk_dim`` a head wide,
    values and the output ``v_dim`` (a lane TILE where ``pack`` heads
    share one)."""
    def query_side(width):
        return pl.BlockSpec((block_q, per // pack * width), lambda g, p, qi, kj, fl: (qi[p], g))

    def key_side(width):
        return pl.BlockSpec((block_k, width), lambda g, p, qi, kj, fl: (kj[p], g))

    row_spec = pl.BlockSpec((None, per, block_q), lambda g, p, qi, kj, fl: (g, 0, qi[p]))
    return query_side(qk_dim), key_side(qk_dim), key_side(v_dim), query_side(v_dim), row_spec


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _cost(pairs, kv_heads, per, block_q, block_k, widths, arrays):
    """``widths``: what each of the kernel's products contracts over or
    writes a score for, a head (``qk_dim`` for a product with queries or
    keys, ``v_dim`` for one with values or the output's cotangent)."""
    tile = len(pairs[0]) * kv_heads * per * block_q * block_k
    return pl.CostEstimate(
        flops=2 * tile * sum(widths), transcendentals=tile,
        bytes_accessed=sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in arrays))


def _edges(pairs, window):
    """What a windowed call's kernel is told beside the sizes; a causal
    call's kernel is told nothing (and is the kernel it always was)."""
    return {} if window is None else dict(window=window, kinds=_kinds(pairs))


def _widths(q, k, v, kv_heads):
    """``(grid steps, query heads a step, qk_dim, v_dim, pack)`` of a call.
    A step is a key/value head with its query heads and the widths are a
    head's; where heads are narrower than a lane tile a step is the
    ``pack`` key/value heads of one tile with all their query heads, and
    the widths are the tile's."""
    qk_dim, v_dim = k.shape[1] // kv_heads, v.shape[1] // kv_heads
    per = q.shape[1] // (kv_heads * qk_dim)
    pack = _LANES // qk_dim if qk_dim < _LANES else 1
    return kv_heads // pack, per * pack, qk_dim * pack, v_dim * pack, pack


def _fwd_parts(q, k, v, *, kv_heads, scale, block_q, block_k, interpret,
               window=None):
    """What a call of the forward kernel is made of, causal or windowed: the
    kernel, ``pallas_call``'s other arguments, the operands."""
    t = q.shape[0]
    kv_heads, per, qk_dim, v_dim, pack = _widths(q, k, v, kv_heads)
    rows = per * block_q
    pairs = _pairs(t // block_q, t // block_k, block_q, block_k, key_major=False,
                   window=window)
    q_spec, k_spec, v_spec, o_spec, row_spec = _specs(per, qk_dim, v_dim, block_q, block_k, pack)
    out_shape = (jax.ShapeDtypeStruct((t, kv_heads * (per // pack) * v_dim), q.dtype),
                 jax.ShapeDtypeStruct((kv_heads, per, t), jnp.float32))
    kernel = functools.partial(
        _fwd_kernel, per=per, qk_dim=qk_dim, v_dim=v_dim, pack=pack,
        scale=scale, block_q=block_q, block_k=block_k,
        **_edges(pairs, window))
    return kernel, dict(
        out_shape=out_shape,
        grid_spec=_grid_spec(
            pairs, kv_heads, [q_spec, k_spec, v_spec], (o_spec, row_spec),
            [pltpu.VMEM((rows, qk_dim), q.dtype), pltpu.VMEM((rows, 1), jnp.float32),
             pltpu.VMEM((rows, 1), jnp.float32), pltpu.VMEM((rows, v_dim), jnp.float32)]),
        compiler_params=_params(),
        cost_estimate=_cost(pairs, kv_heads, per, block_q, block_k, (qk_dim, v_dim),
                            (q, k, v) + out_shape),
        interpret=interpret), (*pairs, q, k, v)


def _causal_attention_fwd_call(q, k, v, **sizes):
    kernel, rest, operands = _fwd_parts(q, k, v, **sizes)
    return pl.pallas_call(kernel, name="causal_attention_fwd", **rest)(*operands)


def _window_attention_fwd_call(q, k, v, *, window, **sizes):
    kernel, rest, operands = _fwd_parts(q, k, v, window=window, **sizes)
    return pl.pallas_call(kernel, name="window_attention_fwd", **rest)(*operands)


# ---------------------------------------------------------------------------
# backward: dq
# ---------------------------------------------------------------------------


def _dq_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, qf_ref, dof_ref, lse_col_ref, delta_col_ref, acc_ref,
               *, per, qk_dim, v_dim, pack, scale, block_q, block_k, window=None, kinds=None):
    pair = pl.program_id(1)
    qi, kj, flag = qi_ref[pair], kj_ref[pair], flag_ref[pair]
    rows = per * block_q

    @pl.when((flag & _FIRST) != 0)
    def _():
        _fold(qf_ref, q_ref, per, block_q, qk_dim, scale, pack)
        _fold(dof_ref, do_ref, per, block_q, v_dim, None, pack)
        eye = _eye()

        def one(r):
            lse_col_ref[_rows(r, block_q), :] = _row_to_column(lse_ref, r, eye)
            delta_col_ref[_rows(r, block_q), :] = _row_to_column(delta_ref, r, eye)

        _heads(per, one, pack)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(diagonal, edge):
        def body(rows0, chunk):
            sl = pl.ds(rows0, chunk)
            s = _dot(qf_ref[sl, :], k_ref[...], _NT)
            if diagonal or edge is not None:
                s = jnp.where(_seen(rows0, chunk, block_q, block_k, qi, kj, diagonal, edge),
                              s, -jnp.inf)
            p = jnp.exp(s - lse_col_ref[sl, :])
            dp = _dot(dof_ref[sl, :], v_ref[...], _NT)
            ds = p * (dp - delta_col_ref[sl, :])
            acc_ref[sl, :] = acc_ref[sl, :] + _dot(ds.astype(k_ref.dtype), k_ref[...], _NN)

        _for_chunks(rows, body)

    _masked_or_not(flag, step, window, kinds)

    @pl.when((flag & _LAST) != 0)
    def _():
        _put_heads(dq_ref, lambda r: acc_ref[_rows(r, block_q), :] * scale, per, qk_dim, pack)


def _dq_parts(q, k, v, do, lse, delta, *, kv_heads, scale, block_q,
              block_k, interpret, window=None):
    """What a call of the dq kernel is made of, causal or windowed: the
    kernel, ``pallas_call``'s other arguments, the operands."""
    t = q.shape[0]
    kv_heads, per, qk_dim, v_dim, pack = _widths(q, k, v, kv_heads)
    rows = per * block_q
    pairs = _pairs(t // block_q, t // block_k, block_q, block_k, key_major=False,
                   window=window)
    q_spec, k_spec, v_spec, o_spec, row_spec = _specs(per, qk_dim, v_dim, block_q, block_k, pack)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    kernel = functools.partial(
        _dq_kernel, per=per, qk_dim=qk_dim, v_dim=v_dim, pack=pack,
        scale=scale, block_q=block_q, block_k=block_k,
        **_edges(pairs, window))
    return kernel, dict(
        out_shape=out_shape,
        grid_spec=_grid_spec(
            pairs, kv_heads, [q_spec, k_spec, v_spec, o_spec, row_spec, row_spec], q_spec,
            [pltpu.VMEM((rows, qk_dim), q.dtype), pltpu.VMEM((rows, v_dim), do.dtype),
             pltpu.VMEM((rows, 1), jnp.float32), pltpu.VMEM((rows, 1), jnp.float32),
             pltpu.VMEM((rows, qk_dim), jnp.float32)]),
        compiler_params=_params(),
        cost_estimate=_cost(pairs, kv_heads, per, block_q, block_k, (qk_dim, v_dim, qk_dim),
                            (q, k, v, do, lse, delta, out_shape)),
        interpret=interpret), (*pairs, q, k, v, do, lse, delta)


def _causal_attention_dq_call(q, k, v, do, lse, delta, **sizes):
    kernel, rest, operands = _dq_parts(q, k, v, do, lse, delta, **sizes)
    return pl.pallas_call(kernel, name="causal_attention_dq", **rest)(*operands)


def _window_attention_dq_call(q, k, v, do, lse, delta, *, window, **sizes):
    kernel, rest, operands = _dq_parts(q, k, v, do, lse, delta, window=window, **sizes)
    return pl.pallas_call(kernel, name="window_attention_dq", **rest)(*operands)


# ---------------------------------------------------------------------------
# backward: dk, dv
# ---------------------------------------------------------------------------


def _dkv_kernel(qi_ref, kj_ref, flag_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                *, per, qk_dim, v_dim, pack, scale, block_q, block_k, window=None, kinds=None):
    pair = pl.program_id(1)
    qi, kj, flag = qi_ref[pair], kj_ref[pair], flag_ref[pair]

    @pl.when((flag & _FIRST) != 0)
    def _():
        dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, jnp.float32)
        dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, jnp.float32)

    def step(diagonal, edge):
        # the transposed tile (keys, queries) of one head after another: the
        # sums over a group's heads and over its query rows are the products'
        masked = diagonal or edge is not None
        if masked:
            key = kj * block_k + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            position = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            seen = _visible(position, key, diagonal, edge)
        # unrolled where it is lowered, not a loop on the chip: as a rolled
        # ``fori_loop`` over the heads this kernel took 2.41 ms where sixteen
        # copies take 1.71 (v5e, PR 33); the copies cost 1.9 MB of code in HBM
        def one(r):
            q = (_head(q_ref, r, qk_dim, per, pack).astype(jnp.float32) * scale
                 ).astype(q_ref.dtype)
            do = _head(do_ref, r, v_dim, per, pack).astype(do_ref.dtype)
            s = _dot(k_ref[...], q, _NT)
            if masked:
                s = jnp.where(seen, s, -jnp.inf)
            p = jnp.exp(s - lse_ref[pl.ds(r, 1), :])
            dv_acc_ref[...] = dv_acc_ref[...] + _dot(p.astype(do.dtype), do, _NN)
            dp = _dot(v_ref[...], do, _NT)
            ds = p * (dp - delta_ref[pl.ds(r, 1), :])
            dk_acc_ref[...] = dk_acc_ref[...] + _dot(ds.astype(q.dtype), q, _NN)

        _heads(per, one, pack)

    _masked_or_not(flag, step, window, kinds)

    @pl.when((flag & _LAST) != 0)
    def _():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _dkv_parts(q, k, v, do, lse, delta, *, kv_heads, scale, block_q,
               block_k, interpret, window=None):
    """What a call of the dk / dv kernel is made of, causal or windowed: the
    kernel, ``pallas_call``'s other arguments, the operands."""
    t = q.shape[0]
    kv_heads, per, qk_dim, v_dim, pack = _widths(q, k, v, kv_heads)
    pairs = _pairs(t // block_q, t // block_k, block_q, block_k, key_major=True,
                   window=window)
    q_spec, k_spec, v_spec, o_spec, row_spec = _specs(per, qk_dim, v_dim, block_q, block_k, pack)
    out_shape = (jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype))
    kernel = functools.partial(
        _dkv_kernel, per=per, qk_dim=qk_dim, v_dim=v_dim, pack=pack,
        scale=scale, block_q=block_q, block_k=block_k,
        **_edges(pairs, window))
    return kernel, dict(
        out_shape=out_shape,
        grid_spec=_grid_spec(
            pairs, kv_heads, [q_spec, k_spec, v_spec, o_spec, row_spec, row_spec],
            (k_spec, v_spec),
            [pltpu.VMEM((block_k, qk_dim), jnp.float32),
             pltpu.VMEM((block_k, v_dim), jnp.float32)]),
        compiler_params=_params(),
        cost_estimate=_cost(pairs, kv_heads, per, block_q, block_k,
                            (qk_dim, v_dim, v_dim, qk_dim),
                            (q, k, v, do, lse, delta) + out_shape),
        interpret=interpret), (*pairs, q, k, v, do, lse, delta)


def _causal_attention_dkv_call(q, k, v, do, lse, delta, **sizes):
    kernel, rest, operands = _dkv_parts(q, k, v, do, lse, delta, **sizes)
    return pl.pallas_call(kernel, name="causal_attention_dkv", **rest)(*operands)


def _window_attention_dkv_call(q, k, v, do, lse, delta, *, window, **sizes):
    kernel, rest, operands = _dkv_parts(q, k, v, do, lse, delta, window=window, **sizes)
    return pl.pallas_call(kernel, name="window_attention_dkv", **rest)(*operands)


# ---------------------------------------------------------------------------
# the function and its derivative
# ---------------------------------------------------------------------------


def causal_attention(q: Array, k: Array, v: Array, *, kv_heads: int,
                     scale: Optional[float] = None,
                     interpret: Optional[bool] = None,
                     window: Optional[int] = None) -> Array:
    """Causal softmax attention of one sequence: ``q (T, H * qk_dim)``,
    ``k (T, kv_heads * qk_dim)``, ``v (T, kv_heads * v_dim)``, query head
    ``h`` reading key/value head ``h // (H / kv_heads)``; returns
    ``(T, H * v_dim)`` in ``q``'s dtype. ``scale`` multiplies the scores
    (default ``qk_dim ** -0.5``; a caller that padded its queries and keys
    to whole lanes, or whose positions stretch the softmax, hands its
    own). ``window = W``: query ``i`` reads keys ``j`` with ``0 <= i - j <
    W`` (its own position counted) through the ``window_attention_*``
    kernels; ``None``, or a window no shorter than the sequence, is the
    causal call, kernel for kernel. Differentiable in all three. Any ``T``:
    the tail is padded to whole blocks (padded keys lie after every query;
    padded queries are cut off and their cotangent is zero)."""
    qk_dim, v_dim = k.shape[1] // kv_heads, v.shape[1] // kv_heads
    if qk_dim in _NARROW_HEADS:
        fits = v_dim == qk_dim and kv_heads % (_LANES // qk_dim) == 0
    else:
        fits = qk_dim % _LANES == 0 and v_dim % _LANES == 0
    if not fits or q.shape[1] % (kv_heads * qk_dim):
        raise ValueError(
            f"causal_attention needs a head_dim of whole {_LANES}s (queries / keys, and values), "
            f"or one of {_NARROW_HEADS} for all three with the key/value heads filling whole "
            f"lane tiles, and whole groups of query "
            f"heads, got q {q.shape}, k {k.shape}, v {v.shape}, kv_heads {kv_heads}")
    if window is not None and window < 1:
        raise ValueError(f"causal_attention: a window holds at least the query's own position, "
                         f"got {window}")
    if scale is None:
        scale = 1.0 / math.sqrt(qk_dim)
    if window is not None and window >= q.shape[0]:
        window = None
    with jax.named_scope("model.attention_core"):
        return _causal_attention(
            q, k, v, kv_heads, scale, _pk._resolve_interpret(interpret), window)


def _padded(t_pad: int, *arrays):
    pad = t_pad - arrays[0].shape[0]
    return tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in arrays) if pad else arrays


def _calls(window):
    """``(forward, dq, dk / dv, the keyword a windowed call adds)``: a
    windowed call is three kernels of their own names (what counts a causal
    call's operations, ``T^2 / 2`` entries a head, would count theirs
    wrongly), a causal call the three it always was."""
    if window is None:
        return (_causal_attention_fwd_call, _causal_attention_dq_call,
                _causal_attention_dkv_call, {})
    return (_window_attention_fwd_call, _window_attention_dq_call,
            _window_attention_dkv_call, {"window": window})


def _forward(q, k, v, kv_heads, scale, interpret, window=None):
    t_pad, block_q, block_k = _blocks(
        q.shape[0], _widths(q, k, v, kv_heads)[1], backward=False)
    forward, _, _, windowed = _calls(window)
    out, lse = forward(
        *_padded(t_pad, q, k, v), kv_heads=kv_heads, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret, **windowed)
    return out[:q.shape[0]], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _causal_attention(q, k, v, kv_heads, scale, interpret, window):
    return _forward(q, k, v, kv_heads, scale, interpret, window)[0]


def _causal_attention_fwd(q, k, v, kv_heads, scale, interpret, window):
    out, lse = _forward(q, k, v, kv_heads, scale, interpret, window)
    return out, (q, k, v, out, lse)


def _causal_attention_bwd(kv_heads, scale, interpret, window, residuals, d_out):
    # the backward rule is traced outside the scopes the forward stood in
    with jax.named_scope("model.attention"), jax.named_scope("model.attention_core"):
        q, k, v, out, lse = residuals
        t = q.shape[0]
        steps, per, _, _, _ = _widths(q, k, v, kv_heads)
        t_pad, block_q, block_k = _blocks(t, per, backward=True)
        # rowsum(d_out * out): what the softmax's Jacobian takes off every row
        # (a step's heads are consecutive heads: its key/value heads' groups)
        delta = jnp.sum(
            (d_out.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
                t, steps, per, -1), axis=-1)
        delta = jnp.pad(jnp.transpose(delta, (1, 2, 0)), ((0, 0), (0, 0), (0, t_pad - t)))
        args = _padded(t_pad, q, k, v, d_out.astype(q.dtype)) + (lse, delta)
        _, dq_call, dkv_call, windowed = _calls(window)
        sizes = dict(kv_heads=kv_heads, scale=scale, block_q=block_q, block_k=block_k,
                     interpret=interpret, **windowed)
        dq = dq_call(*args, **sizes)
        dk, dv = dkv_call(*args, **sizes)
        return dq[:t], dk[:t], dv[:t]


_causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)

__all__ = ["causal_attention", "causal_attention_serves"]
