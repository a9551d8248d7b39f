"""Rows back to the tokens that asked for them, as one Pallas TPU kernel.

A table ``rows (S, D)`` of ``segments`` equal runs of slots (a held expert
each); the first ``filled[e]`` slots of run ``e`` are live, each read by
exactly one of the tokens' ``(T, k)`` picks, ``reader_at[s]`` of the picks
laid flat, with that pick's weight ``gate[t, j]``, and the readers of a run
ascend (a token's place in a run is its rank among the run's tokens). The
kernel computes

    ``out[t] = sum over live s read by a pick (t, j) of gate[t, j] * rows[s]``

in the order of the slots, multiplied and added in float32: the sum of
``gate[t, j] * rows[slot[t, j]]`` over a token's filled picks, with nothing
done for a pick that is empty (of ``T x k`` picks a few in a hundred are
filled: a token's picks that a chip holds are a few tenths of one).

* The grid walks blocks of ``block`` tokens, whose ``(block, D)`` sum
  lives in VMEM; ``reader_at``, ``gate`` (laid flat) and ``filled`` are
  scalar-prefetched. Because a run's readers ascend, the slots of a block
  are one stretch of every run: a step finds each stretch's end from where
  the step before left off (a cursor a run, kept in SMEM), and lists the
  stretch's tiles.
* The table stays in HBM. Mosaic copies whole tiles only (a row alone is
  refused: "slice shape must be aligned to tiling"), so the unit is the
  aligned tile of 8 rows (16 of a 16-bit type): a stretch's tiles come in
  by a DMA each, ``_DEPTH`` in flight, and each live row of a tile is
  multiply-added into its token's row of the sum.

No ``(T, k, D)`` array exists anywhere, nothing is scattered and no row
no token reads is moved but a stretch's two end tiles: the same kernel is
the forward of the held experts' combine and the backward of their
dispatch gather (:mod:`byzpy_tpu.parallel.moe`).

Mosaic on a TPU, the Pallas interpreter on a CPU
(:func:`~byzpy_tpu.ops.pallas_kernels._resolve_interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as _pk

Array = jnp.ndarray

_LANES, _SUBLANES = _pk._LANES, _pk._SUBLANES
# Measured alone on a v5e at 4096 tokens (PR 40; ms a call, block x depth): 32
# runs of 320 slots, 2,521 live, D 2048: 0.223 at 1024 x 8, 0.232 at 512 x 8,
# 0.236 at 2048 x 8, 0.218 at 1024 x 4, 0.231 at 1024 x 16, 0.226 at 256 x 8;
# 8 runs of 1024, 2,023 live, D 2048: 0.212, 0.236, 0.216, 0.232, 0.244, 0.224;
# 8 runs of 1024, 1,528 live, D 2688: 0.217, 0.224, 0.215, 0.221, 0.229, 0.235.
_BLOCK = 1024  # tokens a grid step: their float32 sum and the output's two buffers in VMEM
_DEPTH = 8  # tile copies in flight
_VMEM_LIMIT = 64 * 1024 * 1024
_BLOCK_BYTES = 40 * 1024 * 1024


def rows_to_tokens_serves(rows: Array) -> bool:
    """THE gate of the route: does the kernel serve a table ``rows (S, D)``?
    On a TPU, for float32 / bfloat16 rows of whole 128-lane tiles, an
    operand that is not device-sharded (:func:`~byzpy_tpu.ops.
    pallas_kernels.sharding_allows_pallas`). Asked once a call, in Python,
    by ``parallel.moe._rows_to_tokens``; reads no environment variable."""
    return bool(
        _pk._on_tpu()
        and rows.ndim == 2
        and rows.dtype in (jnp.float32, jnp.bfloat16)
        and rows.shape[1] % _LANES == 0
        and _pk.sharding_allows_pallas(rows)
    )


def _kernel(reader_ref, gate_ref, filled_ref, rows_ref, out_ref, ring_ref, sem_ref, acc_ref,
            cursor_ref, tile_ref, lo_ref, hi_ref, *, k, segments, per, block, group):
    b = pl.program_id(0)
    first_pick = b * block * k  # of the picks laid flat
    last_slot = segments * per - 1

    @pl.when(b == 0)
    def _():
        def run_start(e, carry):
            cursor_ref[e] = e * per
            return carry

        lax.fori_loop(0, segments, run_start, 0)

    def stretch(e, n):
        """List the tiles of run ``e``'s slots whose readers are in this block."""
        lo, end = cursor_ref[e], e * per + filled_ref[e]
        hi = lax.while_loop(
            lambda s: (s < end) & (reader_ref[jnp.minimum(s, last_slot)] < first_pick + block * k),
            lambda s: s + 1, lo)
        cursor_ref[e] = hi
        first = lo // group * group

        def tile(c, n):
            tile_ref[n], lo_ref[n], hi_ref[n] = first + c * group, lo, hi
            return n + 1

        return lax.fori_loop(0, jnp.where(hi > lo, (hi - first + group - 1) // group, 0), tile, n)

    n = lax.fori_loop(0, segments, stretch, 0)

    def copy(q):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(tile_ref[q], group), group)],
            ring_ref.at[q % _DEPTH], sem_ref.at[q % _DEPTH])

    def start(q, carry):
        copy(q).start()
        return carry

    lax.fori_loop(0, jnp.minimum(n, _DEPTH), start, 0)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def add(q, carry):
        copy(q).wait()
        base, lo, hi = tile_ref[q], lo_ref[q], hi_ref[q]
        if group != _SUBLANES:  # two rows share a sublane: none of them loads alone
            whole = ring_ref[q % _DEPTH].astype(jnp.float32)
        for i in range(group):
            s = base + i

            @pl.when((s >= lo) & (s < hi))
            def _():
                reader = reader_ref[s]
                token = pl.ds(lax.div(reader - first_pick, k), 1)
                row = (ring_ref[q % _DEPTH, pl.ds(i, 1), :] if group == _SUBLANES
                       else whole[i:i + 1])
                acc_ref[token, :] = acc_ref[token, :] + gate_ref[reader] * row

        @pl.when(q + _DEPTH < n)
        def _():
            copy(q + _DEPTH).start()

        return carry

    lax.fori_loop(0, n, add, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _rows_to_tokens_call(rows, gate, reader_at, filled, *, block, interpret):
    n_slots, d = rows.shape
    tokens, k = gate.shape
    segments = filled.shape[0]
    group = _SUBLANES * (4 // rows.dtype.itemsize)  # rows of one tile
    pad = -n_slots % group  # a copy brings whole tiles
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    n_tiles = (n_slots + pad) // group + segments  # a stretch's two ends may share a tile
    return pl.pallas_call(
        functools.partial(_kernel, k=k, segments=segments, per=n_slots // segments, block=block,
                          group=group),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(-(-tokens // block),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((_DEPTH, group, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((_DEPTH,)),
                            pltpu.VMEM((block, d), jnp.float32),
                            pltpu.SMEM((segments,), jnp.int32)]
            + [pltpu.SMEM((n_tiles,), jnp.int32)] * 3),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * tokens * d, transcendentals=0,
            bytes_accessed=2 * tokens * d * rows.dtype.itemsize + 8 * n_slots),
        interpret=interpret,
        name="rows_to_tokens",
    )(reader_at.astype(jnp.int32), gate.astype(jnp.float32).reshape(-1), filled.astype(jnp.int32),
      rows)


def rows_to_tokens(rows: Array, gate: Array, reader_at: Array, filled: Array, *, block=None,
                   interpret=None) -> Array:
    """``out (T, D)``, ``out[t]`` the sum of ``gate[t, j] * rows[s]`` over
    the live slots ``s`` that a pick ``(t, j)`` of token ``t`` reads, in the
    slots' order, in float32; the result has the rows' dtype. ``rows (S, D)``
    is ``filled.shape[0]`` runs of ``S / filled.shape[0]`` slots; the first
    ``filled[e]`` of run ``e`` are live, ``reader_at (S,)`` names a live
    slot's pick among the ``(T, k)`` laid flat and ascends over a run's live
    slots. What a slot that is not live holds (a row, a reader) reaches
    nothing."""
    interpret = _pk._resolve_interpret(interpret)
    if block is None:  # the widest of 1024 ... 128 tokens that VMEM holds, or all there are
        per_token = rows.shape[1] * (4 + 2 * rows.dtype.itemsize)
        block = next((b for b in (_BLOCK, 512, 256) if b * per_token <= _BLOCK_BYTES), 128)
        block = min(block, _pk._round_up(gate.shape[0], 2 * _SUBLANES))
    return _rows_to_tokens_call(rows, gate, reader_at, filled, block=block, interpret=interpret)


__all__ = ["rows_to_tokens", "rows_to_tokens_serves"]
