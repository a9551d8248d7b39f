"""Blockwise quantization for the communication fabric.

EQuARX (PAPERS.md) shows XLA collectives with blockwise int8 payloads
recover near-2x collective throughput at negligible quality loss; our
robust aggregators tolerate *adversarial* per-row perturbations by
construction, so the bounded, symmetric error of int8 wire traffic is
well inside their design envelope (measured per aggregator by
``benchmarks/quant_robustness_study.py``). This module is the kernel
tier of that fabric:

* :func:`quantize_blockwise` / :func:`dequantize_blockwise` — symmetric
  int8 with one f32 scale per ``block`` trailing-axis values (absmax /
  127), optional stochastic rounding. Values keep the input's shape, so
  a quantized payload shards and gathers exactly like the tensor it
  replaces; scales ride along as a ``(..., n_blocks)`` side array.
* Pallas kernels (:func:`quantize_blockwise` with ``use_pallas=True``)
  for the on-chip path — one HBM read per tensor, scales computed in
  VMEM — with an XLA fallback that is the default off-TPU. The tile is
  the wrapper's ``tile=`` argument or :func:`_auto_quant_tile`'s.
* :func:`encode_blockwise` / :func:`dequantize_blockwise` — the
  mode-generic door down the SUB-INT8 tier (ISSUE 15): blockwise-
  scaled fp8 (``e4m3fn``/``e5m2`` — the per-block scale centers the
  format's dynamic range, so the mantissa spends its bits on relative
  accuracy) and packed s4 (two symmetric 4-bit codes per byte, half
  the int8 payload). Same non-finite guards; the XLA codec is the
  default, the Pallas kernels an opt-in
  (``BYZPY_TPU_SUBINT8_PALLAS=1``). On a v5e (PR 21) the fp8 kernels
  compile; e5m2 codes are bit-equal to XLA's, e4m3 codes at most one
  code apart (XLA's f32->f8 convert double-rounds through f16), decode
  bit-equal. The s4 kernels do not lower and raise (ROADMAP S4).
* :func:`ef_encode` — per-round **error feedback**: fold the previous
  round's quantization residual into this round's payload so the
  transmitted stream telescopes (compression stops compounding; the
  residual is carried state — see ``collectives.reshard_q_ef`` and the
  serving downlink's snapshot-covered twin).
* :class:`CommPrecision` — the
  ``off | bf16 | int8 | fp8 | fp8_e5m2 | s4`` switch (plus the
  ``error_feedback`` flag) threaded through every fabric
  (``parallel.collectives``, ``parallel.ps``, ``parallel.gossip``).
  ``off`` is the default everywhere and leaves the pre-existing
  programs bit-identical.

Error contract (pinned by ``tests/test_quantization.py``): round-to-
nearest blockwise int8 reconstructs every value within
``absmax(block) / 254`` of the original; stochastic rounding is
unbiased (``E[dequant] = x``) at one extra ULP of variance.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

_LANES = 128
_SUBLANES = 8

#: Default trailing-axis block width: one f32 scale per 256 values keeps
#: the scale overhead at 4/256 = 1.6% of the int8 payload while the
#: absmax stays local enough that a single outlier coordinate cannot
#: flatten a whole gradient's resolution.
DEFAULT_BLOCK = 256

_MODES = ("off", "bf16", "int8", "fp8", "fp8_e5m2", "s4")

#: The sub-int8 tier (ISSUE 15): fp8 at one byte per value with the
#: block scale centering the format's own dynamic range, and 4-bit
#: blockwise symmetric codes at half a byte per value (two nibbles
#: packed per byte).
SUB_INT8_MODES = ("fp8", "fp8_e5m2", "s4")

#: fp8 formats: jnp dtype name, max finite magnitude, and the absmax
#: divisor of the per-element worst-case reconstruction error. The
#: ideal round-to-nearest bound is half the top-binade ulp (e4m3fn:
#: ulp 32 at 448 -> absmax/28; e5m2: ulp 8192 at 57344 -> absmax/14),
#: but XLA's f32->f8 convert double-rounds through f16 (measured on
#: CPU: 303.897 -> f16 304.0 -> tie-to-even 320), adding up to half an
#: f16 ulp before the f8 rounding — the divisors below price that in
#: (448/16.125, 57344/4112) and are pinned by a dense-scan test.
_FP8_FORMATS = {
    "fp8": ("float8_e4m3fn", 448.0, 27.7),
    "fp8_e5m2": ("float8_e5m2", 57344.0, 13.9),
}

#: Symmetric integer code maxima per mode (the scale is absmax/qmax;
#: the s4 nibble range is kept symmetric at [-7, 7] — the -8 code is
#: unused so encode/decode stay sign-symmetric like int8's [-127, 127]).
_INT_QMAX = {"int8": 127.0, "s4": 7.0}

#: absmax divisor of the round-to-nearest error bound per blockwise
#: mode (half a code step: int8 absmax/254, s4 absmax/14; fp8 bounds
#: come from ``_FP8_FORMATS``).
_ERROR_DIVISOR = {"int8": 254.0, "s4": 14.0}


def _fp8_dtype(mode: str):
    name, fmax, _ = _FP8_FORMATS[mode]
    return getattr(jnp, name), fmax


@dataclass(frozen=True)
class CommPrecision:
    """Wire-precision policy for one communication fabric.

    ``mode`` is ``"off"`` (f32 wire, bit-identical to the unquantized
    program), ``"bf16"`` (cast-on-send, 2x fewer wire bytes),
    ``"int8"`` (blockwise symmetric quantization, ~4x fewer wire
    bytes), ``"fp8"``/``"fp8_e5m2"`` (blockwise-scaled float8 e4m3fn /
    e5m2 — one byte per value like int8, but the format's own mantissa
    spends the bits on *relative* accuracy, leaving fold headroom for
    sub-int8 error feedback), or ``"s4"`` (4-bit blockwise symmetric
    codes, two packed per byte, ~7.9x fewer wire bytes). ``block`` is
    the trailing-axis quantization block; ``stochastic`` selects
    unbiased stochastic rounding (needs a key at the quantization
    site; deterministic round-to-nearest otherwise; integer-code modes
    only). ``error_feedback`` opts the fabric into per-round residual
    carry (EF): the encoder adds the previous round's quantization
    residual to this round's payload before encoding and keeps the new
    residual beside the carried state, so compression error stops
    compounding across rounds (EF-SGD lineage; the stateful-adversary
    interaction is measured by the chaos wall's residual-shaping lane).
    """

    mode: str = "off"
    block: int = DEFAULT_BLOCK
    stochastic: bool = False
    error_feedback: bool = False

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"mode must be one of {_MODES}, got {self.mode!r}")
        if self.block <= 0:
            raise ValueError(f"block must be positive, got {self.block}")
        if self.mode == "s4" and self.block % 2:
            raise ValueError(
                f"s4 packs two codes per byte: block must be even, "
                f"got {self.block}"
            )

    @property
    def enabled(self) -> bool:
        """True when any compression is active (mode != "off")."""
        return self.mode != "off"

    @property
    def blockwise(self) -> bool:
        """True for the blockwise-coded modes (codes + per-block
        scales ride the wire; bf16 is a bare cast)."""
        return self.mode in ("int8", *SUB_INT8_MODES)

    def wire_bytes_per_value(self, dtype_bytes: int = 4) -> float:
        """Effective wire bytes per transported value (scale overhead
        amortized over the block) — the factor ``comms.scaling_model``
        uses to predict compressed-fabric traffic."""
        if self.mode == "bf16":
            return 2.0
        if self.mode in ("int8", "fp8", "fp8_e5m2"):
            return 1.0 + 4.0 / self.block
        if self.mode == "s4":
            return 0.5 + 4.0 / self.block
        return float(dtype_bytes)

    def error_bound(self, absmax: float = 1.0) -> float:
        """Per-element worst-case round-to-nearest reconstruction error
        for a block of the given ``absmax`` (the codec error contract;
        pinned by ``tests/test_quantization.py``)."""
        if self.mode in _ERROR_DIVISOR:
            return absmax / _ERROR_DIVISOR[self.mode]
        if self.mode in _FP8_FORMATS:
            return absmax / _FP8_FORMATS[self.mode][2]
        if self.mode == "bf16":
            return absmax * 2.0 ** -8
        return 0.0


def as_comm_precision(value: Union[CommPrecision, str, None]) -> CommPrecision:
    """Coerce a user-facing precision argument (``CommPrecision``, a mode
    string, or ``None``) into a :class:`CommPrecision`."""
    if value is None:
        return CommPrecision()
    if isinstance(value, CommPrecision):
        return value
    if isinstance(value, str):
        return CommPrecision(mode=value)
    raise TypeError(f"cannot interpret {value!r} as a CommPrecision")


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class QuantizedBlocks:
    """A blockwise-quantized tensor: coded ``values`` plus one f32
    scale per ``block`` trailing-axis values
    (``scales.shape == values.shape[:-1] + (n_blocks,)``).

    ``code`` names the value encoding: ``"int8"`` (int8 codes in the
    source tensor's exact shape — the PR-3 codec), ``"fp8"`` /
    ``"fp8_e5m2"`` (blockwise-scaled float8 values, same shape), or
    ``"s4"`` (two 4-bit codes packed per uint8 byte: the trailing axis
    is *half* the block-padded source length, and ``orig_d`` records
    the unpacked trailing dim so decode can slice the pad back off).
    ``orig_d`` is ``-1`` for the unpacked codes (trailing dim == the
    source's).

    Registered as a pytree (``values``/``scales`` are leaves; the rest
    is static), so a ``QuantizedBlocks`` can ride any collective,
    ``shard_map``, or sharding constraint directly — the coded payload
    is what crosses the interconnect.
    """

    values: Array
    scales: Array
    block: int = DEFAULT_BLOCK
    orig_dtype: str = "float32"
    code: str = "int8"
    orig_d: int = -1

    def tree_flatten(self):
        return (self.values, self.scales), (
            self.block, self.orig_dtype, self.code, self.orig_d,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, scales = children
        return cls(values, scales, *aux)

    def dequantize(self, dtype=None) -> Array:
        """Reconstruct the (lossy) tensor; see :func:`dequantize_blockwise`."""
        return dequantize_blockwise(self, dtype=dtype)


def _auto_quant_tile(rows_pad: int, d_pad: int, block: int) -> int:
    """Feature-tile width for the quantize/dequantize kernels: targets
    ~1 MiB f32 tiles, rounded to the quantization block so scales never
    straddle a grid step."""
    per_row = max(block, (262144 // max(rows_pad, 1)) // block * block)
    return min(d_pad, max(block, min(8192 // block * block or block, per_row)))


def _whole_blocks_tile(tile: int, block: int) -> int:
    """``tile`` cut to a whole number of quantization blocks, at most the
    128 blocks whose scales fill one lane group (:func:`_scales_lane_dense`)."""
    return min(max(block, tile // block * block), _LANES * block)


def _scales_lane_dense(scales: Array, bpt: int) -> Array:
    """``(rows, n_tiles * bpt)`` per-block scales -> ``(rows, n_tiles *
    128)``: each feature tile's ``bpt`` scales in the first lanes of a
    128-lane group. Mosaic only takes blocks whose last dim is a
    multiple of 128 (or the whole array), and ``bpt`` — a tile's worth
    of scales, 16-32 — is neither; the kernels address scale ``j`` of
    the tile as lane ``j`` of its group."""
    rows = scales.shape[0]
    grouped = scales.reshape(rows, -1, bpt)
    grouped = jnp.pad(grouped, ((0, 0), (0, 0), (0, _LANES - bpt)))
    return grouped.reshape(rows, -1)


def _scales_compact(dense: Array, bpt: int) -> Array:
    """Inverse of :func:`_scales_lane_dense`."""
    rows = dense.shape[0]
    return dense.reshape(rows, -1, _LANES)[:, :, :bpt].reshape(rows, -1)


def _quantize_kernel(x_ref, v_ref, s_ref, *, block: int, blocks_per_tile: int):
    """Quantize one (rows, tile) VMEM block: per-(row, block) absmax ->
    f32 scale -> round-to-nearest int8. The block loop is unrolled at
    trace time (blocks_per_tile is static); every step is a VPU
    reduction + multiply over a (rows, block) lane slab."""
    for j in range(blocks_per_tile):
        xb = x_ref[:, j * block:(j + 1) * block].astype(jnp.float32)
        # adversarial non-finite coordinates must not poison the block:
        # the scale comes from the FINITE values only, inf clips to the
        # codomain edge and NaN encodes as 0 (see quantize_blockwise)
        absmax = jnp.max(
            jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)),
            axis=1, keepdims=True,
        )
        scale = jnp.where(absmax > 0.0, absmax * (1.0 / 127.0), 1.0)
        s_ref[:, j:j + 1] = scale
        y = xb * (1.0 / scale)
        q = jnp.where(
            jnp.isnan(y), 0.0, jnp.clip(jnp.round(y), -127.0, 127.0)
        )
        v_ref[:, j * block:(j + 1) * block] = q.astype(jnp.int8)


def _dequantize_kernel(v_ref, s_ref, o_ref, *, block: int, blocks_per_tile: int):
    """Inverse of :func:`_quantize_kernel`: int8 * per-block f32 scale."""
    for j in range(blocks_per_tile):
        vb = v_ref[:, j * block:(j + 1) * block].astype(jnp.float32)
        o_ref[:, j * block:(j + 1) * block] = vb * s_ref[:, j:j + 1]


@functools.partial(
    jax.jit, static_argnames=("block", "tile", "interpret")
)
def _quantize_pallas_call(
    x2d: Array, *, block: int, tile: int, interpret: bool
) -> Tuple[Array, Array]:
    rows, d = x2d.shape
    rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
    d_pad = -(-d // tile) * tile
    xp = jnp.zeros((rows_pad, d_pad), jnp.float32)
    xp = xp.at[:rows, :d].set(x2d.astype(jnp.float32))
    bpt = tile // block
    nb_pad = d_pad // block
    values, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, block=block, blocks_per_tile=bpt),
        out_shape=(
            jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.int8),
            jax.ShapeDtypeStruct(
                (rows_pad, (d_pad // tile) * _LANES), jnp.float32
            ),
        ),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=(
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (rows_pad, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ),
        interpret=interpret,
        name="quantize_pallas",
    )(xp)
    nb = -(-d // block)
    return values[:rows, :d], _scales_compact(scales, bpt)[:rows, :nb]


@functools.partial(
    jax.jit, static_argnames=("block", "tile", "interpret", "dtype")
)
def _dequantize_pallas_call(
    values: Array, scales: Array, *, block: int, tile: int, interpret: bool, dtype
) -> Array:
    rows, d = values.shape
    rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
    d_pad = -(-d // tile) * tile
    nb_pad = d_pad // block
    # values.dtype generalizes the pad buffer: int8 codes or fp8 bit
    # patterns decode through the same multiply-by-scale kernel
    vp = jnp.zeros((rows_pad, d_pad), values.dtype).at[:rows, :d].set(values)
    sp = jnp.ones((rows_pad, nb_pad), jnp.float32)
    sp = sp.at[:rows, : scales.shape[1]].set(scales)
    bpt = tile // block
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, block=block, blocks_per_tile=bpt),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.float32),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (rows_pad, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="dequantize_pallas",
    )(vp, _scales_lane_dense(sp, bpt))
    return out[:rows, :d].astype(dtype)


@functools.partial(jax.jit, static_argnames=("block", "stochastic"))
def _quantize_xla(
    x2d: Array, key: Optional[Array], *, block: int, stochastic: bool
) -> Tuple[Array, Array]:
    rows, d = x2d.shape
    nb = -(-d // block)
    pad = nb * block - d
    xf = x2d.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
    xb = xf.reshape(rows, nb, block)
    # non-finite guard (mirrors the Pallas kernel): scale from the finite
    # values only, inf clips to +/-127, NaN encodes as 0 — one adversarial
    # coordinate can never poison its block's finite neighbors
    absmax = jnp.max(jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)), axis=2)
    scales = jnp.where(absmax > 0.0, absmax * (1.0 / 127.0), 1.0)
    y = xb * (1.0 / scales)[..., None]
    if stochastic:
        u = jax.random.uniform(key, y.shape, jnp.float32)
        q = jnp.floor(y + u)
    else:
        q = jnp.round(y)
    q = jnp.where(jnp.isnan(y), 0.0, jnp.clip(q, -127.0, 127.0))
    values = q.astype(jnp.int8).reshape(rows, nb * block)
    return values[:, :d], scales


# ---------------------------------------------------------------------------
# Sub-int8 codecs: blockwise-scaled fp8 and packed 4-bit symmetric codes
# ---------------------------------------------------------------------------


def _subint8_pallas_default() -> bool:
    """Pre-trace dispatch default for the sub-int8 Pallas kernels: on
    TPU AND explicitly opted in (``BYZPY_TPU_SUBINT8_PALLAS=1``). The
    XLA codec stays the default: the kernels have compiled and agreed
    on a chip (fp8; the module docstring has the details) but have no
    chip time yet, and a kernel that wins nothing is removed, not
    defaulted (ROADMAP S4/D5)."""
    import os

    from ..ops.pallas_kernels import _on_tpu

    return _on_tpu() and os.environ.get(
        "BYZPY_TPU_SUBINT8_PALLAS", ""
    ) not in ("", "0")


@functools.partial(jax.jit, static_argnames=("block", "fmt"))
def _quantize_fp8_xla(x2d: Array, *, block: int, fmt: str) -> Tuple[Array, Array]:
    fp_dtype, fmax = _fp8_dtype(fmt)
    rows, d = x2d.shape
    nb = -(-d // block)
    pad = nb * block - d
    xf = x2d.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
    xb = xf.reshape(rows, nb, block)
    # non-finite guard (same contract as int8): scale from the finite
    # values only, inf clips to the codomain edge, NaN encodes as 0
    absmax = jnp.max(jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)), axis=2)
    scales = jnp.where(absmax > 0.0, absmax * (1.0 / fmax), 1.0)
    y = xb * (1.0 / scales)[..., None]
    y = jnp.where(jnp.isnan(y), 0.0, jnp.clip(y, -fmax, fmax))
    values = y.astype(fp_dtype).reshape(rows, nb * block)
    return values[:, :d], scales


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def _dequantize_fp8_xla(
    values: Array, scales: Array, *, block: int, dtype
) -> Array:
    rows, d = values.shape
    nb = scales.shape[1]
    pad = nb * block - d
    vf = values.astype(jnp.float32)
    if pad:
        vf = jnp.pad(vf, ((0, 0), (0, pad)))
    out = (vf.reshape(rows, nb, block) * scales[..., None]).reshape(rows, nb * block)
    return out[:, :d].astype(dtype)


@functools.partial(jax.jit, static_argnames=("block", "stochastic"))
def _quantize_s4_xla(
    x2d: Array, key: Optional[Array], *, block: int, stochastic: bool
) -> Tuple[Array, Array]:
    rows, d = x2d.shape
    nb = -(-d // block)
    d_pad = nb * block
    xf = x2d.astype(jnp.float32)
    if d_pad - d:
        xf = jnp.pad(xf, ((0, 0), (0, d_pad - d)))
    xb = xf.reshape(rows, nb, block)
    absmax = jnp.max(jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)), axis=2)
    scales = jnp.where(absmax > 0.0, absmax * (1.0 / 7.0), 1.0)
    y = xb * (1.0 / scales)[..., None]
    if stochastic:
        u = jax.random.uniform(key, y.shape, jnp.float32)
        q = jnp.floor(y + u)
    else:
        q = jnp.round(y)
    q = jnp.where(jnp.isnan(y), 0.0, jnp.clip(q, -7.0, 7.0))
    # offset-binary nibbles (q + 8 in [1, 15]; 0 only for encoded NaN),
    # two per byte: even coordinate -> low nibble, odd -> high
    n = (q + 8.0).astype(jnp.uint8).reshape(rows, d_pad // 2, 2)
    packed = n[..., 0] | (n[..., 1] << 4)
    return packed, scales


@functools.partial(jax.jit, static_argnames=("block", "d", "dtype"))
def _dequantize_s4_xla(
    packed: Array, scales: Array, *, block: int, d: int, dtype
) -> Array:
    rows = packed.shape[0]
    d_pad = packed.shape[1] * 2
    lo = packed & jnp.uint8(0xF)
    hi = packed >> 4
    codes = (
        jnp.stack([lo, hi], axis=-1).reshape(rows, d_pad).astype(jnp.float32)
        - 8.0
    )
    nb = scales.shape[1]
    out = (codes.reshape(rows, nb, block) * scales[..., None]).reshape(
        rows, d_pad
    )
    return out[:, :d].astype(dtype)


def _quantize_fp8_kernel(
    x_ref, v_ref, s_ref, *, block: int, blocks_per_tile: int, fmt: str
):
    """fp8 twin of :func:`_quantize_kernel`: per-(row, block) absmax ->
    f32 scale centering the fp8 dynamic range -> f8 cast, emitted as
    uint8 bit patterns (the wrapper bitcasts back — Mosaic stores are
    byte-wide either way)."""
    fp_dtype, fmax = _fp8_dtype(fmt)
    from jax import lax as _lax

    for j in range(blocks_per_tile):
        xb = x_ref[:, j * block:(j + 1) * block].astype(jnp.float32)
        absmax = jnp.max(
            jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)),
            axis=1, keepdims=True,
        )
        scale = jnp.where(absmax > 0.0, absmax * (1.0 / fmax), 1.0)
        s_ref[:, j:j + 1] = scale
        y = xb * (1.0 / scale)
        y = jnp.where(jnp.isnan(y), 0.0, jnp.clip(y, -fmax, fmax))
        v_ref[:, j * block:(j + 1) * block] = _lax.bitcast_convert_type(
            y.astype(fp_dtype), jnp.uint8
        )


def _quantize_s4_kernel(
    x_ref, v_ref, s_ref, *, block: int, blocks_per_tile: int
):
    """s4 twin of :func:`_quantize_kernel`: nibble codes packed two per
    byte inside the tile (even coordinate -> low nibble)."""
    for j in range(blocks_per_tile):
        xb = x_ref[:, j * block:(j + 1) * block].astype(jnp.float32)
        absmax = jnp.max(
            jnp.abs(jnp.where(jnp.isfinite(xb), xb, 0.0)),
            axis=1, keepdims=True,
        )
        scale = jnp.where(absmax > 0.0, absmax * (1.0 / 7.0), 1.0)
        s_ref[:, j:j + 1] = scale
        y = xb * (1.0 / scale)
        q = jnp.where(jnp.isnan(y), 0.0, jnp.clip(jnp.round(y), -7.0, 7.0))
        n = (q + 8.0).astype(jnp.uint8)
        v_ref[:, (j * block) // 2:((j + 1) * block) // 2] = (
            n[:, 0::2] | (n[:, 1::2] << 4)
        )


def _dequantize_s4_kernel(
    v_ref, s_ref, o_ref, *, block: int, blocks_per_tile: int
):
    for j in range(blocks_per_tile):
        packed = v_ref[:, (j * block) // 2:((j + 1) * block) // 2]
        lo = (packed & jnp.uint8(0xF)).astype(jnp.float32) - 8.0
        hi = (packed >> 4).astype(jnp.float32) - 8.0
        codes = jnp.stack([lo, hi], axis=-1).reshape(lo.shape[0], block)
        o_ref[:, j * block:(j + 1) * block] = codes * s_ref[:, j:j + 1]


@functools.partial(
    jax.jit, static_argnames=("block", "tile", "interpret", "fmt")
)
def _quantize_fp8_pallas_call(
    x2d: Array, *, block: int, tile: int, interpret: bool, fmt: str
) -> Tuple[Array, Array]:
    fp_dtype, _ = _fp8_dtype(fmt)
    rows, d = x2d.shape
    rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
    d_pad = -(-d // tile) * tile
    xp = jnp.zeros((rows_pad, d_pad), jnp.float32)
    xp = xp.at[:rows, :d].set(x2d.astype(jnp.float32))
    bpt = tile // block
    nb_pad = d_pad // block
    values, scales = pl.pallas_call(
        functools.partial(
            _quantize_fp8_kernel, block=block, blocks_per_tile=bpt, fmt=fmt
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.uint8),
            jax.ShapeDtypeStruct(
                (rows_pad, (d_pad // tile) * _LANES), jnp.float32
            ),
        ),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=(
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (rows_pad, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ),
        interpret=interpret,
        name="quantize_fp8_pallas",
    )(xp)
    nb = -(-d // block)
    from jax import lax as _lax

    return (
        _lax.bitcast_convert_type(values[:rows, :d], fp_dtype),
        _scales_compact(scales, bpt)[:rows, :nb],
    )


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def _quantize_s4_pallas_call(
    x2d: Array, *, block: int, tile: int, interpret: bool
) -> Tuple[Array, Array]:
    rows, d = x2d.shape
    rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
    d_pad = -(-d // tile) * tile
    xp = jnp.zeros((rows_pad, d_pad), jnp.float32)
    xp = xp.at[:rows, :d].set(x2d.astype(jnp.float32))
    bpt = tile // block
    nb_pad = d_pad // block
    values, scales = pl.pallas_call(
        functools.partial(_quantize_s4_kernel, block=block, blocks_per_tile=bpt),
        out_shape=(
            jax.ShapeDtypeStruct((rows_pad, d_pad // 2), jnp.uint8),
            jax.ShapeDtypeStruct(
                (rows_pad, (d_pad // tile) * _LANES), jnp.float32
            ),
        ),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec((rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=(
            pl.BlockSpec(
                (rows_pad, tile // 2), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (rows_pad, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ),
        interpret=interpret,
        name="quantize_s4_pallas",
    )(xp)
    nb = -(-d // block)
    d_blocks_pad = nb * block // 2
    return values[:rows, :d_blocks_pad], _scales_compact(scales, bpt)[:rows, :nb]


@functools.partial(
    jax.jit, static_argnames=("block", "tile", "interpret", "d", "dtype")
)
def _dequantize_s4_pallas_call(
    packed: Array, scales: Array, *, block: int, tile: int, interpret: bool,
    d: int, dtype
) -> Array:
    rows = packed.shape[0]
    rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
    d_codes = packed.shape[1] * 2
    d_pad = -(-d_codes // tile) * tile
    nb_pad = d_pad // block
    vp = jnp.zeros((rows_pad, d_pad // 2), jnp.uint8)
    vp = vp.at[:rows, : packed.shape[1]].set(packed)
    sp = jnp.ones((rows_pad, nb_pad), jnp.float32)
    sp = sp.at[:rows, : scales.shape[1]].set(scales)
    bpt = tile // block
    out = pl.pallas_call(
        functools.partial(
            _dequantize_s4_kernel, block=block, blocks_per_tile=bpt
        ),
        out_shape=jax.ShapeDtypeStruct((rows_pad, d_pad), jnp.float32),
        grid=(d_pad // tile,),
        in_specs=[
            pl.BlockSpec(
                (rows_pad, tile // 2), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (rows_pad, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (rows_pad, tile), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        name="dequantize_s4_pallas",
    )(vp, _scales_lane_dense(sp, bpt))
    return out[:rows, :d].astype(dtype)


def quantize_blockwise(
    x: Array,
    *,
    block: int = DEFAULT_BLOCK,
    stochastic: bool = False,
    key: Optional[Array] = None,
    use_pallas: Optional[bool] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> QuantizedBlocks:
    """Blockwise symmetric int8 quantization along the trailing axis.

    One f32 scale (``absmax / 127``) per ``block`` consecutive values;
    all-zero (and empty) blocks get scale 1 so dequantization is
    well-defined. Non-finite coordinates (adversarial ``inf``/``NaN``
    rows are first-class inputs to the robust fabrics) never poison
    their block: the scale is computed over the finite values only,
    ``+/-inf`` clips to the codomain edge (``+/-127 * scale``) and
    ``NaN`` encodes as 0 — the dequantized tensor is always finite with
    every finite coordinate inside the usual half-step bound. ``stochastic=True`` uses unbiased stochastic rounding
    (requires ``key``; always on the XLA path — randomness and Mosaic
    PRNG state do not mix with the tiled grid here). Dispatch (Pallas
    vs XLA, tile width) resolves in this wrapper, pre-trace, exactly
    like the PR-2 kernel wrappers: ``use_pallas=None`` routes to the
    Pallas kernel on TPU and the XLA fallback elsewhere.
    """
    if stochastic and key is None:
        raise ValueError("stochastic rounding needs an explicit PRNG key")
    orig_shape = x.shape
    orig_dtype = str(x.dtype)
    d = orig_shape[-1] if orig_shape else 1
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2d = x.reshape(rows, d)
    if d == 0 or rows == 0:
        return QuantizedBlocks(
            jnp.zeros(orig_shape, jnp.int8),
            jnp.zeros((*orig_shape[:-1], 0), jnp.float32),
            block,
            orig_dtype,
        )
    if use_pallas is None:
        from ..ops.pallas_kernels import _on_tpu

        use_pallas = _on_tpu() and not stochastic
    if use_pallas and not stochastic:
        from ..ops.pallas_kernels import _resolve_interpret

        interpret = _resolve_interpret(interpret)
        rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
        d_pad = -(-d // block) * block
        if tile is None:
            tile = _auto_quant_tile(rows_pad, d_pad, block)
        tile = _whole_blocks_tile(tile, block)
        values, scales = _quantize_pallas_call(
            x2d, block=block, tile=tile, interpret=interpret
        )
    else:
        values, scales = _quantize_xla(
            x2d, key, block=block, stochastic=stochastic
        )
    nb = scales.shape[-1]
    return QuantizedBlocks(
        values.reshape(orig_shape),
        scales.reshape(*orig_shape[:-1], nb),
        block,
        orig_dtype,
    )


def dequantize_blockwise(
    q: QuantizedBlocks,
    *,
    dtype=None,
    use_pallas: Optional[bool] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Reconstruct the tensor a :class:`QuantizedBlocks` approximates
    (``values * scale`` per trailing-axis block), in ``dtype`` (default:
    the dtype recorded at quantization). Same pre-trace dispatch rules
    as :func:`quantize_blockwise`; dispatches on ``q.code`` (int8 codes
    and fp8 bit patterns share the multiply-by-scale path, packed s4
    unpacks its nibbles first)."""
    out_dtype = jnp.dtype(dtype if dtype is not None else q.orig_dtype)
    if q.code == "s4":
        return _dequantize_s4(
            q, dtype=out_dtype, use_pallas=use_pallas, tile=tile,
            interpret=interpret,
        )
    shape = q.values.shape
    d = shape[-1] if shape else 1
    rows = 1
    for s in shape[:-1]:
        rows *= s
    if d == 0 or rows == 0:
        return jnp.zeros(shape, out_dtype)
    block = q.block
    v2d = q.values.reshape(rows, d)
    s2d = q.scales.reshape(rows, -1)
    sub8 = q.code in _FP8_FORMATS
    if use_pallas is None:
        if sub8:
            use_pallas = _subint8_pallas_default()
        else:
            from ..ops.pallas_kernels import _on_tpu

            use_pallas = _on_tpu()
    if use_pallas:
        from ..ops.pallas_kernels import _resolve_interpret

        interpret = _resolve_interpret(interpret)
        rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
        d_pad = -(-d // block) * block
        if tile is None:
            tile = _auto_quant_tile(rows_pad, d_pad, block)
        tile = _whole_blocks_tile(tile, block)
        out = _dequantize_pallas_call(
            v2d, s2d, block=block, tile=tile, interpret=interpret,
            dtype=out_dtype,
        )
    else:
        out = _dequantize_xla(v2d, s2d, block=block, dtype=out_dtype)
    return out.reshape(shape)


def _dequantize_s4(
    q: QuantizedBlocks,
    *,
    dtype,
    use_pallas: Optional[bool] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
    """Unpack + rescale an s4 :class:`QuantizedBlocks` (two nibbles per
    byte; ``q.orig_d`` is the unpacked trailing length)."""
    lead = q.values.shape[:-1]
    packed_d = q.values.shape[-1] if q.values.shape else 0
    d = q.orig_d if q.orig_d >= 0 else packed_d * 2
    rows = 1
    for s in lead:
        rows *= s
    if d == 0 or rows == 0:
        return jnp.zeros((*lead, d), dtype)
    block = q.block
    v2d = q.values.reshape(rows, packed_d)
    s2d = q.scales.reshape(rows, -1)
    if use_pallas is None:
        use_pallas = _subint8_pallas_default()
    if use_pallas:
        from ..ops.pallas_kernels import (
            _resolve_interpret,
            s4_kernels_unsupported,
        )

        interpret = _resolve_interpret(interpret)
        if not interpret:
            raise s4_kernels_unsupported("uint8 -> float32")
        rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
        d_pad = -(-d // block) * block
        if tile is None:
            tile = _auto_quant_tile(rows_pad, d_pad, block)
        tile = _whole_blocks_tile(tile, block)
        out = _dequantize_s4_pallas_call(
            v2d, s2d, block=block, tile=tile, interpret=interpret,
            d=d, dtype=dtype,
        )
    else:
        out = _dequantize_s4_xla(v2d, s2d, block=block, d=d, dtype=dtype)
    return out.reshape(*lead, d)


def encode_blockwise(
    x: Array,
    precision: Union["CommPrecision", str],
    *,
    key: Optional[Array] = None,
    use_pallas: Optional[bool] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> QuantizedBlocks:
    """Blockwise encode under any coded :class:`CommPrecision` mode —
    the mode-generic door of the codec tier (``int8`` delegates to
    :func:`quantize_blockwise`; ``fp8``/``fp8_e5m2``/``s4`` are the
    sub-int8 codecs). Same non-finite guards (scale from finite values
    only, inf clips to the codomain edge, NaN encodes as 0) and the
    same pre-trace dispatch pattern as the int8 codec; the sub-int8
    Pallas kernels default on only with ``BYZPY_TPU_SUBINT8_PALLAS=1``
    on TPU (XLA fallback authoritative until the queued on-chip
    sweep)."""
    p = as_comm_precision(precision)
    if not p.blockwise:
        raise ValueError(
            f"encode_blockwise needs a coded mode (int8/fp8/fp8_e5m2/s4), "
            f"got {p.mode!r}"
        )
    if p.mode == "int8":
        return quantize_blockwise(
            x, block=p.block, stochastic=p.stochastic, key=key,
            use_pallas=use_pallas, tile=tile, interpret=interpret,
        )
    if p.stochastic and p.mode in _FP8_FORMATS:
        raise ValueError(
            "stochastic rounding is integer-code only (int8/s4); fp8 "
            "rounds to nearest in the format's own grid"
        )
    if p.stochastic and key is None:
        raise ValueError("stochastic rounding needs an explicit PRNG key")
    orig_shape = x.shape
    orig_dtype = str(x.dtype)
    d = orig_shape[-1] if orig_shape else 1
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    if d == 0 or rows == 0:
        if p.mode == "s4":
            values = jnp.zeros((*orig_shape[:-1], 0), jnp.uint8)
        else:
            values = jnp.zeros(orig_shape, _fp8_dtype(p.mode)[0])
        return QuantizedBlocks(
            values, jnp.zeros((*orig_shape[:-1], 0), jnp.float32),
            p.block, orig_dtype, p.mode, d if p.mode == "s4" else -1,
        )
    x2d = x.reshape(rows, d)
    if use_pallas is None:
        use_pallas = _subint8_pallas_default() and not p.stochastic
    if use_pallas and not p.stochastic:
        from ..ops.pallas_kernels import (
            _resolve_interpret,
            s4_kernels_unsupported,
        )

        interpret = _resolve_interpret(interpret)
        if p.mode == "s4" and not interpret:
            raise s4_kernels_unsupported("float32 -> uint8")
        rows_pad = max(_SUBLANES, -(-rows // _SUBLANES) * _SUBLANES)
        d_pad = -(-d // p.block) * p.block
        if tile is None:
            tile = _auto_quant_tile(rows_pad, d_pad, p.block)
        tile = _whole_blocks_tile(tile, p.block)
        if p.mode == "s4":
            values, scales = _quantize_s4_pallas_call(
                x2d, block=p.block, tile=tile, interpret=interpret
            )
        else:
            values, scales = _quantize_fp8_pallas_call(
                x2d, block=p.block, tile=tile, interpret=interpret,
                fmt=p.mode,
            )
    elif p.mode == "s4":
        values, scales = _quantize_s4_xla(
            x2d, key, block=p.block, stochastic=p.stochastic
        )
    else:
        values, scales = _quantize_fp8_xla(x2d, block=p.block, fmt=p.mode)
    nb = scales.shape[-1]
    return QuantizedBlocks(
        values.reshape(*orig_shape[:-1], values.shape[-1]),
        scales.reshape(*orig_shape[:-1], nb),
        p.block,
        orig_dtype,
        p.mode,
        d if p.mode == "s4" else -1,
    )


def ef_encode(
    x: Array,
    residual: Optional[Array],
    precision: Union["CommPrecision", str],
    **kwargs: Any,
) -> Tuple[QuantizedBlocks, Array]:
    """Error-feedback encode: fold the previous round's quantization
    residual into this round's payload, encode, and return the NEW
    residual to carry forward.

    ``compensated = x + residual`` is what crosses the wire;
    ``new_residual = compensated - decode(encode(compensated))`` is
    exactly the quantization error of this round's transmission, so
    over N rounds the decoded sum telescopes to the true sum of ``x``
    plus ONE round's bounded error — compression error stops
    compounding (the EF-SGD contract, pinned by
    ``tests/test_quantization.py``). ``residual=None`` starts the
    chain at zero. The residual is STATE: it must live beside the
    caller's carried round state (optimizer state in the fused PS,
    snapshot-covered tenant state in the serving frontend) and — being
    attacker-controlled on a Byzantine client — is exactly what the
    forensics plane's residual-shaping detector watches for."""
    xc = x if residual is None else x + residual.astype(x.dtype)
    q = encode_blockwise(xc, precision, **kwargs)
    new_residual = xc - dequantize_blockwise(q, dtype=xc.dtype)
    return q, new_residual


@functools.partial(jax.jit, static_argnames=("block", "dtype"))
def _dequantize_xla(values: Array, scales: Array, *, block: int, dtype) -> Array:
    rows, d = values.shape
    nb = scales.shape[1]
    pad = nb * block - d
    vf = values.astype(jnp.float32)
    if pad:
        vf = jnp.pad(vf, ((0, 0), (0, pad)))
    out = (vf.reshape(rows, nb, block) * scales[..., None]).reshape(rows, nb * block)
    return out[:, :d].astype(dtype)


def dequantize_rows(
    codes: Array, scales: Array, *, mode: str, block: int, d: int,
    dtype=jnp.float32,
) -> Array:
    """Trace-safe row-batched dequantization of WIRE-layout codes — the
    in-jit twin of ``engine.actor.wire.decode_rows_np`` and the entry
    point the ragged fold's jitted program uses to consume admitted
    submissions that are still compressed (PR 16's batched ingress
    hands codes + scales through admission untouched).

    ``codes`` is ``(rows, ncodes)`` exactly as the wire carries them:
    int8 codes for ``int8``, uint8 fp8 bit patterns for
    ``fp8``/``fp8_e5m2``, packed offset-binary nibbles (``nb*block//2``
    bytes) for ``s4``; ``scales`` is ``(rows, nb)`` f32. On CPU/TPU the
    result is bit-identical to the host mirror (cast + f32 multiply,
    both IEEE-exact), which is what keeps the fused device-side path at
    bit parity with the per-frame ingress decode."""
    if mode == "s4":
        return _dequantize_s4_xla(codes, scales, block=block, d=d, dtype=dtype)
    if mode in _FP8_FORMATS:
        fp_dtype, _ = _fp8_dtype(mode)
        values = jax.lax.bitcast_convert_type(codes, fp_dtype)
        return _dequantize_fp8_xla(values, scales, block=block, dtype=dtype)
    if mode == "int8":
        return _dequantize_xla(codes, scales, block=block, dtype=dtype)
    raise ValueError(f"no wire row codec for mode {mode!r}")


def quantization_error_bound(
    x: Array, *, block: int = DEFAULT_BLOCK, mode: str = "int8"
) -> Array:
    """Per-element worst-case reconstruction error of round-to-nearest
    blockwise coding: half a code step — ``absmax(block) / 254`` for
    int8, ``/ 14`` for s4, ``/ 28`` (e4m3) and ``/ 14`` (e5m2) for the
    fp8 formats' top binade — broadcast back to ``x``'s shape (exact up
    to f32 roundoff in the scale division, ~1e-5 relative). The
    robustness study compares this against each aggregator's measured
    Byzantine tolerance to derive the per-aggregator precision floor."""
    if mode in _ERROR_DIVISOR:
        divisor = _ERROR_DIVISOR[mode]
    elif mode in _FP8_FORMATS:
        divisor = _FP8_FORMATS[mode][2]
    else:
        raise ValueError(f"no blockwise error bound for mode {mode!r}")
    shape = x.shape
    d = shape[-1]
    nb = -(-d // block)
    pad = nb * block - d
    xf = jnp.abs(x.astype(jnp.float32))
    if pad:
        xf = jnp.concatenate(
            [xf, jnp.zeros((*shape[:-1], pad), jnp.float32)], axis=-1
        )
    absmax = jnp.max(xf.reshape(*shape[:-1], nb, block), axis=-1)
    bound = jnp.repeat(absmax / divisor, block, axis=-1)
    return bound[..., :d]


__all__ = [
    "DEFAULT_BLOCK",
    "SUB_INT8_MODES",
    "CommPrecision",
    "QuantizedBlocks",
    "as_comm_precision",
    "dequantize_blockwise",
    "dequantize_rows",
    "ef_encode",
    "encode_blockwise",
    "quantization_error_bound",
    "quantize_blockwise",
]
