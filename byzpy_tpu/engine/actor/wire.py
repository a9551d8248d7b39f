"""Length-prefixed binary frames over asyncio streams.

Control-plane wire format (ref: ``byzpy/engine/actor/_wire.py:8-18``): a
4-byte big-endian length followed by a cloudpickle body. Device arrays are
converted to numpy on serialization — bulk tensor movement between chips
never goes through this wire; it rides XLA collectives (see
``byzpy_tpu.parallel``).

.. warning:: **Trusted networks only.** Frames are cloudpickle: anyone who
   can reach the socket can execute arbitrary code in the receiving
   process (same property as the reference's pickle wire). Bind servers to
   loopback or a private, firewalled fabric. Setting ``BYZPY_TPU_WIRE_KEY``
   (a shared secret, same value on every host) prepends an HMAC-SHA256 tag
   to every frame and rejects unsigned/forged ones — the analogue of the
   reference's signed pickle frames (ref:
   ``examples/ps/remote_tcp/ps_node.py:1-56``). Signing authenticates the
   sender; it does not encrypt.
"""

from __future__ import annotations

import dataclasses
import hashlib
import hmac
import os
import struct
import warnings
from typing import Any, Optional, Sequence, Tuple

import asyncio

import cloudpickle
import numpy as np

from ...observability import metrics as _obs_metrics
from ...observability import runtime as _obs_runtime
from ...observability import tracing as _obs_tracing

_HEADER = struct.Struct(">I")
MAX_FRAME = 1 << 31
_SIG_LEN = hashlib.sha256().digest_size

#: Env opt-in for compressed tensor frames: "off" (default, lossless
#: cloudpickle), "bf16", "int8" (blockwise symmetric, per-block f32
#: scales carried in the frame), or the sub-int8 tier "fp8"/"fp8_e5m2"
#: (blockwise-scaled float8 — one byte per value, format-relative
#: accuracy) and "s4" (two 4-bit codes packed per byte, ~7.9x fewer
#: payload bytes). Lossy — see docs/performance.md §quantized comms and
#: §sub-int8 fabric.
_WIRE_PRECISION_ENV = "BYZPY_TPU_WIRE_PRECISION"
_WIRE_BLOCK_ENV = "BYZPY_TPU_WIRE_BLOCK"
#: Every lossy wire mode, and the blockwise subset carrying per-block
#: scale headers (pre-decode forensics — the residual-shaping detector
#: — applies to these).
WIRE_MODES = ("bf16", "int8", "fp8", "fp8_e5m2", "s4")
BLOCKWISE_WIRE_MODES = ("int8", "fp8", "fp8_e5m2", "s4")
#: Per-mode code maximum in the scaled domain: an honest blockwise
#: encoder maps each block's absmax to EXACTLY this code magnitude, so
#: the pre-decode inflation ratio qmax/max|code| of every nonzero block
#: is 1.0 — the invariant the residual-shaping detector leans on.
_WIRE_QMAX = {"int8": 127.0, "s4": 7.0, "fp8": 448.0, "fp8_e5m2": 57344.0}
#: Arrays below this element count always travel lossless (the scale
#: header would rival the payload).
WIRE_QUANT_MIN_SIZE = 1024
_WIRE_DEFAULT_BLOCK = 256


def _ml_f8_dtype(mode: str):
    import ml_dtypes

    return (
        ml_dtypes.float8_e4m3fn if mode == "fp8" else ml_dtypes.float8_e5m2
    )


def _wire_key() -> bytes | None:
    key = os.environ.get("BYZPY_TPU_WIRE_KEY")
    return key.encode() if key else None


#: Keyed HMAC bases, one per wire key ever seen (in practice: one).
#: ``hmac.new(key, ...)`` pays two SHA-256 block compressions just to
#: absorb the padded key; cloning a cached keyed base skips that setup,
#: which matters once ingress verifies whole batches of frames per
#: event-loop wakeup. Keys rotate via env restarts, so the cache is
#: bounded by construction; cleared defensively if it ever grows.
_HMAC_BASE: dict = {}


def _hmac_base(key: bytes) -> "hmac.HMAC":
    base = _HMAC_BASE.get(key)
    if base is None:
        if len(_HMAC_BASE) > 8:
            _HMAC_BASE.clear()
        base = _HMAC_BASE[key] = hmac.new(key, b"", hashlib.sha256)
    return base


def _sign(body, key: bytes) -> bytes:
    mac = _hmac_base(key).copy()
    mac.update(body)
    return mac.digest()

_LOOPBACK = {"127.0.0.1", "::1", "localhost"}  # "" binds ALL interfaces — warn


def warn_untrusted_bind(host: str, component: str) -> None:
    """One-line safety rail: surface a RuntimeWarning when a cloudpickle
    control-plane server binds beyond loopback, where deserializing frames
    means remote code execution for anyone who can reach the port."""
    if host not in _LOOPBACK:
        warnings.warn(
            f"{component} binding to {host!r}: the control-plane wire "
            "deserializes cloudpickle frames, which allows arbitrary code "
            "execution by anyone able to reach this socket. Use only on "
            "trusted/firewalled networks (or keep to loopback).",
            RuntimeWarning,
            stacklevel=3,
        )


def wire_precision() -> str:
    """Resolved ``BYZPY_TPU_WIRE_PRECISION`` policy: ``"off"``
    (default), ``"bf16"``, ``"int8"``, ``"fp8"``, ``"fp8_e5m2"``, or
    ``"s4"``. Unknown values degrade to ``"off"`` — the wire must never
    fail on a typo'd env var."""
    mode = os.environ.get(_WIRE_PRECISION_ENV, "off").lower()
    return mode if mode in WIRE_MODES else "off"


def _wire_block() -> int:
    try:
        block = int(os.environ.get(_WIRE_BLOCK_ENV, _WIRE_DEFAULT_BLOCK))
    except ValueError:
        return _WIRE_DEFAULT_BLOCK
    return block if block > 0 else _WIRE_DEFAULT_BLOCK


@dataclasses.dataclass(frozen=True)
class QuantizedWireArray:
    """One compressed tensor inside a wire frame: ``codes`` (int8 for
    ``int8`` mode, uint16 bf16 bit patterns for ``bf16``, uint8 float8
    bit patterns for ``fp8``/``fp8_e5m2``, block-padded packed nibbles
    for ``s4``), the per-block f32 ``scales`` header (``None`` for
    bf16), and enough metadata to reconstruct shape/dtype. Pickles
    alongside the rest of the payload, so the frame HMAC covers codes
    AND scales — a tampered scale block fails :func:`decode` before any
    dequantization runs."""

    mode: str
    codes: np.ndarray
    scales: Optional[np.ndarray]
    block: int
    shape: Tuple[int, ...]
    dtype: str


def _np_quantize(
    arr: np.ndarray, block: int
) -> Tuple[np.ndarray, np.ndarray, bool]:
    """Blockwise symmetric int8 over the flattened array (numpy mirror of
    ``parallel.quantization.quantize_blockwise``; parity is pinned by
    ``tests/test_quantized_wire.py``). The third return is False when any
    block's absmax is non-finite (an inf OR NaN input poisoned it — note
    a NaN absmax yields a *finite* scale of 1.0, so the caller must test
    this flag, not the scales) — the wire then ships the array lossless,
    preserving attack vectors verbatim."""
    flat = np.ascontiguousarray(arr, dtype=np.float32).ravel()
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    xb = flat.reshape(nb, block)
    absmax = np.max(np.abs(xb), axis=1)  # propagates inf AND NaN
    finite = bool(np.isfinite(absmax).all())
    scales = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        codes = np.clip(np.rint(xb / scales[:, None]), -127, 127).astype(np.int8)
    return codes.ravel()[:n], scales, finite


def _np_dequantize(
    codes: np.ndarray, scales: np.ndarray, block: int, shape, dtype
) -> np.ndarray:
    n = codes.size
    nb = scales.size
    pad = nb * block - n
    flat = codes.astype(np.float32)
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    out = (flat.reshape(nb, block) * scales[:, None]).ravel()[:n]
    return out.astype(dtype).reshape(shape)


def _np_blockwise_encode(
    arr: np.ndarray, block: int, mode: str
) -> Tuple[np.ndarray, np.ndarray, bool]:
    """Mode-generic blockwise encode over the flattened array (numpy
    mirror of ``parallel.quantization.encode_blockwise``; parity pinned
    by ``tests/test_quantized_wire.py``). Returns ``(codes, scales,
    finite)`` — ``finite=False`` means a block's absmax is non-finite
    and the frame must travel lossless (same contract as the int8
    codec). Codes are int8 for ``int8``, uint8 float8 bit patterns for
    ``fp8``/``fp8_e5m2``, and block-padded packed nibbles (uint8, two
    codes per byte) for ``s4``."""
    if mode == "int8":
        return _np_quantize(arr, block)
    flat = np.ascontiguousarray(arr, dtype=np.float32).ravel()
    n = flat.size
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    xb = flat.reshape(nb, block)
    absmax = np.max(np.abs(xb), axis=1)  # propagates inf AND NaN
    finite = bool(np.isfinite(absmax).all())
    qmax = _WIRE_QMAX[mode]
    scales = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        y = xb / scales[:, None]
        if mode == "s4":
            q = np.clip(np.rint(y), -7, 7).astype(np.int8)
            nib = (q + np.int8(8)).astype(np.uint8).reshape(-1)
            codes = nib[0::2] | (nib[1::2] << 4)  # padded: nb*block//2 bytes
        else:
            y = np.clip(y, -qmax, qmax)
            codes = y.astype(_ml_f8_dtype(mode)).view(np.uint8).ravel()[:n]
    return codes, scales, finite


def _code_values_f32(codes: np.ndarray, mode: str) -> np.ndarray:
    """Decoded f32 code values BEFORE the per-block scale multiply —
    the expensive half of a blockwise decode (the fp8 bit-pattern cast
    alone is ~57 % of that mode's decode on one CPU core, PR 19), shared
    by dequantization and the pre-decode inflation forensics so
    :func:`decode_with_stats` converts each frame's codes exactly once.
    Per-frame analogue of :func:`_rows_code_values`."""
    if mode == "int8":
        return codes.astype(np.float32)
    if mode == "s4":
        nib = np.empty(codes.size * 2, np.uint8)
        nib[0::2] = codes & np.uint8(0xF)
        nib[1::2] = codes >> 4
        return nib.astype(np.float32) - 8.0
    return codes.view(_ml_f8_dtype(mode)).astype(np.float32)


def _dequant_values(
    values: np.ndarray, scales: np.ndarray, block: int, shape, dtype
) -> np.ndarray:
    """The cheap tail of a blockwise decode: pad the f32 code values to
    whole blocks, apply the per-block scales, trim and reshape."""
    nb = scales.size
    n = 1
    for s in shape:
        n *= s
    pad = nb * block - values.size
    if pad > 0:
        values = np.concatenate([values, np.zeros(pad, np.float32)])
    out = (values.reshape(nb, block) * scales[:, None]).ravel()[:n]
    return out.astype(dtype).reshape(shape)


def _np_blockwise_decode(
    codes: np.ndarray, scales: np.ndarray, block: int, shape, dtype, mode: str
) -> np.ndarray:
    """Inverse of :func:`_np_blockwise_encode` (lossy)."""
    return _dequant_values(
        _code_values_f32(codes, mode), scales, block, shape, dtype
    )


def _np_to_bf16(arr: np.ndarray) -> Tuple[np.ndarray, bool]:
    """f32 -> bf16 bit patterns (uint16) with round-to-nearest-even.
    The second return is False when the frame must travel lossless:
    non-finite INPUTS (checked on the source exponent bits — a negative
    NaN's rounding add wraps uint32 and would otherwise encode as +0.0,
    silently sanitizing an adversarial payload) or finite values that
    overflow to inf in bf16 (checked on the output exponent bits)."""
    u = np.ascontiguousarray(arr, dtype=np.float32).view(np.uint32)
    exp_mask = np.uint32(0x7F800000)
    nonfinite_in = bool(np.any((u & exp_mask) == exp_mask))
    rounded = u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    codes = (rounded >> np.uint32(16)).astype(np.uint16)
    overflow_out = bool(
        np.any((codes & np.uint16(0x7F80)) == np.uint16(0x7F80))
    )
    return codes, not (nonfinite_in or overflow_out)


def _np_from_bf16(codes: np.ndarray, shape, dtype) -> np.ndarray:
    u = codes.astype(np.uint32) << 16
    return u.view(np.float32).astype(dtype).reshape(shape)


def _quantizable(arr: np.ndarray, min_size: int) -> bool:
    # lossless fallback for everything the blockwise codec can't carry
    # faithfully enough: non-float dtypes, object payloads, small arrays.
    # Non-finite payloads also fall back, but that is detected from the
    # codec's own per-block reductions (a NaN/inf absmax poisons its
    # scale, an overflowing bf16 cast sets exponent bits) instead of an
    # extra full-array isfinite pass on the hot encode path.
    return (
        isinstance(arr, np.ndarray)
        and arr.dtype.kind == "f"
        and arr.dtype.itemsize >= 4
        and arr.size >= min_size
        and not arr.dtype.hasobject
    )


def _map_payload_leaves(leaf_fn, obj: Any) -> Any:
    """Copy-on-write recursion over the wire payload containers
    (dataclasses, dicts, tuples/namedtuples, lists): ``leaf_fn`` maps a
    leaf to its replacement or returns it unchanged (identity). Untouched
    subtrees are returned AS-IS — a frame with nothing to transform pays
    one traversal and zero rebuilds, and payload dataclasses that cannot
    be ``dataclasses.replace``'d (e.g. ``init=False`` fields) only fail
    if a transformed leaf actually lives inside them. Both codec
    directions (:func:`compress_payload` / :func:`decompress_payload`)
    walk through here so the container semantics cannot drift; the shm
    tier's wrap/unwrap and the jax-aware :func:`host_view` keep their own
    walks (error-cleanup and registered-pytree semantics respectively)."""

    def walk(x: Any) -> Any:
        out = leaf_fn(x)
        if out is not x:
            return out
        if isinstance(x, QuantizedWireArray):
            # atomic: never descend into a frame (its scales header is a
            # float array a compress pass must not re-quantize)
            return x
        if dataclasses.is_dataclass(x) and not isinstance(x, type):
            new = {f.name: walk(getattr(x, f.name))
                   for f in dataclasses.fields(x)}
            if all(new[f.name] is getattr(x, f.name)
                   for f in dataclasses.fields(x)):
                return x
            return dataclasses.replace(x, **new)
        if isinstance(x, dict):
            new = {k: walk(v) for k, v in x.items()}
            if all(new[k] is v for k, v in x.items()):
                return x
            return new
        if isinstance(x, (tuple, list)):
            vals = [walk(v) for v in x]
            if all(a is b for a, b in zip(vals, x, strict=True)):
                return x
            if isinstance(x, list):
                return vals
            if hasattr(x, "_fields"):
                return type(x)(*vals)
            return tuple(vals)
        return x

    return walk(obj)


def compress_payload(
    obj: Any, mode: str, *, block: Optional[int] = None,
    min_size: int = WIRE_QUANT_MIN_SIZE,
) -> Any:
    """Swap large finite float arrays in a payload pytree for
    :class:`QuantizedWireArray` frames (``mode`` one of
    :data:`WIRE_MODES`; anything else returns ``obj`` unchanged).
    Non-float, object-dtype, small, and non-finite arrays pass through
    lossless (attack vectors arrive verbatim, the reference's
    semantics). Untouched subtrees are returned as-is."""
    if mode not in WIRE_MODES:
        return obj
    if type(obj) is dict and not any(
        isinstance(v, (np.ndarray, QuantizedWireArray, dict, list, tuple))
        or dataclasses.is_dataclass(v)
        for v in obj.values()
    ):
        return obj  # scalar-only frame (acks, control) — nothing to swap
    block = block or _wire_block()

    def leaf(x: Any) -> Any:
        if isinstance(x, QuantizedWireArray):
            return x
        if isinstance(x, np.ndarray) and _quantizable(x, min_size):
            if mode == "bf16":
                codes, ok = _np_to_bf16(x)
                if not ok:
                    return x
                return QuantizedWireArray(
                    "bf16", codes, None, block, x.shape, str(x.dtype)
                )
            codes, scales, finite = _np_blockwise_encode(x, block, mode)
            # cheap post-hoc non-finite detection from the codec's own
            # per-block absmax reduction (no extra full-array pass)
            if not finite:
                return x
            return QuantizedWireArray(
                mode, codes, scales, block, x.shape, str(x.dtype)
            )
        return x

    return _map_payload_leaves(leaf, obj)


def decompress_payload(obj: Any) -> Any:
    """Inverse of :func:`compress_payload`: every
    :class:`QuantizedWireArray` becomes a (lossy) numpy array again;
    everything else — including the whole payload when no compressed
    frame is present — passes through untouched."""

    def leaf(x: Any) -> Any:
        if isinstance(x, QuantizedWireArray):
            if x.mode == "bf16":
                return _np_from_bf16(x.codes, x.shape, x.dtype)
            return _np_blockwise_decode(
                x.codes, x.scales, x.block, x.shape, x.dtype, x.mode
            )
        return x

    return _map_payload_leaves(leaf, obj)


def frame_inflation(
    qwa: QuantizedWireArray, *, _values: Optional[np.ndarray] = None
) -> Optional[float]:
    """PRE-decode per-block inflation ratio of one blockwise frame:
    ``max over nonzero blocks of qmax / max|code|``.

    An honest blockwise encoder maps each block's absmax to exactly the
    code maximum (127 / 7 / the fp8 format max), so every nonzero
    block's ratio is 1.0 (stochastic rounding can dip one code step).
    A residual-shaping client inflates its per-block SCALES relative to
    the content it encodes — buying itself a coarser grid whose
    "quantization error" it steers via error feedback — which is
    invisible post-decode but shows pre-decode as max|code| well under
    qmax. Computed from the codes alone (no dequantization, no scale
    trust); ``None`` for non-blockwise frames (bf16 carries no scale
    header to shape). All-zero payloads report 1.0. ``_values`` lets
    the fused stats+decode walk hand in the frame's already-converted
    :func:`_code_values_f32` instead of converting again."""
    if qwa.mode not in BLOCKWISE_WIRE_MODES or qwa.scales is None:
        return None
    qmax = _WIRE_QMAX[qwa.mode]
    block = qwa.block
    vals = (
        _values
        if _values is not None
        else _code_values_f32(qwa.codes, qwa.mode)
    )
    if qwa.mode == "s4":
        # nibble 0 decodes to -8, outside the honest encoder's [-7, 7]
        # codomain; clamp so a hostile -8 cannot fake EXTRA magnitude
        mags = np.minimum(np.abs(vals), qmax)
    elif qwa.mode == "int8":
        mags = np.abs(vals)
    else:
        mags = np.minimum(np.abs(np.where(np.isfinite(vals), vals, qmax)), qmax)
    n = mags.size
    nb = qwa.scales.size
    pad = nb * block - n
    if pad > 0:
        mags = np.concatenate([mags, np.zeros(pad, np.float32)])
    blockmax = mags[: nb * block].reshape(nb, block).max(axis=1)
    nonzero = blockmax > 0
    if not nonzero.any():
        return 1.0
    return float(qmax / blockmax[nonzero].min())


def payload_block_stats(obj: Any) -> Optional[dict]:
    """Pre-decode wire forensics over a still-compressed payload: the
    worst :func:`frame_inflation` across every blockwise
    :class:`QuantizedWireArray` in the pytree (``None`` when the
    payload carries none — lossless and bf16 frames have no per-block
    scale header to shape). The serving ingress computes this BEFORE
    :func:`decompress_payload` runs and threads it into the forensics
    plane as the submission's ``wire_inflation`` feature."""
    worst: Optional[float] = None
    frames = 0

    def leaf(x: Any) -> Any:
        nonlocal worst, frames
        if isinstance(x, QuantizedWireArray):
            infl = frame_inflation(x)
            if infl is not None:
                frames += 1
                worst = infl if worst is None else max(worst, infl)
        return x

    _map_payload_leaves(leaf, obj)
    if worst is None:
        return None
    return {"max_inflation": worst, "frames": frames}


def _decompress_with_stats(raw: Any) -> Tuple[Any, Optional[dict]]:
    """:func:`payload_block_stats` + :func:`decompress_payload` in ONE
    pytree walk, with each blockwise frame's codes→f32 conversion done
    once and shared between the inflation forensics and the
    dequantization (the per-frame door previously ran it twice under
    ``decode_with_stats`` — ~57 % of an fp8 decode; byte parity with
    the two-pass shape is pinned by ``tests/test_quantized_wire.py``)."""
    worst: Optional[float] = None
    frames = 0

    def leaf(x: Any) -> Any:
        nonlocal worst, frames
        if not isinstance(x, QuantizedWireArray):
            return x
        if x.mode == "bf16":
            return _np_from_bf16(x.codes, x.shape, x.dtype)
        values = _code_values_f32(x.codes, x.mode)
        infl = frame_inflation(x, _values=values)
        if infl is not None:
            frames += 1
            worst = infl if worst is None else max(worst, infl)
        return _dequant_values(values, x.scales, x.block, x.shape, x.dtype)

    obj = _map_payload_leaves(leaf, raw)
    stats = (
        None if worst is None else {"max_inflation": worst, "frames": frames}
    )
    return obj, stats


_MAG_LUT: dict = {}


def _byte_mag_lut(mode: str) -> Tuple[np.ndarray, np.ndarray]:
    """Rank-compressed forensics table: ``(rank, mag_of_rank)`` where
    ``rank`` is a ``(256,)`` uint8 mapping each code byte to the RANK of
    the clamped magnitude the per-frame :func:`frame_inflation` assigns
    it (for s4, the max of the byte's two nibble magnitudes — valid per
    block whenever blocks hold whole bytes), and ``mag_of_rank`` maps
    ranks back to the exact f32 magnitudes. Block maxima run over uint8
    ranks (SIMD-max over a quarter the bytes of an f32 expansion); the
    rank order is magnitude-isomorphic, so mapping the winning rank
    back yields bit-for-bit the per-frame path's block maximum. Rank 0
    is always magnitude 0.0 (bytes 0x00 / 0x88 decode to zero), so
    zero-padding ragged tails in rank space is exact too."""
    ent = _MAG_LUT.get(mode)
    if ent is None:
        b = np.arange(256, dtype=np.uint8)
        qmax = _WIRE_QMAX[mode]
        if mode == "s4":
            lo = np.abs((b & np.uint8(0xF)).astype(np.float32) - 8.0)
            hi = np.abs((b >> 4).astype(np.float32) - 8.0)
            lut = np.minimum(np.maximum(lo, hi), qmax).astype(np.float32)
        elif mode == "int8":
            lut = np.abs(b.view(np.int8).astype(np.float32))
        else:
            vals = b.view(_ml_f8_dtype(mode)).astype(np.float32)
            lut = np.minimum(
                np.abs(np.where(np.isfinite(vals), vals, qmax)), qmax
            ).astype(np.float32)
        mag_of_rank = np.unique(lut)  # sorted ascending, <= 256 entries
        rank = np.searchsorted(mag_of_rank, lut).astype(np.uint8)
        _MAG_LUT[mode] = ent = (rank, mag_of_rank.astype(np.float32))
    return ent


def _rows_code_values(codes: np.ndarray, mode: str) -> np.ndarray:
    """Row-batched code -> f32 value expansion shared by the batched
    dequantizer and the batched forensics pass: ``codes`` is ``(R,
    ncodes)`` stacked wire codes, the result ``(R, nvals)`` f32 code
    values BEFORE scaling (s4 nibbles unpacked and recentred, fp8 bit
    patterns reinterpreted — non-finite patterns propagate, exactly as
    the per-frame codec's)."""
    if mode == "s4":
        nib = np.empty((codes.shape[0], codes.shape[1] * 2), np.uint8)
        nib[:, 0::2] = codes & np.uint8(0xF)
        nib[:, 1::2] = codes >> 4
        return nib.astype(np.float32) - 8.0
    if mode == "int8":
        return codes.astype(np.float32)
    return codes.view(_ml_f8_dtype(mode)).astype(np.float32)


def decode_rows_np(
    codes: np.ndarray, scales: np.ndarray, *, mode: str, block: int,
    d: int, dtype=np.float32,
) -> np.ndarray:
    """Row-batched numpy mirror of :func:`_np_blockwise_decode` over
    ``R`` stacked ``(d,)`` frames: ``codes`` is ``(R, ncodes)`` (``d``
    codes per row for int8/fp8, ``nb*block//2`` packed nibble bytes for
    s4), ``scales`` ``(R, nb)`` f32. Every arithmetic step is the
    per-frame codec's, applied elementwise across the row axis, so each
    output row is bit-identical to decoding its frame alone — the
    invariant the batched-vs-per-frame parity tests pin. This is also
    the host reference the in-jit ``parallel.quantization
    .dequantize_rows`` mirrors."""
    codes = np.asarray(codes)
    scales = np.asarray(scales)
    rows, nb = scales.shape
    flat = _rows_code_values(codes, mode)
    pad = nb * block - flat.shape[1]
    if pad > 0:
        flat = np.concatenate(
            [flat, np.zeros((rows, pad), np.float32)], axis=1
        )
    out = (flat.reshape(rows, nb, block) * scales[:, :, None]).reshape(
        rows, -1
    )[:, :d]
    return np.ascontiguousarray(out).astype(dtype, copy=False)


def rows_code_absmax(
    codes: np.ndarray, *, mode: str, block: int, nb: int
) -> np.ndarray:
    """Row-batched per-block max |code value| — ``(R, nb)`` f32 from
    ``(R, ncodes)`` stacked codes, UNclamped (a hostile s4 ``-8``
    nibble reports 8, a non-finite fp8 pattern propagates), so
    ``isfinite(absmax * scales)`` decides finiteness of the dequantized
    rows without materializing them: IEEE multiply is magnitude-
    monotone, hence the max-magnitude code's product is finite iff
    every code's product in that block is."""
    mags = np.abs(_rows_code_values(np.asarray(codes), mode))
    rows = mags.shape[0]
    pad = nb * block - mags.shape[1]
    if pad > 0:
        mags = np.concatenate(
            [mags, np.zeros((rows, pad), np.float32)], axis=1
        )
    return mags.reshape(rows, nb, block).max(axis=2)


def ef_precompensate(
    arr: np.ndarray,
    residual: Optional[np.ndarray],
    mode: Optional[str] = None,
    *,
    block: Optional[int] = None,
    min_size: int = WIRE_QUANT_MIN_SIZE,
) -> Tuple[np.ndarray, np.ndarray]:
    """Client-side error feedback for the lossy wire fabric: fold the
    previous frame's quantization residual into ``arr`` and return
    ``(compensated, new_residual)``.

    ``compensated`` is what the caller hands to :func:`encode` — the
    wire's own (deterministic) blockwise encode then reproduces exactly
    the encoding this function measured, so ``new_residual`` is
    precisely the error the receiver's decode will see this round and
    the transmitted stream telescopes across frames (the numpy mirror
    of ``parallel.quantization.ef_encode``). Frames the wire would ship
    LOSSLESS (small/non-finite payloads, ``mode`` off/bf16-less-stateful)
    deliver the compensation exactly, so the residual returns to zero.
    ``mode=None`` resolves ``BYZPY_TPU_WIRE_PRECISION``."""
    mode = wire_precision() if mode is None else mode
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    comp = arr if residual is None else arr + residual.astype(np.float32)
    zero = np.zeros_like(comp)
    if mode not in BLOCKWISE_WIRE_MODES:
        # bf16/off: no blockwise codec on the wire. bf16's cast error
        # is below the EF signal; carrying state for it buys nothing.
        return comp, zero
    if not _quantizable(comp, min_size):
        return comp, zero  # travels lossless: fully delivered
    block = block or _wire_block()
    codes, scales, finite = _np_blockwise_encode(comp, block, mode)
    if not finite:
        return comp, zero  # lossless fallback path delivers exactly
    dec = _np_blockwise_decode(
        codes, scales, block, comp.shape, np.float32, mode
    )
    return comp, comp - dec


#: (frames, bytes) counter pairs per direction, resolved ONCE on the
#: first telemetry-enabled frame — encode/decode are per-frame hot
#: paths and must not pay a registry get-or-create lookup per call.
_FRAME_COUNTER_CACHE: dict = {}


def _frame_counters(direction: str, nbytes: int) -> None:
    """Publish one wire frame into the process registry (telemetry-
    enabled path only; callers hold the flag check). Per-direction
    frame/byte counters are the measured side of the ingress/wire laws
    EQuARX-style comms tuning needs in flight."""
    pair = _FRAME_COUNTER_CACHE.get(direction)
    if pair is None:
        reg = _obs_metrics.registry()
        labels = {"direction": direction}
        pair = _FRAME_COUNTER_CACHE[direction] = (
            reg.counter(
                "byzpy_wire_frames_total",
                help="actor-wire frames encoded (tx) / decoded (rx)",
                labels=labels,
            ),
            reg.counter(
                "byzpy_wire_bytes_total",
                help="actor-wire frame bytes incl. length prefix and HMAC tag",
                labels=labels,
            ),
        )
    frames, nbytes_counter = pair
    frames.inc()
    nbytes_counter.inc(nbytes)


#: Reserved frame key carrying the sender's ``(trace_id, span_id)``
#: trace context across the process boundary (dict frames only; popped
#: and restored on decode — consumers never see it).
TRACE_CTX_KEY = "_trace_ctx"


def encode(obj: Any, *, precision: Optional[str] = None) -> bytes:
    """Pickle ``obj`` into a length-prefixed (optionally HMAC-signed) frame
    body. With ``BYZPY_TPU_WIRE_PRECISION`` set (``bf16``/``int8``), large
    finite float arrays ship as compressed frames (per-block scales in the
    header); the HMAC — unchanged — signs the whole body, compressed
    payload and scale headers included. ``precision`` overrides the env
    policy for THIS frame (``"off"`` forces lossless — frames whose bits
    are load-bearing, e.g. the sharded tier's partial folds, must not
    ride the lossy submit fabric).

    Trace propagation: with telemetry enabled and a span open in the
    caller (``tracing.wire_context()``), dict frames are stamped with a
    ``_trace_ctx`` key so the receiver's spans link as children of the
    sender's (client submit → shard admission, shard close → root
    merge). The stamp rides INSIDE the signed body — no frame-format
    change — and never touches the payload the consumer decodes
    (:func:`decode` pops it). Telemetry disabled: one flag check, the
    frame bytes are byte-identical to the pre-propagation wire."""
    mode = wire_precision() if precision is None else (
        precision if precision in WIRE_MODES else "off"
    )
    if _obs_runtime.STATE.enabled and type(obj) is dict:
        ctx = _obs_tracing.wire_context()
        if ctx is not None and TRACE_CTX_KEY not in obj:
            obj = {**obj, TRACE_CTX_KEY: (ctx[0], ctx[1])}
    body = cloudpickle.dumps(compress_payload(obj, mode))
    key = _wire_key()
    if key is not None:
        body = _sign(body, key) + body
    if _obs_runtime.STATE.enabled:
        _frame_counters("tx", _HEADER.size + len(body))
    return _HEADER.pack(len(body)) + body


def decode(body: bytes) -> Any:
    """Inverse of :func:`encode` (verifies the HMAC when signing is
    configured, then expands any compressed tensor frames — so a tampered
    code or scale byte fails verification before dequantization).

    A ``_trace_ctx`` stamp on a dict frame is popped (consumers see the
    payload they were sent) and — when telemetry is enabled — restored
    as the decoding task's current trace context, so the very next span
    this task opens (the admission span, the root's merge span) becomes
    the remote sender's child. Frames without a stamp leave the local
    context untouched (a decode inside an open local span must not
    orphan it)."""
    return _decode_impl(body, want_stats=False)[0]


def decode_with_stats(body: bytes) -> Tuple[Any, Optional[dict]]:
    """:func:`decode` plus the PRE-decode :func:`payload_block_stats` of
    the frame's compressed payload, captured between unpickle and
    dequantization (after HMAC verification — stats from a forged frame
    would be attacker-free ink). The serving ingress uses this so the
    forensics plane sees each submission's wire-side block-inflation
    ratio; stats are ``None`` for frames carrying no blockwise
    payload."""
    return _decode_impl(body, want_stats=True)


def _decode_impl(body: bytes, *, want_stats: bool) -> Tuple[Any, Optional[dict]]:
    if _obs_runtime.STATE.enabled:
        _frame_counters("rx", _HEADER.size + len(body))
    key = _wire_key()
    if key is not None:
        if len(body) < _SIG_LEN:
            raise ValueError("frame too short to carry an HMAC signature")
        sig, body = body[:_SIG_LEN], body[_SIG_LEN:]
        if not hmac.compare_digest(sig, _sign(body, key)):
            raise ValueError(
                "frame HMAC verification failed: wrong BYZPY_TPU_WIRE_KEY "
                "or tampered/unsigned frame"
            )
    raw = cloudpickle.loads(body)
    if want_stats:
        obj, stats = _decompress_with_stats(raw)
    else:
        obj, stats = decompress_payload(raw), None
    if type(obj) is dict and TRACE_CTX_KEY in obj:
        ctx = obj.pop(TRACE_CTX_KEY)
        if _obs_runtime.STATE.enabled:
            _obs_tracing.adopt_context(ctx)
    return obj, stats


@dataclasses.dataclass
class DecodedFrame:
    """One :func:`decode_batch` result slot: the decoded payload and its
    pre-decode forensics stats (:func:`payload_block_stats` semantics),
    or the exception the frame's verify/decode raised. A batch result
    is truncated at the first error slot — exactly the frames the
    per-frame path would have served before dropping the peer."""

    obj: Any = None
    stats: Optional[dict] = None
    error: Optional[BaseException] = None
    #: the frame's popped ``_trace_ctx`` stamp (None when unstamped) —
    #: a batched ingress adopts it per frame so each admission span
    #: stays the SENDING client's child, exactly like the per-frame
    #: door's decode-time adoption
    trace_ctx: Optional[Any] = None


def _qwa_group_key(q: QuantizedWireArray):
    codes = q.codes
    scales = q.scales
    return (
        q.mode, q.block, getattr(codes, "size", -1),
        str(getattr(codes, "dtype", "?")),
        -1 if scales is None else getattr(scales, "size", -1),
    )


def _qwa_honest_layout(q: QuantizedWireArray) -> bool:
    """True when the frame has exactly the layout the honest encoder
    emits — the precondition for the row-batched decode. Anything else
    (hand-crafted pickles with inconsistent code/scale sizes) takes the
    per-frame codec verbatim, so hostile frames fail — or pass — with
    exactly the per-frame path's semantics."""
    try:
        n = 1
        for s in q.shape:
            n *= int(s)
        codes = q.codes
        if not isinstance(codes, np.ndarray):
            return False
        if q.mode == "bf16":
            return q.scales is None and codes.size == n
        scales = q.scales
        if not isinstance(scales, np.ndarray) or q.block <= 0:
            return False
        nb = -(-n // q.block)
        if scales.size != nb:
            return False
        if q.mode == "s4":
            return codes.size * 2 == nb * q.block
        return codes.size == n
    except Exception:
        return False


def _batch_inflations(group: list) -> list:
    """:func:`frame_inflation` over a group of same-layout blockwise
    frames in one vectorized pass (bit-identical per frame: every step
    is the per-frame codec's, applied along a stacked row axis; the
    final division is done per frame with the same scalar types)."""
    q0 = group[0]
    qmax = _WIRE_QMAX[q0.mode]
    block = q0.block
    nb = group[0].scales.size
    codes = np.stack([q.codes.ravel() for q in group])
    canonical = codes.dtype == (
        np.dtype(np.int8) if q0.mode == "int8" else np.dtype(np.uint8)
    )
    if canonical and (q0.mode != "s4" or block % 2 == 0):
        # rank-LUT gather per code byte, block maxima in uint8 rank
        # space, winners mapped back to exact f32 magnitudes (for s4
        # the byte-level maxima equal nibble-level ones because blocks
        # hold whole bytes)
        rank_lut, mag_of_rank = _byte_mag_lut(q0.mode)
        ranks = np.take(rank_lut, codes.view(np.uint8))
        per_block = block // 2 if q0.mode == "s4" else block
        pad = nb * per_block - ranks.shape[1]
        if pad > 0:
            ranks = np.concatenate(
                [ranks, np.zeros((len(group), pad), np.uint8)], axis=1
            )
        blockmax = mag_of_rank[
            ranks[:, : nb * per_block]
            .reshape(len(group), nb, per_block)
            .max(axis=2)
        ]
    else:
        vals = _rows_code_values(codes, q0.mode)
        if q0.mode == "s4":
            mags = np.minimum(np.abs(vals), qmax)
        elif q0.mode == "int8":
            mags = np.abs(vals)
        else:
            mags = np.minimum(
                np.abs(np.where(np.isfinite(vals), vals, qmax)), qmax
            )
        pad = nb * block - mags.shape[1]
        if pad > 0:
            mags = np.concatenate(
                [mags, np.zeros((len(group), pad), np.float32)], axis=1
            )
        blockmax = mags[:, : nb * block].reshape(
            len(group), nb, block
        ).max(axis=2)
    masked = np.where(blockmax > 0, blockmax, np.float32(np.inf))
    mins = masked.min(axis=1)
    return [
        1.0 if not np.isfinite(mn) else float(qmax / mn) for mn in mins
    ]


def _batch_decode_group(group: list) -> list:
    """Vectorized :func:`_np_blockwise_decode` / :func:`_np_from_bf16`
    over a group of same-layout frames (honest layout pre-checked)."""
    q0 = group[0]
    codes = np.stack([q.codes.ravel() for q in group])
    if q0.mode == "bf16":
        flat = (codes.astype(np.uint32) << 16).view(np.float32)
        return [
            flat[i].astype(q.dtype).reshape(q.shape)
            for i, q in enumerate(group)
        ]
    scales = np.stack([q.scales.ravel() for q in group])
    rows = decode_rows_np(
        codes, scales, mode=q0.mode, block=q0.block,
        d=flat_size(q0.shape),
    )
    return [
        rows[i].astype(q.dtype, copy=False).reshape(q.shape)
        for i, q in enumerate(group)
    ]


def flat_size(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def decode_batch(
    bodies: Sequence, *, keep_quantized: bool = False
) -> list:
    """Batched :func:`decode_with_stats` over many frame bodies (bytes
    or memoryviews, length prefixes stripped): HMAC verification rides
    a cloned keyed base (the per-frame key schedule is amortized away),
    and the numpy codec mirrors + pre-decode block-inflation forensics
    run vectorized across every same-layout compressed tensor in the
    batch — one pass over the stacked codes instead of one per frame.
    Results are bit-identical to calling :func:`decode_with_stats` per
    frame (pinned by the ingress parity tests); frames whose payloads
    don't group (lossless, object, odd layouts) fall back to the
    per-frame codec inside the same call.

    ``keep_quantized=True`` leaves a dict frame's top-level
    ``"gradient"`` :class:`QuantizedWireArray` COMPRESSED when it is a
    well-formed 1-D blockwise float frame — the serving ingress admits
    codes+scales and dequantization happens inside the ragged fold's
    jitted program (device-side), not here. Stats are still computed
    for kept frames; ill-formed frames are decoded (and fail) exactly
    as the per-frame path would.

    Returns a list of :class:`DecodedFrame`, truncated after the first
    error slot: the per-frame TCP door drops a peer at the first bad
    frame, so later frames in the batch must not be served either.
    Trace context: the first stamped frame's ``_trace_ctx`` is adopted
    for the batch (the batch's admission span links to that sender);
    every frame's stamp is popped regardless."""
    telemetry = _obs_runtime.STATE.enabled
    key = _wire_key()
    base = _hmac_base(key) if key is not None else None
    out: list = []
    raws: list = []
    for body in bodies:
        if telemetry:
            _frame_counters("rx", _HEADER.size + len(body))
        try:
            payload = body
            if key is not None:
                if len(body) < _SIG_LEN:
                    raise ValueError(
                        "frame too short to carry an HMAC signature"
                    )
                sig, payload = body[:_SIG_LEN], body[_SIG_LEN:]
                mac = base.copy()
                mac.update(payload)
                if not hmac.compare_digest(bytes(sig), mac.digest()):
                    raise ValueError(
                        "frame HMAC verification failed: wrong "
                        "BYZPY_TPU_WIRE_KEY or tampered/unsigned frame"
                    )
            raw = cloudpickle.loads(payload)
        except Exception as exc:  # noqa: BLE001 — per-frame error slot
            out.append(DecodedFrame(error=exc))
            return out
        raws.append(raw)
        out.append(DecodedFrame(obj=raw))

    # one walk per frame collects its compressed tensors; same-layout
    # tensors across the whole batch then share one vectorized pass
    # (flat dicts — every honest submit frame — skip the generic
    # recursive walk for one shallow scan over the values)
    per_frame: list = []
    groups: dict = {}
    for raw in raws:
        qwas: list = []
        flat = type(raw) is dict
        if flat:
            for v in raw.values():
                if isinstance(v, QuantizedWireArray):
                    qwas.append(v)
                elif isinstance(v, (dict, list, tuple)) or (
                    dataclasses.is_dataclass(v) and not isinstance(v, type)
                ):
                    flat = False
                    qwas.clear()
                    break
        if not flat:

            def leaf(x, _q=qwas):
                if isinstance(x, QuantizedWireArray):
                    _q.append(x)
                return x

            _map_payload_leaves(leaf, raw)
        per_frame.append(qwas)
        for q in qwas:
            if _qwa_honest_layout(q):
                groups.setdefault(_qwa_group_key(q), []).append(q)

    infl: dict = {}
    dec: dict = {}
    keep: set = set()
    if keep_quantized:
        for raw in raws:
            if type(raw) is not dict:
                continue
            g = raw.get("gradient")
            if (
                isinstance(g, QuantizedWireArray)
                and g.mode in BLOCKWISE_WIRE_MODES
                and len(g.shape) == 1
                and _qwa_honest_layout(g)
            ):
                try:
                    if np.dtype(g.dtype).kind == "f":
                        keep.add(id(g))
                except TypeError:
                    pass
    for gkey, group in groups.items():
        mode = gkey[0]
        if mode in BLOCKWISE_WIRE_MODES:
            try:
                for q, r in zip(group, _batch_inflations(group)):
                    infl[id(q)] = r
            except Exception:  # noqa: BLE001 — per-frame fallback below
                pass
        to_decode = [q for q in group if id(q) not in keep]
        if not to_decode:
            continue
        try:
            for q, row in zip(to_decode, _batch_decode_group(to_decode)):
                dec[id(q)] = row
        except Exception:  # noqa: BLE001 — per-frame fallback below
            pass

    adopted = False
    for i, raw in enumerate(raws):
        qwas = per_frame[i]
        worst = None
        frames = 0
        try:
            for q in qwas:
                r = infl.get(id(q))
                if r is None:
                    r = frame_inflation(q)
                if r is not None:
                    frames += 1
                    worst = r if worst is None else max(worst, r)
            stats = (
                None if worst is None
                else {"max_inflation": worst, "frames": frames}
            )

            def leaf(x):
                if isinstance(x, QuantizedWireArray):
                    if id(x) in keep:
                        return x
                    row = dec.get(id(x))
                    if row is not None:
                        return row
                    if x.mode == "bf16":
                        return _np_from_bf16(x.codes, x.shape, x.dtype)
                    return _np_blockwise_decode(
                        x.codes, x.scales, x.block, x.shape, x.dtype,
                        x.mode,
                    )
                return x

            needs_map = any(id(q) not in keep for q in qwas)
            obj = _map_payload_leaves(leaf, raw) if needs_map else raw
        except Exception as exc:  # noqa: BLE001 — per-frame error slot
            del out[i:]
            out.append(DecodedFrame(error=exc))
            return out
        ctx = None
        if type(obj) is dict and TRACE_CTX_KEY in obj:
            ctx = obj.pop(TRACE_CTX_KEY)
            if telemetry and not adopted:
                adopted = True
                _obs_tracing.adopt_context(ctx)
        out[i] = DecodedFrame(obj=obj, stats=stats, trace_ctx=ctx)
    return out


def host_view(obj: Any) -> Any:
    """Convert any jax.Arrays in a payload pytree to numpy before it crosses
    a process or network boundary (device buffers don't pickle portably and
    must never transit the control plane anyway). Dataclass envelopes
    (e.g. ``Message``) are rebuilt field-by-field — they are not registered
    pytrees, so a plain ``tree_map`` would pass their device arrays through
    untouched."""
    import dataclasses

    import jax
    import numpy as np

    def _is_dc(x: Any) -> bool:
        return dataclasses.is_dataclass(x) and not isinstance(x, type)

    if _is_dc(obj):
        return dataclasses.replace(
            obj,
            **{
                f.name: host_view(getattr(obj, f.name))
                for f in dataclasses.fields(obj)
            },
        )

    def conv(leaf: Any) -> Any:
        if _is_dc(leaf):
            return host_view(leaf)
        if isinstance(leaf, jax.Array):
            return np.asarray(leaf)
        return leaf

    return jax.tree_util.tree_map(conv, obj, is_leaf=_is_dc)


async def send_obj(writer: asyncio.StreamWriter, obj: Any) -> None:
    """Write one encoded frame to the stream and drain."""
    writer.write(encode(obj))
    await writer.drain()


async def recv_obj(reader: asyncio.StreamReader) -> Any:
    """Read exactly one frame from the stream and decode it."""
    header = await reader.readexactly(_HEADER.size)
    (length,) = _HEADER.unpack(header)
    if length > MAX_FRAME:
        raise ValueError(f"frame too large: {length}")
    body = await reader.readexactly(length)
    return decode(body)


__all__ = [
    "BLOCKWISE_WIRE_MODES",
    "TRACE_CTX_KEY",
    "WIRE_MODES",
    "send_obj",
    "recv_obj",
    "encode",
    "decode",
    "decode_batch",
    "decode_rows_np",
    "decode_with_stats",
    "DecodedFrame",
    "rows_code_absmax",
    "ef_precompensate",
    "frame_inflation",
    "host_view",
    "payload_block_stats",
    "warn_untrusted_bind",
    "wire_precision",
    "compress_payload",
    "decompress_payload",
    "QuantizedWireArray",
    "WIRE_QUANT_MIN_SIZE",
]
