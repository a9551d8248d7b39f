"""Communication accounting: per-round collective traffic, measured from
the compiled program, plus the analytic ICI scaling model.

The reference argues its transport layer's efficiency by construction
(UCX device-to-device, ``byzpy/engine/actor/transports/ucx.py``); a
compiled SPMD program lets us do better — XLA's optimized HLO states
exactly which collectives run with which shapes, so the bytes a training
round moves are a *measurement of the compiled artifact*, not a claim.
:func:`collective_traffic` parses them out of any jitted function;
:func:`scaling_model` turns (FLOPs, bytes-moved) into the analytic
ICI-bound efficiency table that the 8→128-chip ≥90% north star rests on
(single-host CPU cannot measure that; the model + the compiled byte
counts are the checkable substitute).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    # fp8 families (quantized fabrics; XLA spells both the IEEE-ish and
    # the -fn/-fnuz saturating variants)
    "f8e4m3": 1, "f8e5m2": 1, "f8e4m3fn": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
    # s4/u4 pack two values per byte; HLO sizes them at 1 byte minimum
    "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# matches sync collectives AND the -start half of async pairs; the -done
# twin repeats the shape and is excluded so nothing double-counts. The
# result shape is whatever lies between "= " and the opcode: TPU layouts
# carry parentheses of their own (`f32[11173964]{0:T(1024)S(1)}`), so a
# tuple shape cannot be matched as one balanced group — that form made
# every tuple-shaped collective of a TPU program invisible (PR 21: the
# d-sized all-reduce XLA:TPU lowers the params gather to).
_INSTR_RE = re.compile(
    r"=\s*(.*?)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\(",
)
_ENTRY_RE = re.compile(r"^ENTRY\s")
_COMPUTATION_RE = re.compile(r"^%?\S+\s*(?:\([^)]*\))?\s*->.*\{\s*$|^ENTRY\s")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every array shape mentioned in ``shape_text``
    (handles tuple shapes by summing members)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction in the optimized HLO (per-device view)."""

    opcode: str
    result_bytes: int  # bytes of the per-device result buffer(s)
    group_size: int  # devices participating in each replica group
    in_entry: bool = True  # False: inside a called computation (e.g. a
    # while-loop body) — executes an unknown number of times per
    # invocation, so its bytes are a LOWER bound (reported separately)

    @property
    def wire_bytes_per_device(self) -> int:
        """Bytes each device puts on the interconnect for this op, under
        the standard ring schedules XLA uses on TPU:

        * all-gather: receives (g-1)/g of the result -> sends the same.
        * all-reduce: ring reduce-scatter + all-gather = 2·(g-1)/g of the
          buffer.
        * reduce-scatter: (g-1)/g of the *input* (= result · (g-1)).
        * all-to-all: (g-1)/g of the result leaves the device.
        * collective-permute: the whole buffer moves to the neighbor.
        """
        g = max(self.group_size, 1)
        b = self.result_bytes
        if self.opcode == "all-gather":
            return b * (g - 1) // g
        if self.opcode == "all-reduce":
            return 2 * b * (g - 1) // g
        if self.opcode == "reduce-scatter":
            return b * (g - 1)
        if self.opcode == "all-to-all":
            return b * (g - 1) // g
        return b  # collective-permute


def _parse_group_size(line: str, default: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        members = [p for p in m.group(1).split(",") if p.strip() != ""]
        return max(len(members), 1)
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota form [G,S]<=[N]: G groups of S devices
        return max(int(m.group(2)), 1)
    return default


def collectives_in_hlo(hlo_text: str, *, default_group: int = 1) -> List[CollectiveOp]:
    """Every collective instruction in an optimized-HLO dump.

    Sync opcodes and the ``-start`` half of async pairs are counted
    (``-done`` repeats the shape and is skipped). Instructions inside
    non-ENTRY computations — while-loop bodies, conditionals — execute a
    runtime-dependent number of times; they are tagged
    ``in_entry=False`` and their bytes are a per-iteration lower bound.
    """
    out: List[CollectiveOp] = []
    in_entry = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{"):
            in_entry = bool(_ENTRY_RE.match(stripped))
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_text, opcode = m.group(1), m.group(2)
        out.append(
            CollectiveOp(
                opcode=opcode,
                result_bytes=_shape_bytes(shape_text),
                group_size=_parse_group_size(line, default_group),
                in_entry=in_entry,
            )
        )
    return out


def collective_traffic(
    fn: Callable,
    *args: Any,
    default_group: Optional[int] = None,
    **kwargs: Any,
) -> Dict[str, Any]:
    """Compile ``fn(*args)`` (jit if not already) and account its
    collectives: returns ``{"ops": [...], "per_opcode_bytes": {...},
    "wire_bytes_per_device": N}`` for ONE invocation (= one training
    round when ``fn`` is a round step)."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    if default_group is None:
        default_group = len(jax.devices())
    ops = collectives_in_hlo(compiled.as_text(), default_group=default_group)
    per: Dict[str, int] = {}
    loop_bytes = 0
    for op in ops:
        if op.in_entry:
            per[op.opcode] = per.get(op.opcode, 0) + op.wire_bytes_per_device
        else:
            loop_bytes += op.wire_bytes_per_device
    return {
        "ops": ops,
        "per_opcode_bytes": per,
        "wire_bytes_per_device": sum(per.values()),
        # collectives inside loop/cond bodies: per-iteration bytes; the
        # true per-invocation total is this x the (runtime) trip count
        "loop_body_bytes_per_iteration": loop_bytes,
    }


@dataclass(frozen=True)
class ScalingPoint:
    """One row of the analytic efficiency table."""

    n_chips: int
    compute_s: float
    comm_s: float

    @property
    def efficiency(self) -> float:
        """Fraction of perfect weak scaling: compute / (compute + exposed
        comm), assuming no compute/comm overlap (pessimistic)."""
        return self.compute_s / (self.compute_s + self.comm_s)


def compression_factor(
    precision: str = "off", *, block: int = 256, dtype_bytes: int = 4
) -> float:
    """Wire-byte multiplier of a compressed fabric relative to its
    full-precision baseline: 1.0 for ``"off"``, ``2/dtype_bytes`` for
    ``"bf16"``, ``(1 + 4/block)/dtype_bytes`` for ``"int8"`` and the
    fp8 formats (one byte per value is one byte per value), and
    ``(0.5 + 4/block)/dtype_bytes`` for packed ``"s4"``. The
    law itself lives on
    :meth:`~byzpy_tpu.parallel.quantization.CommPrecision.wire_bytes_per_value`
    (single source of truth for the blockwise wire layout); this wrapper
    only normalizes it to a ratio. Lazy import keeps this module's
    top-level jax-free, like :func:`collective_traffic`."""
    from .quantization import CommPrecision, as_comm_precision

    p = as_comm_precision(precision or "off")
    if p.block != block:
        p = CommPrecision(mode=p.mode, block=block)
    return p.wire_bytes_per_value(dtype_bytes) / dtype_bytes


def opt_state_bytes(
    n_params: int,
    *,
    slots: int = 1,
    dtype_bytes: int = 4,
    update_sharded: bool = False,
    n_shards: int = 1,
) -> int:
    """Per-chip bytes of the round's carried weight-update state.

    A replicated update keeps ``slots`` full d-sized moment buffers on
    EVERY chip (SGD+momentum: 1; Adam: 2). The sharded update
    (``parallel.ps.ShardedUpdateConfig``) carries ``slots + 1`` buffers
    — every moment plus the chip's authoritative exact flat param shard
    — each split over the ``n_shards``-way feature grid (ceil: d pads to
    the grid): a ``slots·n/(slots+1)``× cut (4× at n=8 for momentum,
    5.3× for Adam; → n× as slots grow)."""
    if not update_sharded or n_shards <= 1:
        return slots * n_params * dtype_bytes
    per_shard = -(-n_params // n_shards)
    return (slots + 1) * per_shard * dtype_bytes


def measured_opt_state_bytes(opt_state: Any) -> int:
    """Per-chip bytes the carried update state ACTUALLY occupies, from
    each leaf's shard shape — the measured side of the
    :func:`opt_state_bytes` law (used by the probe, the sharded-update
    bench, and its tests; lazy import keeps this module jax-free at the
    top level)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or not hasattr(leaf, "shape"):
            continue
        n = 1
        for dim in sharding.shard_shape(leaf.shape):
            n *= int(dim)
        total += n * leaf.dtype.itemsize
    return total


def ps_round_wire_bytes(
    n_params: int,
    n_chips: int,
    *,
    dtype_bytes: int = 4,
    update_sharded: bool = False,
    grad_precision: str = "off",
    param_precision: str = "off",
    quant_block: int = 256,
) -> float:
    """Closed-form per-device wire bytes of the fused PS round's two
    dominant collectives (validated against compiled HLO by
    ``benchmarks/sharded_update_bench.py``):

    * the gradient transpose — an all-to-all moving ``d·dt·(n-1)/n``,
      compressible per ``grad_precision`` (the PR-3 fabric);
    * the update move — an all-gather of ``d`` values with the same
      ``(n-1)/n`` law. Replicated update: the f32 *aggregated gradient*
      is gathered and must stay exact (it feeds every chip's optimizer
      state), so ``param_precision`` is ignored. Sharded update: only
      the *refreshed params* are gathered, each chip's exact shard stays
      in the carried opt state, and the gather compresses per
      ``param_precision`` without compounding error.

    Robust-aggregation traffic itself (a scalar or an (n, n) Gram psum)
    is negligible next to these at ``d >= 1e5``."""
    g = max(n_chips, 1)
    saturate = (g - 1) / g
    transpose = (
        n_params * dtype_bytes
        * compression_factor(grad_precision, block=quant_block, dtype_bytes=dtype_bytes)
        * saturate
    )
    pfac = (
        compression_factor(param_precision, block=quant_block, dtype_bytes=dtype_bytes)
        if update_sharded
        else 1.0
    )
    gather = n_params * dtype_bytes * pfac * saturate
    return transpose + gather


#: Measured cloudpickle envelope of one serving submission frame (the
#: dict keys, tenant/client strings, numpy array header — everything
#: but the length prefix, HMAC tag, and gradient payload), per wire
#: precision: compressed frames carry a ``QuantizedWireArray`` header
#: (mode/block/shape/dtype + the scales array's own pickle framing).
#: Pinned within tolerance by ``tests/test_serving.py``.
_SERVING_ENVELOPE_BYTES = {
    "off": 224, "bf16": 368, "int8": 432,
    # sub-int8 frames carry the same QuantizedWireArray header as int8
    # (mode string length and scale-array framing shift it a few bytes)
    "fp8": 431, "fp8_e5m2": 436, "s4": 430,
}


def serving_ingress_bytes(
    n_params: int,
    *,
    precision: str = "off",
    quant_block: int = 256,
    signed: bool = False,
    dtype_bytes: int = 4,
    envelope_bytes: Optional[int] = None,
) -> float:
    """Analytic wire bytes of ONE client gradient submission entering
    the serving tier (``byzpy_tpu.serving``): the 4-byte length prefix,
    the 32-byte HMAC tag when ``signed`` (``BYZPY_TPU_WIRE_KEY``), the
    cloudpickle envelope, and the gradient payload —
    ``n_params · dtype_bytes`` scaled by :func:`compression_factor` for
    the ``BYZPY_TPU_WIRE_PRECISION`` fabric the frame rides
    (``off``/``bf16``/``int8``/``fp8``/``fp8_e5m2``/``s4``). Multiply by sustained submissions/sec
    for the tier's ingress-bandwidth law; the measured side is the
    frontend's per-tenant ``ingress_bytes`` counter and
    ``benchmarks/serving_bench.py``'s accounting lane.

    Known small bias: with telemetry ENABLED the client stamps each
    submit frame with its ``_trace_ctx`` trace context (~60 pickled
    bytes, ``engine.actor.wire``) which this law deliberately does not
    price — the measured side only exists with telemetry on, so the
    residual pins carry a systematic +0.4% at d=4096 f32 (~1.5% on the
    int8 fabric), well inside the 5% smoke tolerance; the <2% test
    pins measure telemetry-off frames."""
    mode = (precision or "off").lower()
    if envelope_bytes is None:
        envelope_bytes = _SERVING_ENVELOPE_BYTES.get(
            mode, _SERVING_ENVELOPE_BYTES["off"]
        )
    payload = (
        n_params
        * dtype_bytes
        * compression_factor(mode, block=quant_block, dtype_bytes=dtype_bytes)
    )
    return 4 + (32 if signed else 0) + envelope_bytes + payload


#: Measured cloudpickle envelope of one PartialFold frame (dict keys,
#: tenant/digest strings, array headers — everything but the length
#: prefix, HMAC tag, per-row identity fields, row payload and extras)
#: and the per-row identity cost at the default ~6-char client ids
#: (pickled client string ≈ id + 7 framing bytes, seq/wal small ints).
#: Pinned within tolerance by ``tests/test_sharded_serving.py``.
_PARTIAL_FOLD_ENVELOPE_BYTES = 310
_PARTIAL_FOLD_ROW_FRAMING_BYTES = 7
#: Measured envelope of the root's merge-result broadcast frame.
_MERGE_BROADCAST_ENVELOPE_BYTES = 229


def partial_fold_bytes(
    m: int,
    n_params: int,
    *,
    signed: bool = False,
    extras_bytes: float = 0.0,
    client_id_bytes: int = 6,
    dtype_bytes: int = 4,
    envelope_bytes: Optional[int] = None,
) -> float:
    """Analytic wire bytes of ONE shard's :class:`~byzpy_tpu.serving.
    PartialFold` frame on the shard→root hop (``serving.sharded``): the
    4-byte length prefix, the 32-byte HMAC tag when ``signed``, the
    frame envelope, ``m`` per-row identities (client id + seq + wal id
    pickle framing), the ``m · n_params`` float32 row payload — ALWAYS
    lossless: the rows' exact bits are load-bearing (digest cross-check
    + the hierarchical fold's bit-parity contract), so the submit
    fabric's ``BYZPY_TPU_WIRE_PRECISION`` compression never applies to
    this hop — and the family's streaming-accumulator ``extras_bytes``
    (trimmed mean ``(2f+1)·d·4``; Multi-Krum ``m²·4`` Gram block; CGE
    ``m·4`` norms; 0 for families without extras)."""
    per_row = client_id_bytes + _PARTIAL_FOLD_ROW_FRAMING_BYTES
    if envelope_bytes is None:
        envelope_bytes = _PARTIAL_FOLD_ENVELOPE_BYTES
    return (
        4
        + (32 if signed else 0)
        + envelope_bytes
        + m * per_row
        + m * n_params * dtype_bytes
        + extras_bytes
    )


def sharded_round_wire_bytes(
    n_shards: int,
    n_clients_round: int,
    n_params: int,
    *,
    precision: str = "off",
    signed: bool = False,
    quant_block: int = 256,
    extras_bytes_per_shard: float = 0.0,
    client_id_bytes: int = 6,
    dtype_bytes: int = 4,
) -> float:
    """Closed-form per-ROUND wire bytes of the sharded frontend tier
    (``serving.sharded``), three hops:

    * **client → home shard**: ``n_clients_round`` submit frames, each
      priced by :func:`serving_ingress_bytes` (the PR-6 law — this hop
      rides the compressed fabric when configured);
    * **shard → root**: one :func:`partial_fold_bytes` frame per shard
      carrying its ``n_clients_round / n_shards`` rows LOSSLESS (the
      bit-parity hop; the aggregate per-round row payload is the same
      ``n · d · 4`` the single frontend would fold — sharding moves it
      across a wire once, it does not multiply it);
    * **root → shard**: the merge-result broadcast, one lossless
      ``(d,)`` aggregate frame per shard.

    Sub-laws are exposed separately; the measured side is
    ``benchmarks/serving_bench.py``'s scale lane (pinned < 2%)."""
    submits = n_clients_round * serving_ingress_bytes(
        n_params,
        precision=precision,
        signed=signed,
        quant_block=quant_block,
        dtype_bytes=dtype_bytes,
    )
    per_shard_m = n_clients_round / max(n_shards, 1)
    partials = n_shards * partial_fold_bytes(
        per_shard_m,
        n_params,
        signed=signed,
        extras_bytes=extras_bytes_per_shard,
        client_id_bytes=client_id_bytes,
        dtype_bytes=dtype_bytes,
    )
    broadcast = n_shards * (
        4
        + (32 if signed else 0)
        + _MERGE_BROADCAST_ENVELOPE_BYTES
        + n_params * dtype_bytes
    )
    return submits + partials + broadcast


#: Measured per-segment pickle framing of a combined PartialFold's
#: ``segments`` list (one ``[shard, m]`` pair ≈ two small ints + list
#: envelope). Pinned alongside the partial-fold law.
_MERGE_SEGMENT_BYTES = 10


def merge_tree_wire_bytes(
    n_shards: int,
    fanout: Optional[int],
    n_clients_round: int,
    n_params: int,
    *,
    signed: bool = False,
    extras_bytes_per_row: float = 0.0,
    client_id_bytes: int = 6,
    dtype_bytes: int = 4,
) -> float:
    """Closed-form per-round bytes of the depth-N merge tree's FOLD
    hops (``serving.runner`` / ``MergeTopology``): at every tree level
    the partial-fold row payload crosses a wire once more — level 0
    ships ``n_shards`` flat frames (the PR-12 shard→root hop), each
    internal level re-ships the combined rows up in fewer, larger
    frames (plus per-segment framing). ``fanout=None`` degenerates to
    the flat single-hop law, so
    ``sharded_round_wire_bytes(...) - flat fold hop + this`` prices a
    deep deployment. The per-row identity and extras costs repeat per
    level too (a combined frame carries its leaves' client ids and the
    family's recomputed accumulators).

    The structural point the law makes explicit: depth multiplies FOLD
    wire bytes by the level count while dividing the per-node frame
    COUNT — the trade pays when the root's verify+merge CPU (the PR-13
    blame table's 37.5% at 4 shards), not the fabric, is the
    bottleneck. Measured side:
    ``benchmarks/serving_bench.py --processes`` (depth A/B lane)."""
    from ..serving.sharded import MergeTopology

    topo = MergeTopology(n_shards, fanout)
    per_shard_m = n_clients_round / max(n_shards, 1)

    def frame(m_rows: float, segments: int) -> float:
        return partial_fold_bytes(
            m_rows,
            n_params,
            signed=signed,
            extras_bytes=extras_bytes_per_row * m_rows,
            client_id_bytes=client_id_bytes,
            dtype_bytes=dtype_bytes,
        ) + segments * _MERGE_SEGMENT_BYTES

    total = n_shards * frame(per_shard_m, 1)
    for level in topo.levels:
        for group in level:
            total += frame(per_shard_m * len(group), len(group))
    return total


def scaling_model(
    *,
    flops_per_chip: float,
    wire_bytes_fn: Callable[[int], float],
    chip_flops: float = 197e12,  # v5e bf16 peak
    ici_bytes_per_s: float = 4.5e10,  # v5e: 45 GB/s per direction per link
    chips: Sequence[int] = (8, 16, 32, 64, 128),
    mfu: float = 0.4,
    precision: str = "off",
    quant_block: int = 256,
) -> List[ScalingPoint]:
    """Analytic weak-scaling table: per-chip compute stays constant
    (``flops_per_chip`` at ``mfu`` of peak), per-chip wire bytes follow
    ``wire_bytes_fn(n_chips)`` (use :func:`collective_traffic` at a small
    mesh and the collectives' (g-1)/g laws to extrapolate), and the link
    runs at ``ici_bytes_per_s``. Effiency ≥ target iff comm stays hidden
    under compute / (1 - target).

    ``precision`` extends the model to the compressed fabrics:
    ``wire_bytes_fn`` keeps describing the FULL-precision (f32) traffic
    and the comm term is scaled by :func:`compression_factor` — so one
    measured byte inventory predicts all three wire modes."""
    factor = compression_factor(precision, block=quant_block)
    points = []
    for n in chips:
        compute_s = flops_per_chip / (chip_flops * mfu)
        comm_s = wire_bytes_fn(n) * factor / ici_bytes_per_s
        points.append(ScalingPoint(n, compute_s, comm_s))
    return points


__all__ = [
    "CollectiveOp",
    "collectives_in_hlo",
    "collective_traffic",
    "ScalingPoint",
    "compression_factor",
    "measured_opt_state_bytes",
    "merge_tree_wire_bytes",
    "opt_state_bytes",
    "partial_fold_bytes",
    "ps_round_wire_bytes",
    "scaling_model",
    "serving_ingress_bytes",
    "sharded_round_wire_bytes",
]
