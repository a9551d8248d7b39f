"""Collectives of a compiled program, counted from its optimized HLO text.

A copy of the parser PR 21 repaired for TPU text (tuple shapes whose
layouts carry parentheses), kept with the benchmark so that no later PR
can move the yardstick. The original is ``byzpy_tpu/parallel/comms.py``
(``collectives_in_hlo``); PERF.md lists it for deletion or sharing.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List

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



def wire_bytes_per_device(hlo_text: str, *, default_group: int) -> Dict[str, int]:
    """Bytes each device moves per invocation, by opcode, over the ENTRY
    computation's collectives (loop bodies run an unknown number of times
    and are left out)."""
    per: Dict[str, int] = {}
    for op in collectives_in_hlo(hlo_text, default_group=default_group):
        if op.in_entry:
            per[op.opcode] = per.get(op.opcode, 0) + op.wire_bytes_per_device
    return per
