"""The types the timed step asks the compiler for, read from its lowered
text (StableHLO, before any compiler pass).

A configuration states the type its values are kept and computed in
(``stated_dtype``). That is a guarantee, and a comparison of numbers
cannot hold it here: at the TPU's default contraction precision two
compilations of the same float32 mathematics differ by more than a
bfloat16 rounding of every gradient (PERF.md section 2). So ``correct``
reads it from the program the window runs: no value in it may be
narrower than stated.

What this cannot see: a Pallas kernel's body (serialized in the custom
call's configuration), which may narrow on the way through.
"""

from __future__ import annotations

import re
from typing import Dict

_TENSOR = re.compile(r"tensor<((?:\d+x)*)([a-z][a-zA-Z0-9]*)>")
_TYPE = re.compile(r"(bf|tf|f|ui|si|i)(\d+)")
_NUMPY_NAMES = {"float64": "f64", "float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def bits_and_kind(mlir_type: str):
    """``"bf16" -> (16, "float")``, ``"f8E4M3FN" -> (8, "float")``,
    ``"ui8" -> (8, "int")``; ``(None, None)`` for anything else."""
    found = _TYPE.match(mlir_type)
    if found is None:
        return None, None
    return int(found.group(2)), "int" if found.group(1) in ("ui", "si", "i") else "float"


def largest_by_type(text: str) -> Dict[str, int]:
    """Element type -> the element count of the largest tensor of that
    type anywhere in ``text`` (arguments, results and every value between)."""
    largest: Dict[str, int] = {}
    for dims, mlir_type in _TENSOR.findall(text):
        count = 1
        for dim in dims.split("x"):
            if dim:
                count *= int(dim)
        largest[mlir_type] = max(largest.get(mlir_type, 0), count)
    return largest


def narrow_elements(text: str, stated_dtype: str) -> int:
    """Elements of the largest tensor in ``text`` whose type is narrower
    than ``stated_dtype``: a float of fewer bits, or an integer of 2 to 16
    bits (quantised values; ``i1`` masks and 32-bit indices are neither).
    0 when the program keeps what the configuration states."""
    stated_bits, _ = bits_and_kind(_NUMPY_NAMES.get(stated_dtype, stated_dtype))
    if stated_bits is None:
        raise ValueError(f"no bit width known for stated_dtype {stated_dtype!r}")
    worst = 0
    for mlir_type, count in largest_by_type(text).items():
        bits, kind = bits_and_kind(mlir_type)
        if bits is None:
            continue
        if (kind == "float" and bits < stated_bits) or (kind == "int" and 1 < bits <= 16):
            worst = max(worst, count)
    return worst
