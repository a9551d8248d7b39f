"""The held experts' loads beside the round they are multiplied in.

``byzpy_tpu.parallel.moe.held_experts_ffn`` multiplies a worker's tokens
in rounds of ``rows`` slots an expert, as many rounds as the pass's
fullest held expert needs: the cost of a layer pass steps at every whole
multiple of ``rows``. A cell whose fullest expert sits near such a
multiple runs one round more or fewer by which side a draw fell, and its
rate moves by a round's cost (0.9-1.3 % of the Nemotron step a layer:
PERF.md section 6, PR 50). This module reads the three things that say
how near: each layer's fullest expert over the run's passes (from the
step's own ``segment_aux``), the rows of a round (from ``segment_aux``
where the program reports ``expert_round_rows``, else from the compiled
step's text), and the distance between them.
"""

from __future__ import annotations

import collections
import re
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_SEGMENT = re.compile(r"segment\.(\w+)")
_RANK3 = re.compile(r"= (?:f32|bf16|f16)\[(\d+),(\d+),(\d+)\]")
LABEL = "model.moe_experts"


def fullest_by_layer(tokens: np.ndarray) -> Dict[str, List[int]]:
    """``tokens``: ``held_expert_tokens`` as (steps, h, expert layers,
    held). A pass is one worker's tokens through one layer in one step,
    and what sizes its rounds is its fullest held expert. For each layer:
    ``largest``, the most any held expert drew in any pass (the largest
    count over steps, workers and experts), and ``least``, the least of
    the passes' fullest experts: the two ends between which the layer's
    passes lie."""
    fullest = np.asarray(tokens).max(axis=-1)  # (steps, h, layers)
    return {"largest": [int(v) for v in fullest.max(axis=(0, 1))],
            "least": [int(v) for v in fullest.min(axis=(0, 1))]}


def rows_in_text(compiled_text: str, held: int) -> Dict[str, int]:
    """segment key -> the rows of a round, from the compiled step's text.
    Under the label ``model.moe_experts`` the arrays with a leading
    ``held`` and two more axes are the experts' matrices, ``(held, D,
    F)`` and ``(held, F, D)``, and a round's slots before and after the
    first product, ``(held, rows, D)`` and ``(held, rows, F)``: ``rows``
    is the one middle axis that stands before two different widths. A
    segment where that does not single out one number is left out."""
    widths: Dict[str, Dict[int, set]] = collections.defaultdict(
        lambda: collections.defaultdict(set))
    for line in compiled_text.splitlines():
        if LABEL not in line:
            continue
        segment, shape = _SEGMENT.search(line), _RANK3.search(line)
        if segment and shape and int(shape[1]) == held:
            widths[segment[1]][int(shape[2])].add(int(shape[3]))
    out: Dict[str, int] = {}
    for segment, by_middle in widths.items():
        rows = [middle for middle, last in by_middle.items() if len(last) >= 2]
        if len(rows) == 1:
            out[segment] = rows[0]
    return out


def rows_by_layer(aux: Dict[str, Dict[str, Any]], compiled_text: str) -> Optional[List[int]]:
    """The rows of a round for each expert layer, in the order of
    ``fullest_by_layer`` (the segments of ``aux``, one step's
    ``segment_aux``, that hold an expert layer, sorted by key). The
    program's own word (``expert_round_rows`` in a segment's aux, the
    largest over the workers) where it gives one, else the compiled
    text's; ``None`` where a layer has neither."""
    layers = [key for key in sorted(aux) if "held_expert_tokens" in aux[key]]
    if not layers:
        return None
    held = int(np.asarray(aux[layers[0]]["held_expert_tokens"]).shape[-1])
    read = rows_in_text(compiled_text, held) if compiled_text else {}
    rows = [int(np.max(np.asarray(aux[key]["expert_round_rows"])))
            if "expert_round_rows" in aux[key] else read.get(key) for key in layers]
    return None if any(r is None for r in rows) else rows


def _distance(count: int, rows: int) -> int:
    """From ``count`` to the nearest ``k x rows``, ``k >= 1``."""
    return abs(count - max(1, round(count / rows)) * rows)


def margin_pct(least: Sequence[int], largest: Sequence[int],
               rows: Optional[Sequence[int]]) -> Optional[float]:
    """Over the expert layers, the least distance of a layer's fullest
    expert (both ends of its passes: ``fullest_by_layer``) from a whole
    multiple ``k x rows``, ``k >= 1``, of that layer's round, as a
    percentage of the round: 500 of 512 reads 2.3, 762 reads 48.8, 256
    reads 50, 1030 reads 1.2. A layer whose passes lie on BOTH sides of a
    multiple reads 0: an edge is inside its own spread. ``None`` where
    there is no expert layer or no round to hold it against."""
    if not rows or not largest:
        return None
    margins = []
    for lo, hi, r in zip(least, largest, rows):
        if -(-max(lo, 1) // r) != -(-max(hi, 1) // r):
            margins.append(0.0)
        else:
            margins.append(100.0 * min(_distance(lo, r), _distance(hi, r)) / r)
    return min(margins)


def facts(aux: Dict[str, Dict[str, Any]], tokens: np.ndarray,
          compiled_text: str) -> Dict[str, Any]:
    """What a streamed driver prints and keeps for the readers: the
    layers' loads, their rounds and the margin between them."""
    ends = fullest_by_layer(tokens)
    rows = rows_by_layer(aux, compiled_text)
    return {"held_expert_tokens_by_layer": ends["largest"],
            "held_expert_fullest_least_by_layer": ends["least"],
            "expert_round_rows": rows,
            "expert_round_margin_pct": margin_pct(ends["least"], ends["largest"], rows)}
