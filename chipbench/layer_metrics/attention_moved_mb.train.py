"""Megabytes that attention moves without arithmetic, each instruction
once: the bytes of the results of the compiled step's TOP-LEVEL `copy`,
`transpose`, `reshape`, `concatenate`, `pad` and `slice` instructions whose
`op_name` holds `model.attention` (and so any label nested in it).

The rule exactly: an instruction counts if (1) its opcode is one of the six
(a `copy-start` / `copy-done` pair, a prefetch between memory spaces, is
none of them), (2) it stands in a computation that no `fusion` instruction
names as `calls=` (a move fused into a consumer is that consumer's work and
counts nowhere), (3) the `op_name` of its own metadata holds the string
`model.attention`; an instruction the compiler made WITHOUT an `op_name` (a
layout copy) is asked through the instruction it was made for, as
`scope_parts.through_neighbours` finds it (the first instruction that uses
it and has a place, else its first operand that has one): it counts if one
of that instruction's paths (`scope_parts.paths_of`) holds the string. It
counts the bytes of its result (element size x the product of the
dimensions, layouts apart), once, however often the loop it stands in runs.
A `reshape` the TPU compiler left standing is a relayout; one that is none
is a `bitcast` in this text and is not counted.

It asks what a path HOLDS, so a label added inside `model.attention` does
not move it; it moves when a relayout goes or comes. `None` for a step with
no such label. Read from the compiled program's text; repeats exactly.
Source: program_counter."""

from chipbench import hlo_collectives, scope_parts

MOVES = ("copy", "transpose", "reshape", "concatenate", "pad", "slice")
LABEL = "model.attention"


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or LABEL not in text:
        return None
    measured = ctx.outcome.get("measured", {})
    details, through = measured.get("scope_parts_details"), measured.get("scope_parts_through")
    if details is None or through is None:  # no part reader has asked yet
        details = scope_parts.read_details(text)
        through = scope_parts.through_neighbours(
            scope_parts.shares_of(scope_parts.paths_of(details)), details)
    paths = scope_parts.paths_of(details)
    fused = {about["calls"] for about in details.values() if about["calls"]}
    moved = 0
    for name, about in details.items():
        if about["opcode"] in MOVES and about["computation"] not in fused:
            mine = [about["op_name"]] if about["op_name"] else paths.get(through.get(name), ())
            if any(LABEL in path for path in mine):
                moved += hlo_collectives._shape_bytes(about["shape"])
    return moved / 1e6
