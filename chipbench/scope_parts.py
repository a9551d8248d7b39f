"""Which part, which segment, which pass: the inside of ``round.fwdbwd``.

``scope_paths.of(ctx)`` gives the step's instructions with the ``op_name``
paths each stands for and the nanoseconds each owns per execution. Here
every path is placed on three axes, and a fusion's time is shared out over
the places of its fused instructions that name a primitive (a fusion that
holds a product is the product's):

* **part**: the LAST ``model.*`` or ``stream.*`` label of the path,
  ``model.mtp`` left out (an envelope around a whole block, like
  ``round.segment_*``); ``unlabelled`` where the path holds none. A backward
  op's path wraps the label (``transpose(jvp(model.norm))``), so labels are
  found by pattern, not by splitting on ``/``.
* **pass**: ``round.segment_bwd`` where the path holds it, else
  ``round.segment_recompute``, else ``round.segment_fwd`` (a backward op
  holds the second forward's label too: it was traced there).
* **segment**: the ``segment.<key>`` the path holds.

An instruction the compiler made without an ``op_name`` goes with the
neighbour it was made for (:func:`through_neighbours`), so every traced
nanosecond of ``round.fwdbwd`` lies in exactly one part.

One step is the execution whose ``round.fwdbwd`` time is the median (the
mean of the two middle ones where their number is even): a table taken
from whole executions adds up exactly. ``PARTS.md`` beside this file has
the rules, and how a part and its metric are added.
"""

from __future__ import annotations

import re
import statistics
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

from chipbench import scope_join, scope_paths
from chipbench import trace_reduce as tr

UNLABELLED = "unlabelled"
FWDBWD = "round.fwdbwd"
ENVELOPES = ("model.mtp",)
PASSES = ("round.segment_bwd", "round.segment_recompute", "round.segment_fwd")
# the columns of `segments`: the three passes, then the round's own stages
COLUMNS = ("round.segment_fwd", "round.segment_recompute", "round.segment_bwd",
           "round.build_matrix", "round.aggregate", "round.update")
NO_SEGMENT = "-"
PRODUCTS = ("convolution", "dot")

_LABEL = re.compile(r"(?:model|stream)\.[A-Za-z0-9_]+")
_SEGMENT = re.compile(r"segment\.([A-Za-z0-9_\-]+)")
_PLUMBING = re.compile(r"/(?:while|body|cond|closed_call)$")
_SOURCE = re.compile(r'source_file="([^"]*)"\s+source_line=(\d+)')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+) (.*)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Place = Tuple[str, Optional[str], str, Optional[str]]  # part, pass, segment, stage


def part_of(path: str) -> str:
    """The part an ``op_name`` path belongs to."""
    found = [label for label in _LABEL.findall(path) if label not in ENVELOPES]
    return found[-1] if found else UNLABELLED


def place_of(path: str) -> Place:
    """``(part, pass, segment, stage)`` of a path; the stage is its
    innermost ``round.*`` scope, as ``scope_join`` reads it."""
    a_pass = next((p for p in PASSES if p in path), None)
    segment = _SEGMENT.search(path)
    return (part_of(path), a_pass, segment.group(1) if segment else NO_SEGMENT,
            scope_join.scope_of(path))


def paths_of(details: Dict[str, Dict[str, Any]]) -> Dict[str, List[str]]:
    """Instruction -> the ``op_name`` paths it stands for. Its own; a
    fusion's: those of its fused computation's instructions, as
    ``scope_paths.read_text`` gives them, but where the fused computation
    holds a product (a ``convolution`` or ``dot``: the MXU's work, with
    element-wise neighbours fused onto its operands and its result) the
    fusion is the product's alone: shared by instruction count, a norm of
    ten small instructions fused onto a product of one would take ten
    elevenths of the product's time."""
    inside: Dict[str, List[Dict[str, Any]]] = {}
    for about in details.values():
        if about["op_name"] and about["opcode"] != "parameter":
            inside.setdefault(about["computation"], []).append(about)
    out: Dict[str, List[str]] = {}
    for name, about in details.items():
        fused = inside.get(about["calls"], []) if about["calls"] else []
        fused = [f for f in fused if f["opcode"] in PRODUCTS] or fused
        own = [about["op_name"]] if about["op_name"] and about["opcode"] != "parameter" else []
        out[name] = [f["op_name"] for f in fused] or own
    return out


def shares_of(paths: Dict[str, List[str]]) -> Dict[str, Dict[Place, float]]:
    """Instruction -> the share of its time that goes to each place: one
    over the number of its paths that name a primitive, a path. A path that
    ends at a control-flow node (``.../while/body/closed_call``) is what the
    compiler put around a loop (a constant, its broadcast, a tuple's element,
    fused into a neighbour): it names no work, and counts only where an
    instruction holds nothing else. (``scope_join`` labels a fusion the same
    way: by the scope of those of its instructions that carry one.)"""
    out: Dict[str, Dict[Place, float]] = {}
    for name, mine in paths.items():
        named = [path for path in mine if not _PLUMBING.search(path)] or mine
        if named:
            counted = Counter(place_of(path) for path in named)
            out[name] = {place: n / len(named) for place, n in counted.items()}
    return out


def through_neighbours(shares: Dict[str, Dict[Place, float]], details: Dict[str, Dict[str, Any]]
                       ) -> Dict[str, str]:
    """Places for the instructions the compiler made without an ``op_name``
    (a weight's cast, a prefetch's ``copy-start`` / ``copy-done``, an
    asynchronous slice of a kept boundary, a layout copy): each takes the
    places of the first instruction that USES it and has some (it was made
    for that one), else of its first operand that has some, as
    ``scope_join`` labels them through their operands. Adds them to
    ``shares``; returns instruction -> the neighbour it took them from."""
    users: Dict[str, List[str]] = {}
    for name, about in details.items():
        for operand in about["operands"]:
            users.setdefault(operand, []).append(name)
    through: Dict[str, str] = {}
    order = [name for name, about in details.items() if about["opcode"] != "parameter"]
    for neighbours, names in ((users, reversed(order)), (
            {name: details[name]["operands"] for name in order}, order)):
        for name in names:
            if name not in shares:
                found = next((n for n in neighbours.get(name, ()) if n in shares), None)
                if found is not None:
                    shares[name] = shares[found]
                    through[name] = through.get(found, found)
    return through


def _median_runs(owned: List[Dict[str, float]], shares) -> List[Dict[str, float]]:
    """The execution(s) whose ``round.fwdbwd`` time is the median one."""
    def fwdbwd(run):
        return sum(ns * share for name, ns in run.items()
                   for place, share in shares.get(name, {}).items() if place[3] == FWDBWD)

    ranked = sorted((run for run in owned if run), key=fwdbwd)
    middle = len(ranked) // 2
    return ranked[middle:middle + 1] if len(ranked) % 2 else ranked[middle - 1:middle + 1]


def one_step(found: Dict[str, Any], shares) -> Dict[str, float]:
    """Instruction -> nanoseconds of one step: the median execution(s) of
    each device, averaged, then the mean over devices."""
    picked = [_median_runs(runs, shares) for runs in found["owned"]]
    picked = [runs for runs in picked if runs]
    step: Dict[str, float] = {}
    for runs in picked:
        for run in runs:
            for name, ns in run.items():
                step[name] = step.get(name, 0.0) + ns / (len(runs) * len(picked))
    return step


def table_of(step: Dict[str, float], shares) -> Dict[Place, float]:
    """Place -> milliseconds of one step."""
    cells: Dict[Place, float] = {}
    for name, ns in step.items():
        for place, share in shares.get(name, {}).items():
            cells[place] = cells.get(place, 0.0) + 1e-6 * ns * share
    return cells


def read_details(compiled_text: str) -> Dict[str, Dict[str, Any]]:
    """Instruction (in the text's order) -> its computation, the
    computation a fusion calls, its opcode, operands, result shape (layouts
    dropped), own ``op_name`` and the ``file:line`` its metadata names
    (directly, or through the text's ``StackFrames`` / ``FileLocations`` /
    ``FileNames`` tables). Read with ``scope_join``'s patterns: one grammar."""
    tables: Dict[str, Dict[str, str]] = {}
    table = None
    computation = ""
    out: Dict[str, Dict[str, Any]] = {}
    for line in compiled_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            table = tables.setdefault(line, {})
            continue
        if table is not None:
            row = _TABLE_ROW.match(line)
            if row:
                table[row.group(1)] = row.group(2)
                continue
            table = None
        head = scope_join._COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = scope_join._INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        called = scope_join._OPCODE.search(" " + rest)
        op_name = scope_join._OP_NAME.search(rest)
        source = _SOURCE.search(rest)
        frame = _FRAME.search(rest)
        calls = scope_join._CALLS.search(rest) if called and called.group(1) == "fusion" else None
        out[name] = {
            "computation": computation, "calls": calls.group(1) if calls else "",
            "opcode": called.group(1) if called else "",
            "operands": scope_join._OPERAND.findall(
                scope_join._in_parentheses(rest, called.end() - 2)) if called else [],
            "shape": _LAYOUT.sub("", rest[:called.start()]).strip() if called else "",
            "op_name": op_name.group(1) if op_name else "",
            "source": f"{source.group(1)}:{source.group(2)}" if source
            else _frame_source(tables, frame.group(1)) if frame else "",
        }
    return out


def _frame_source(tables: Dict[str, Dict[str, str]], frame: str) -> str:
    def field(row: str, key: str) -> str:
        m = re.search(key + r"=(\d+)", row)
        return m.group(1) if m else ""

    location = tables.get("FileLocations", {}).get(
        field(tables.get("StackFrames", {}).get(frame, ""), "file_location_id"), "")
    name = tables.get("FileNames", {}).get(field(location, "file_name_id"), "").strip('"')
    return f"{name}:{field(location, 'line')}" if name else ""


# --------------------------------------------------------------------------
# what a reader asks for
# --------------------------------------------------------------------------


def _text_shares(ctx) -> Optional[Dict[str, Dict[Place, float]]]:
    """The places of the compiled step's instructions, from the text alone."""
    measured = ctx.outcome["measured"]
    if "scope_parts_shares" not in measured:
        text = ctx.outcome.get("compiled_text")
        shares = None
        if text:
            details = read_details(text)
            shares = shares_of(paths_of(details))
            measured["scope_parts_details"] = details
            measured["scope_parts_through"] = through_neighbours(shares, details)
        measured["scope_parts_shares"] = shares or None
    return measured["scope_parts_shares"]


def _holds(ctx, wanted) -> bool:
    """The text is asked first, as ``scope_paths._asked`` asks it: whether
    any instruction of the compiled step has a place that ``wanted``."""
    shares = _text_shares(ctx)
    return bool(shares) and any(wanted(place) for places in shares.values() for place in places)


def of(ctx) -> Optional[Dict[str, Any]]:
    """One step's table and what it was made from, once a run; the first
    reader to ask prints it. None where there is nothing to join."""
    measured = ctx.outcome["measured"]
    if "scope_parts" not in measured:
        shares, found = _text_shares(ctx), scope_paths.of(ctx)
        t0 = time.perf_counter()  # the join is scope_paths'; from here on it is this module's
        made = None
        if shares and found:
            step = one_step(found, shares)
            made = {"step": step, "shares": shares, "cells": table_of(step, shares)}
            through = measured["scope_parts_through"]
            made["cells_through"] = table_of(
                {name: ns for name, ns in step.items() if name in through}, shares)
        measured["scope_parts"] = made
        if made:
            _say(ctx, t0)
    return measured["scope_parts"]


def parts_by_pass(ctx) -> Optional[Dict[str, Dict[str, float]]]:
    """Part -> pass -> milliseconds of one step inside ``round.fwdbwd``
    (what holds no pass stands under ``-``). None where the compiled step
    holds no ``model.*`` label at all."""
    if not _holds(ctx, lambda place: place[0].startswith("model.")):
        return None
    made = of(ctx)
    if made is None:
        return None
    out: Dict[str, Dict[str, float]] = {}
    for (part, a_pass, _, stage), ms in made["cells"].items():
        if stage == FWDBWD:
            row = out.setdefault(part, {})
            row[a_pass or NO_SEGMENT] = row.get(a_pass or NO_SEGMENT, 0.0) + ms
    return out


def parts(ctx) -> Optional[Dict[str, float]]:
    """Part -> milliseconds of one step inside ``round.fwdbwd``: every
    traced nanosecond of it in exactly one entry."""
    by_pass = parts_by_pass(ctx)
    return None if by_pass is None else {part: sum(row.values()) for part, row in by_pass.items()}


def part_ms(ctx, *labels: str) -> Optional[float]:
    """Milliseconds of one step in the given parts; None where the
    compiled step holds none of them (the parent of the PR that added the
    label, a cell of another model)."""
    if not _holds(ctx, lambda place: place[0] in labels and place[3] == FWDBWD):
        return None
    found = parts(ctx)
    return None if found is None else sum(found.get(label, 0.0) for label in labels)


def segments(ctx) -> Optional[Dict[str, Dict[str, float]]]:
    """Segment key -> milliseconds of one step in its first forward,
    second forward, backward, ``round.build_matrix``, ``round.aggregate``
    and ``round.update`` (``COLUMNS``); what holds no segment stands under
    ``-``. None where the compiled step holds no ``segment.*`` label."""
    if not _holds(ctx, lambda place: place[2] != NO_SEGMENT):
        return None
    made = of(ctx)
    if made is None:
        return None
    out: Dict[str, Dict[str, float]] = {}
    for (_, a_pass, segment, stage), ms in made["cells"].items():
        column = a_pass if stage == FWDBWD else stage
        if column in COLUMNS:
            row = out.setdefault(segment, dict.fromkeys(COLUMNS, 0.0))
            row[column] += ms
    return out


def leading_ops(ctx, label: str, k: int = 8) -> Optional[List[Dict[str, Any]]]:
    """The ``k`` heaviest instructions of a part of ``round.fwdbwd``
    (``unlabelled`` too): name, opcode, result shape, milliseconds a step
    summed over its executions (a fusion's: the share that belongs to the
    part), how many executions, the tail of its ``op_name`` and the source
    line its metadata names; for an instruction the compiler made without
    an ``op_name``, ``through``: the neighbour whose places it took. The
    same op in several segments is one row (``instructions``: how many;
    ``name``: the heaviest of them)."""
    made = of(ctx)
    if made is None:
        return None
    measured = ctx.outcome["measured"]
    if "scope_parts_executions" not in measured:
        measured["scope_parts_executions"] = _executions(ctx)
    details, executions = measured["scope_parts_details"], measured["scope_parts_executions"]
    through = measured["scope_parts_through"]
    # the same op of another segment (equal opcode, shape, op_name tail and
    # source line) is one row: the blocks of one kind repeat it
    rows: Dict[Tuple[str, ...], Dict[str, Any]] = {}
    for name, ns in made["step"].items():
        share = sum(s for place, s in made["shares"].get(name, {}).items()
                    if place[0] == label and place[3] == FWDBWD)
        if not share:
            continue
        about = details.get(name, {})
        row = {"name": name, "opcode": about.get("opcode", ""), "shape": about.get("shape", ""),
               "ms": 1e-6 * ns * share, "executions": executions.get(name, 0.0), "instructions": 1,
               "op_name": _tail(about.get("op_name", "")), "source": about.get("source", "")}
        if name in through:  # the compiler's, placed with the neighbour it was made for
            row["through"] = through[name]
        key = (row["opcode"], row["shape"], row["op_name"], row["source"], str("through" in row))
        if key in rows:
            heaviest, lighter = sorted((rows[key], row), key=lambda r: -r["ms"])
            rows[key] = dict(heaviest, ms=heaviest["ms"] + lighter["ms"],
                             executions=heaviest["executions"] + lighter["executions"],
                             instructions=heaviest["instructions"] + lighter["instructions"])
        else:
            rows[key] = row
    return sorted(rows.values(), key=lambda r: -r["ms"])[:k]


def _tail(op_name: str, segments_kept: int = 3) -> str:
    return "/".join(op_name.split("/")[-segments_kept:])


def _executions(ctx) -> Dict[str, float]:
    """Instruction -> how often it runs in one step (the median over the
    first device's executions of the step)."""
    joined = scope_join.read_runs(
        tr.find_xplane(ctx.trace_dir), ctx.outcome["measured"]["step_module"])
    for dev in joined.devices:
        counted = [Counter(op.name for op in run.ops) for run in dev.runs if run.ops]
        if counted:
            return {name: statistics.median(c.get(name, 0) for c in counted)
                    for name in set().union(*counted)}
    return {}


def _say(ctx, t0: float) -> None:
    """The run's one information line: parts by pass, segments, the
    leading ops of every part over 2 % of the step, and the seconds the
    table and the listing took (the second reads the trace once more, for
    the executions)."""
    by_pass, totals = parts_by_pass(ctx) or {}, parts(ctx) or {}
    step_ms = sum(ctx.outcome["measured"]["scope_parts"]["cells"].values())
    by_segment = segments(ctx)
    t1 = time.perf_counter()
    leading = {part: leading_ops(ctx, part, k=12) for part, ms in sorted(totals.items())
               if ms > 0.02 * step_ms}
    through: Dict[str, float] = {}
    for (part, _, _, stage), ms in ctx.outcome["measured"]["scope_parts"]["cells_through"].items():
        if stage == FWDBWD:
            through[part] = through.get(part, 0.0) + ms
    ctx.say(
        model_parts_ms=totals, of_it_through_neighbours_ms=through,
        model_parts_by_pass_ms=by_pass, segment_ms=by_segment, leading_ops=leading,
        scope_parts_seconds={"table": t1 - t0, "leading_ops": time.perf_counter() - t1},
    )
