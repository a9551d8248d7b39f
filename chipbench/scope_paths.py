"""Device time of one step by what an op's ``op_name`` path HOLDS, or by
its opcode: the readers that ``scope_join`` cannot serve.

``scope_join`` gives every op the innermost ``round.*`` segment of its
path, and nothing else. A step that streams segment by segment nests three
kinds of label in one path: the pass (``round.segment_fwd``,
``round.segment_recompute``, ``round.segment_bwd``), the stage
(``round.fwdbwd``, innermost of the ``round.*``, so that the accepted
readers read it as before) and the mixer (``model.ssm_scan``,
``model.attention``, ``model.moe_route``, ``model.moe_experts``). This
module asks the other question: the time of the ops whose path holds a
given label, wherever in the path it stands.

The trace, the executions of the step and the rule "every instant belongs
to the innermost op running then" are ``scope_join``'s (``read_runs``,
``owned_ns``). A fusion's time is shared out by the share of its fused
instructions (those that carry an ``op_name``) whose path holds the label:
the compiler fuses across a scope's edge, and a fusion is not made to
choose. Median over the step's executions, mean over chips.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from chipbench import scope_join
from chipbench import trace_reduce as tr

# the compiled text is read with scope_join's own patterns: one grammar
_COMPUTATION, _INSTRUCTION, _OPCODE, _OP_NAME, _CALLS = (
    scope_join._COMPUTATION, scope_join._INSTRUCTION, scope_join._OPCODE,
    scope_join._OP_NAME, scope_join._CALLS)


def read_text(compiled_text: str) -> Dict[str, Dict[str, Any]]:
    """Instruction -> its opcode and the ``op_name`` paths it stands for
    (its own; a fusion's: those of its fused computation's instructions)."""
    own: Dict[str, Optional[str]] = {}
    opcode: Dict[str, str] = {}
    fused: Dict[str, str] = {}
    inside: Dict[str, List[str]] = {}
    computation = None
    for line in compiled_text.splitlines():
        if computation is None:
            head = _COMPUTATION.match(line)
            if head:
                computation = head.group(1)
                inside[computation] = []
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        inside[computation].append(name)
        called = _OPCODE.search(" " + rest)
        opcode[name] = called.group(1) if called else ""
        path = _OP_NAME.search(rest)
        own[name] = path.group(1) if path and opcode[name] != "parameter" else None
        if opcode[name] == "fusion":
            calls = _CALLS.search(rest)
            if calls:
                fused[name] = calls.group(1)
    out = {}
    for name in own:
        paths = [own[i] for i in inside.get(fused.get(name, ""), ()) if own[i]]
        out[name] = {"opcode": opcode[name], "paths": paths or ([own[name]] if own[name] else [])}
    return out


def _instructions(ctx) -> Optional[Dict[str, Dict[str, Any]]]:
    measured = ctx.outcome["measured"]
    if "scope_paths_text" not in measured:
        text = ctx.outcome.get("compiled_text")
        measured["scope_paths_text"] = read_text(text) if text else None
    return measured["scope_paths_text"]


def of(ctx) -> Optional[Dict[str, Any]]:
    """The step's instructions and, per chip and execution, the
    nanoseconds each owns; made once a run. None where there is nothing
    to join (no compiled text, no step in the trace)."""
    measured = ctx.outcome["measured"]
    if "scope_paths" not in measured:
        instructions = _instructions(ctx)
        step_module = measured.get("step_module")
        found = None
        if instructions and step_module:
            joined = scope_join.read_runs(tr.find_xplane(ctx.trace_dir), step_module)
            owned = [[scope_join.owned_ns(run.ops) for run in dev.runs] for dev in joined.devices]
            owned = [runs for runs in owned if any(runs)]
            if owned:
                found = {"instructions": instructions, "owned": owned}
        measured["scope_paths"] = found
    return measured["scope_paths"]


def _asked(ctx, share) -> Optional[float]:
    """The text is asked first: where no instruction of the compiled step
    answers to ``share``, there is nothing to read and no trace is opened."""
    instructions = _instructions(ctx)
    if not instructions or not any(share(ins) for ins in instructions.values()):
        return None
    found = of(ctx)
    return None if found is None else _ms(found, share)


def _ms(found: Dict[str, Any], share) -> float:
    per_device = []
    for runs in found["owned"]:
        per_device.append(statistics.median(
            1e-6 * sum(ns * share(found["instructions"].get(name)) for name, ns in run.items())
            for run in runs))
    return statistics.fmean(per_device)


def path_ms(ctx, *labels: str, without: Sequence[str] = ()) -> Optional[float]:
    """Milliseconds of one step in ops whose path holds one of ``labels``
    (and none of ``without``); None where no instruction of the compiled
    step does (a program that never enters the scope: the parent of the PR
    that added it, a cell of another model)."""

    def holds(path: str) -> bool:
        return any(label in path for label in labels) and not any(w in path for w in without)

    def share(ins) -> float:
        if not ins or not ins["paths"]:
            return 0.0
        return sum(1 for p in ins["paths"] if holds(p)) / len(ins["paths"])

    return _asked(ctx, share)


def opcode_ms(ctx, *opcodes: str) -> Optional[float]:
    """Milliseconds of one step in ops whose opcode starts with one of
    ``opcodes`` (``all-gather`` covers ``all-gather-start`` and ``-done``);
    None where the compiled step has no such instruction."""

    def share(ins) -> float:
        return 1.0 if ins and ins["opcode"].startswith(opcodes) else 0.0

    return _asked(ctx, share)
