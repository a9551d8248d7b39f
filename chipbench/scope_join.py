"""Per-scope device time: the join of a profiler trace with the compiled text.

A TPU trace names an op by its HLO instruction and carries no scope
(``XLA Ops`` events have three timing stats and nothing else). The scope
is in the compiled program's text, on the instruction's line:
``metadata={op_name="jit(train_step)/round.fwdbwd/vmap(...)/add_any"}``.
So: *trace event -> instruction name -> compiled-text line -> op_name ->
innermost ``round.*`` segment*. ``SCOPES.md`` beside this file says how to
add a metric on top.

An instruction's label is its scope. A fusion is labelled by its fused
computation: the one scope on which all of its instructions that carry
one agree, else ``mixed``. An instruction the compiler made carries no
``op_name`` (on the chip: the ``dynamic-update-slice`` chain a
concatenate became, layout copies, asynchronous slices); it inherits the
one scope that all its operands carrying a label share, and is
``unscoped`` where they share none (copies of parameters, a ``mixed``
operand). A Pallas kernel is a custom call whose line holds a name of
the program's ``KERNELS``.

Time: inside one execution of the step's program every instant belongs
to the innermost op running then (``XLA Ops`` holds a ``while`` and the
ops of its body: the body's ops own their time, the ``while`` the rest),
so a scope's time is the union of its ops' intervals, never their sum,
and the labels' times add up to the execution's busy time exactly.

Usage by hand: ``python -m chipbench.scope_join <file.xplane.pb> <compiled.hlo.txt>``.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from chipbench import trace_reduce as tr

MIXED, UNSCOPED = "mixed", "unscoped"
SCOPE = re.compile(r"round\.[A-Za-z0-9_]+")
ENQUEUE_EVENT = "DoEnqueueProgram"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_KERNEL_BODY = re.compile(r'"body":"[^"]*"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_EVENT_NAME = re.compile(r"^%?([^\s=]+)")


# --------------------------------------------------------------------------
# the compiled text
# --------------------------------------------------------------------------


@dataclass
class Labels:
    """What the compiled text says of each instruction, by its name."""

    label: Dict[str, str] = field(default_factory=dict)
    straddles: Dict[str, Tuple[str, ...]] = field(default_factory=dict)  # mixed fusions
    inherited: Set[str] = field(default_factory=set)  # labelled through their operands
    kernel: Dict[str, str] = field(default_factory=dict)  # custom call -> KERNELS name
    scopes: Tuple[str, ...] = ()  # every scope some instruction carries


def scope_of(op_name: str) -> Optional[str]:
    """The innermost scope of an ``op_name`` path; the segment can sit
    anywhere in it, also inside a transform's parentheses."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def read_labels(compiled_text: str, kernels: Iterable[str] = ()) -> Labels:
    # whole names only: `selection_mean_stream` is not found inside
    # `clip_selection_mean_stream` or `_selection_mean_stream_call`
    kernel_res = [(k, re.compile(r"(?<![\w.])" + re.escape(k) + r"(?![\w.])")) for k in kernels]
    scope: Dict[str, Optional[str]] = {}
    fused: Dict[str, str] = {}  # fusion instruction -> its computation
    inside: Dict[str, List[str]] = {}  # computation -> its instructions
    operands: Dict[str, List[str]] = {}
    out = Labels()
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
        opcode = called.group(1) if called else ""
        op_name = _OP_NAME.search(rest)
        scope[name] = None if opcode == "parameter" or not op_name else scope_of(op_name.group(1))
        operands[name] = _OPERAND.findall(_in_parentheses(rest, called.end() - 2)) if called else []
        if opcode == "fusion":
            calls = _CALLS.search(rest)
            if calls:
                fused[name] = calls.group(1)
        elif opcode == "custom-call" and kernel_res:
            visible = _KERNEL_BODY.sub("", rest)
            for kernel, pattern in kernel_res:
                if pattern.search(visible):
                    out.kernel[name] = kernel
                    break
    for name, own in scope.items():  # in the text's order: operands come first
        agreed = {scope[i] for i in inside.get(fused.get(name, ""), ()) if scope[i]}
        if len(agreed) > 1:
            out.label[name] = MIXED
            out.straddles[name] = tuple(sorted(agreed))
            continue
        label = next(iter(agreed), None) or own
        if label is None:
            fed = {out.label.get(o, UNSCOPED) for o in operands[name]} - {UNSCOPED}
            if len(fed) == 1 and MIXED not in fed:
                label = fed.pop()
                out.inherited.add(name)
        out.label[name] = label or UNSCOPED
    out.scopes = tuple(sorted({s for s in scope.values() if s}))
    return out


def _in_parentheses(text: str, at: int) -> str:
    """What stands between the parenthesis at ``text[at]`` and its match."""
    depth = 0
    for i in range(at, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            return text[at + 1:i]
    return text[at + 1:]


# --------------------------------------------------------------------------
# the trace
# --------------------------------------------------------------------------


@dataclass
class Run:
    """One execution of the step's program on one device."""

    run_id: str
    start: float
    end: float
    ops: List[tr.Event] = field(default_factory=list)  # named by instruction


@dataclass
class DeviceRuns:
    name: str
    ordinal: int
    runs: List[Run]  # the step's program
    programs: List[Run]  # every program's executions, no ops


@dataclass
class Joined:
    devices: List[DeviceRuns]
    enqueued: Dict[Tuple[int, str], float]  # (device ordinal, run_id) -> host start
    spans: List[tr.Event]  # chipbench.* host spans


def instruction_of(event_name: str) -> str:
    """A TPU trace names an op by its whole HLO line."""
    return _EVENT_NAME.match(event_name).group(1) if event_name else ""


def read_runs(path: str, step_module: str) -> Joined:
    """Executions of the programs whose name holds ``step_module``, each
    with its ops. TPU: ``XLA Modules`` and ``XLA Ops`` of each device
    plane (``Async XLA Ops`` stays out). CPU rehearsal: host-thread
    events with an ``hlo_op`` stat, grouped by device and ``run_id``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Joined(devices=[], enqueued={}, spans=[])
    for plane in data.planes:
        if not plane.name.startswith(tr.DEVICE_PLANE_PREFIX):
            continue
        ordinal = int(plane.name[len(tr.DEVICE_PLANE_PREFIX):].split()[0])
        programs, ops = [], []
        for line in plane.lines:
            if line.name == tr.MODULES_LINE:
                programs = [
                    (ev.name, Run(str(dict(ev.stats).get("run_id", "")), float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
                    for ev in line.events
                ]
            elif line.name == tr.OPS_LINE:
                ops = [tr.Event(instruction_of(ev.name), float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns))
                       for ev in line.events if ev.duration_ns > 0]
        programs.sort(key=lambda p: p[1].start)
        runs = [run for name, run in programs if step_module in name]
        starts = [run.start for run in runs]
        for op in ops:
            run = _containing(runs, starts, op.start)
            if run is not None:
                run.ops.append(op)
        out.devices.append(DeviceRuns(plane.name, ordinal, runs, [r for _, r in programs]))
    on_cpu: Dict[Tuple[int, str], Run] = {}
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name.startswith(tr.SPAN_PREFIX):
                    out.spans.append(tr.Event(ev.name, s, e))
                elif out.devices:  # a TPU trace: the host's enqueue of each run
                    if ev.name == ENQUEUE_EVENT:
                        stats = dict(ev.stats)
                        if "run_id" in stats:
                            out.enqueued.setdefault(_run_key(stats), s)
                elif e > s and not ev.name.startswith("end: "):  # the rehearsal: its ops
                    stats = dict(ev.stats)
                    if "hlo_op" in stats and step_module in str(stats.get("hlo_module", "")):
                        key = _run_key(stats)
                        run = on_cpu.setdefault(key, Run(key[1], s, e))
                        run.start, run.end = min(run.start, s), max(run.end, e)
                        run.ops.append(tr.Event(str(stats["hlo_op"]), s, e))
    if not out.devices:
        for ordinal in sorted({k[0] for k in on_cpu}):
            runs = sorted((r for k, r in on_cpu.items() if k[0] == ordinal), key=lambda r: r.start)
            out.devices.append(DeviceRuns(f"cpu-backend:{ordinal}", ordinal, runs, list(runs)))
    out.devices.sort(key=lambda d: d.ordinal)
    out.spans.sort(key=lambda ev: ev.start)
    return out


def _run_key(stats: Dict[str, Any]) -> Tuple[int, str]:
    return int(stats.get("device_ordinal", 0)), str(stats.get("run_id", ""))


def _containing(runs: Sequence[Run], starts: Sequence[float], at: float) -> Optional[Run]:
    """The run (of ``runs``, sorted by their ``starts``) that ``at`` falls in."""
    i = bisect.bisect_right(starts, at)
    return runs[i - 1] if i and at < runs[i - 1].end else None


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------


def owned_ns(ops: Sequence[tr.Event]) -> Dict[str, float]:
    """Nanoseconds each op name owns: every instant goes to the op that
    started last among those running then (the innermost of nested ops)."""
    edges = []  # a close sorts before an open at the same instant; of two
    for i, op in enumerate(ops):  # opens, the longer op (the outer) first
        edges.append((op.start, 1, -op.end, i))
        edges.append((op.end, 0, 0.0, i))
    edges.sort()
    owned: Dict[str, float] = {}
    open_ops: List[int] = []  # by start; the last one owns
    closed = set()
    at = 0.0
    for when, opens, _, i in edges:
        while open_ops and open_ops[-1] in closed:
            open_ops.pop()
        if open_ops and when > at:
            name = ops[open_ops[-1]].name
            owned[name] = owned.get(name, 0.0) + (when - at)
        at = when
        if opens:
            open_ops.append(i)
        else:
            closed.add(i)
    return owned


def clock_skew_ns(joined: Joined) -> Optional[float]:
    """The least the host's clock runs ahead of the device's: the largest
    amount by which a program starts on the device before the host
    enqueued it (paired by ``run_id``). None without such pairs."""
    ahead = [
        host - run.start
        for dev in joined.devices for run in dev.programs
        for host in [joined.enqueued.get((dev.ordinal, run.run_id))] if host is not None
    ]
    return max(0.0, max(ahead)) if ahead else None


def between_program_gaps(joined: Joined, skew_ns: float, k: int = 10) -> List[List[object]]:
    """The k longest idle stretches between two program executions on
    the first device, each named by the innermost benchmark span that
    covers its middle once the host's spans are shifted onto the device's
    clock: [span, seconds]. Gaps inside an execution are the program's."""
    if not joined.devices:
        return []
    gaps, reach = [], None
    for run in joined.devices[0].programs:
        if reach is not None and run.start > reach:
            gaps.append((reach, run.start))
        reach = run.end if reach is None else max(reach, run.end)
    gaps.sort(key=lambda g: g[0] - g[1])
    out: List[List[object]] = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e) + skew_ns
        covering = [sp for sp in joined.spans if sp.start <= mid <= sp.end]
        name = "outside-spans"
        if covering:
            name = min(covering, key=lambda sp: sp.end - sp.start).name[len(tr.SPAN_PREFIX):]
        out.append([name, (e - s) * 1e-9])
    return out


TABLES = ("label_ms", "kernel_ms", "inherited_ms")


def _tally(run: Run, labels: Labels) -> Dict[str, Dict[str, float]]:
    """Milliseconds of one execution by label, by kernel, by label for
    what was labelled through its operands, and by instruction for what
    no scope owns."""
    out: Dict[str, Dict[str, float]] = {table: {} for table in TABLES + ("unattributed_ops",)}

    def add(table: str, key: str, ns: float) -> None:
        out[table][key] = out[table].get(key, 0.0) + 1e-6 * ns

    for name, ns in owned_ns(run.ops).items():
        label = labels.label.get(name, UNSCOPED)
        add("label_ms", label, ns)
        if name in labels.inherited:
            add("inherited_ms", label, ns)
        if name in labels.kernel:
            add("kernel_ms", labels.kernel[name], ns)
        if label in (MIXED, UNSCOPED):
            add("unattributed_ops", name, ns)
    return out


def _per_key(combine, rows: Iterable[Dict[str, float]]) -> Dict[str, float]:
    """``combine`` over the rows' values of each key; a row without a key
    counts as 0 there."""
    rows = list(rows)
    return {k: combine(row.get(k, 0.0) for row in rows)
            for k in sorted({k for row in rows for k in row})}


def reduce_runs(joined: Joined, labels: Labels) -> Optional[Dict[str, Any]]:
    """Per label and per kernel the milliseconds of one execution (median
    over executions, mean over devices), the unattributed share of its
    busy time, and the idle microseconds between two executions."""
    per_device: List[Dict[str, Any]] = []
    heaviest: Dict[str, float] = {}
    for dev in joined.devices:
        tallies = [_tally(run, labels) for run in dev.runs]
        busy = [sum(t["label_ms"].values()) for t in tallies]
        if not any(busy):
            continue
        if not per_device:  # the first device that ran the step names its ops
            heaviest = _per_key(statistics.fmean, (t["unattributed_ops"] for t in tallies))
        between = [max(0.0, b.start - a.end) for a, b in zip(dev.runs, dev.runs[1:])]
        row = {table: _per_key(statistics.median, (t[table] for t in tallies)) for table in TABLES}
        row["busy_ms"] = statistics.median(busy)
        row["unattributed_pct"] = statistics.median(
            100.0 * sum(t["unattributed_ops"].values()) / b for t, b in zip(tallies, busy) if b)
        row["gap_us"] = 1e-3 * statistics.fmean(between) if between else None
        per_device.append(row)
    if not per_device:
        return None
    gaps = [d["gap_us"] for d in per_device if d["gap_us"] is not None]
    out: Dict[str, Any] = {
        table: _per_key(statistics.fmean, (d[table] for d in per_device)) for table in TABLES}
    out.update(
        busy_ms=statistics.fmean(d["busy_ms"] for d in per_device),
        unattributed_pct=statistics.fmean(d["unattributed_pct"] for d in per_device),
        host_gap_us_per_step=statistics.fmean(gaps) if gaps else None,
        executions=[len(dev.runs) for dev in joined.devices],
        heaviest_unattributed=[
            [name, labels.label.get(name, UNSCOPED), list(labels.straddles.get(name, ())), ms]
            for name, ms in sorted(heaviest.items(), key=lambda kv: -kv[1])[:8]
        ],
    )
    return out


# --------------------------------------------------------------------------
# what a reader asks for
# --------------------------------------------------------------------------


def of(ctx) -> Optional[Dict[str, Any]]:
    """The reduction of this run's trace, made once and kept with what the
    driver measured; None where there is nothing to join (no compiled
    text, no step in the trace, or a program that declares no scopes, as
    the parent of the PR that added them)."""
    measured = ctx.outcome["measured"]
    if "scope_join" not in measured:
        measured["scope_join"] = _join(ctx)
    return measured["scope_join"]


def _join(ctx) -> Optional[Dict[str, Any]]:
    from byzpy_tpu.observability import catalog

    text = ctx.outcome.get("compiled_text")
    step_module = ctx.outcome["measured"].get("step_module")
    declared = [s for s in getattr(catalog, "SCOPES", ()) if SCOPE.fullmatch(s)]
    if not text or not step_module or not declared:
        return None
    labels = read_labels(text, getattr(catalog, "KERNELS", ()))
    joined = read_runs(tr.find_xplane(ctx.trace_dir), step_module)
    out = reduce_runs(joined, labels)
    if out is None:
        return None
    out["scopes_in_text"] = list(labels.scopes)
    if not labels.scopes:
        # the program declares scopes and its executable carries none (one
        # loaded from a cache that an older program filled): nothing is
        # attributed, and nothing is guessed
        out["unattributed_pct"] = 100.0
    skew = clock_skew_ns(joined)
    ctx.say(
        scope_device_ms=out["label_ms"], of_it_through_operands_ms=out["inherited_ms"],
        kernel_device_ms=out["kernel_ms"],
        step_busy_ms=out["busy_ms"], executions=out["executions"],
        scopes_in_text=out["scopes_in_text"], kernels_in_text=sorted(set(labels.kernel.values())),
        heaviest_unattributed=out["heaviest_unattributed"],
    )
    ctx.say(
        clock_skew_us=None if skew is None else 1e-3 * skew,
        between_program_gaps=between_program_gaps(joined, skew or 0.0),
    )
    return out


def scope_ms(ctx, *scopes: str) -> Optional[float]:
    """Milliseconds of one step inside the given scopes, None where the
    compiled step has none of them."""
    joined = of(ctx)
    if joined is None or not any(s in joined["scopes_in_text"] for s in scopes):
        return None
    return sum(joined["label_ms"].get(s, 0.0) for s in scopes)


if __name__ == "__main__":
    with open(sys.argv[2], encoding="utf-8") as fh:
        found = read_labels(fh.read(), sys.argv[4:])
    traced = read_runs(sys.argv[1], sys.argv[3] if len(sys.argv) > 3 else "train_step")
    print(json.dumps(reduce_runs(traced, found), indent=1, default=float))
    print(json.dumps({"clock_skew_us": 1e-3 * (clock_skew_ns(traced) or 0.0)}))
