"""From a profiler trace (``.xplane.pb``) to busy time, idle gaps and op times.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. What
a trace of this program on a TPU v5e looks like (PERF.md section 5 has
the account): one plane per chip, ``/device:TPU:<i>``, whose line
``XLA Ops`` holds one event per executed HLO op and whose line
``XLA Modules`` holds one event per executed program, named after the
jitted function; the host's threads are lines of ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. All
times are nanoseconds on one clock.

On the CPU backend (the selftest's rehearsal only) there is no device
plane: ops are host-thread events that carry an ``hlo_op`` stat, and are
gathered into one pseudo-device so that the same code path runs.

Usage by hand: ``python -m chipbench.trace_reduce <file.xplane.pb>``
prints the planes, lines and a few events of each.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclass
class Event:
    name: str
    start: float
    end: float
    module: str = ""

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class DeviceTrace:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Reduced:
    devices: List[DeviceTrace]
    spans: List[Event]  # the benchmark's own host spans (chipbench.*)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


_HLO = re.compile(r"^%?(?P<name>[^\s=]+)\s*=\s*\(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?.*?\s(?P<op>[a-z][a-z0-9\-]*)\(")


def op_label(text: str) -> str:
    """A TPU trace names an op by its whole HLO line; keep the
    instruction's name, its opcode and its first result shape:
    ``fusion.699 fusion f32[1024,32,32,64]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    return " ".join(x for x in (m.group("name"), m.group("op"), m.group("shape")) if x)


def _events(line) -> Iterable[Tuple[str, float, float, Dict[str, object]]]:
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev


def reduce_trace(path: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    spans: List[Event] = []
    pseudo = DeviceTrace(name="cpu-backend")
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = DeviceTrace(name=plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = [Event(op_label(n), s, e) for n, s, e, _ in _events(line) if e > s]
                elif line.name == MODULES_LINE:
                    dev.modules = [Event(n, s, e) for n, s, e, _ in _events(line) if e > s]
            dev.ops.sort(key=lambda ev: ev.start)
            dev.modules.sort(key=lambda ev: ev.start)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for name, s, e, ev in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        spans.append(Event(name, s, e))
                    elif not devices and e > s and not name.startswith("end: "):
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            pseudo.ops.append(
                                Event(name, s, e, module=str(stats.get("hlo_module", "")))
                            )
    if not devices and pseudo.ops:
        pseudo.ops.sort(key=lambda ev: ev.start)
        devices = [pseudo]
    devices.sort(key=lambda d: d.name)
    spans.sort(key=lambda ev: ev.start)
    return Reduced(devices=devices, spans=spans)


# --------------------------------------------------------------------------
# arithmetic on intervals
# --------------------------------------------------------------------------


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    out = []
    for ev in events:
        s, e = max(ev.start, lo), min(ev.end, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_seconds(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one op ran on the device."""
    return sum(e - s for s, e in union(clip(dev.ops, lo, hi))) * 1e-9


def span(reduced: Reduced, name: str) -> Optional[Event]:
    """The first host span of that name (``chipbench.`` is prefixed)."""
    for ev in reduced.spans:
        if ev.name == SPAN_PREFIX + name:
            return ev
    return None


def module_runs(dev: DeviceTrace, needle: str, lo: float = 0.0,
                hi: float = float("inf")) -> List[Event]:
    """Executions of the programs whose name contains ``needle``, inside
    [lo, hi]. Where the trace has no module line (CPU rehearsal), ops
    carrying that module name are gathered into one run."""
    if dev.modules:
        return [m for m in dev.modules if needle in m.name and m.start >= lo and m.end <= hi]
    ops = [op for op in dev.ops if needle in op.module and op.start >= lo and op.end <= hi]
    if not ops:
        return []
    return [Event(needle, ops[0].start, max(op.end for op in ops))]


def busy_in_runs(dev: DeviceTrace, runs: Sequence[Event]) -> List[float]:
    """Device-busy seconds inside each program execution."""
    return [busy_seconds(dev, r.start, r.end) for r in runs]


def top_ops(dev: DeviceTrace, lo: float, hi: float, k: int = 10) -> List[List[object]]:
    """The k op names with most device time in [lo, hi]: [name, seconds]."""
    total: Dict[str, float] = {}
    for ev in dev.ops:
        s, e = max(ev.start, lo), min(ev.end, hi)
        if e > s:
            total[ev.name] = total.get(ev.name, 0.0) + (e - s) * 1e-9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(reduced: Reduced, dev: DeviceTrace, lo: float, hi: float,
              k: int = 10) -> List[List[object]]:
    """The k longest stretches of [lo, hi] with no op on the device, each
    named by the innermost benchmark span covering its middle (what the
    host was doing), or ``outside-spans``: [name, seconds]."""
    busy = union(clip(dev.ops, lo, hi))
    gaps: List[Interval] = []
    at = lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out: List[List[object]] = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e)
        covering = [sp for sp in reduced.spans if sp.start <= mid <= sp.end]
        name = "outside-spans"
        if covering:
            inner = min(covering, key=lambda sp: sp.end - sp.start)
            name = inner.name[len(SPAN_PREFIX):]
        out.append([name, (e - s) * 1e-9])
    return out


def describe(path: str, per_line: int = 5) -> str:
    """Planes, lines and a few events of each, for reading by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    rows: List[str] = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: v for k, v in list(ev.stats)[:6]}
                rows.append(
                    f"    {ev.name[:90]!r} start_ns={ev.start_ns:.0f} "
                    f"dur_ns={ev.duration_ns:.0f} stats={stats}"
                )
    return "\n".join(rows)


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    print(describe(target))
