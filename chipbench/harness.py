"""What every driver and reader is handed: the cell's files, the seed, the
clock, spans, the profiler, and the devices.

``run.py`` is the command; this module is the part of it a selftest can
drive without a chip (``run_cell`` takes the devices it is given).
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = {"count": 0, "listening": False}


def _on_duration(event: str, seconds: float, **_: Any) -> None:
    if event == _COMPILE_EVENT:
        _compiles["count"] += 1


def resolve(dotted: str) -> Any:
    """``package.module.attribute`` -> the attribute."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_by_path(path: str, name: str) -> Any:
    """Import a file whose name is a metric's name (dots and all)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Ctx:
    manifest: Dict[str, Any]
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: Sequence[Any]
    t_process: float
    control: Optional[str] = None
    trace_dir: str = ""
    spans: List[tuple] = field(default_factory=list)  # (name, t0, t1) host clock
    setup_s: Optional[float] = None
    reduced: Any = None
    outcome: Dict[str, Any] = field(default_factory=dict)
    peaks: Dict[str, Any] = field(default_factory=dict)
    _tracing: bool = False

    @property
    def weights_seed(self) -> int:
        """What the WEIGHTS are drawn from: the configuration's
        ``checkpoint_seed`` where it states one (a deployment trains one
        checkpoint on changing data, so which model is no part of the
        traffic), else ``--seed`` as ever. Batches, step keys and the
        aggregator's roofline matrix are ``--seed``'s in either case."""
        return int(self.config.get("checkpoint_seed", self.seed))

    def say(self, **facts: Any) -> None:
        """One information line; every line names the device."""
        first = self.devices[0]
        facts = {"cell": self.cell["name"], "platform": first.platform,
                 "device_kind": first.device_kind, "devices": len(self.devices), **facts}
        print(json.dumps(facts, default=float), flush=True)

    def control_spec(self) -> Dict[str, Any]:
        """The lower-precision switch of a control run, from the
        configuration's ``controls``; empty in every benchmark run."""
        if self.control is None:
            return {}
        return self.config["controls"][self.control]

    def compiles(self) -> int:
        return _compiles["count"]

    def window_opens(self) -> None:
        self.setup_s = time.perf_counter() - self.t_process

    def memory_peak(self) -> int:
        """Bytes the fullest chip held at one moment, and a line with the
        parts. On a TPU ``memory_stats`` keeps a loaded program's
        temporaries under ``reserved``, apart from the arrays ``in use``,
        and they stay reserved between its runs (a step with 4.59 GB of
        temporaries read 0.21 GB in use and 4.56 GB reserved after it
        ran: PERF.md, PR 25). So what the chip holds now is the sum of
        the two in one reading; each of the two peaks is a moment of its
        own, and they are not added."""
        held, parts = 0, []
        for dev in self.devices:
            stats = dev.memory_stats() or {}
            read = {k: int(stats.get(k, 0)) for k in (
                "bytes_in_use", "bytes_reserved", "peak_bytes_in_use", "peak_bytes_reserved")}
            parts.append(read)
            held = max(held, read["bytes_in_use"] + read["bytes_reserved"],
                       read["peak_bytes_in_use"], read["peak_bytes_reserved"])
        self.say(memory_peak_bytes=held, memory_stats_per_device=parts)
        return held

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept in memory, and written into the profiler's
        trace as ``chipbench.<name>`` while one is being taken."""
        t0 = time.perf_counter()
        if self._tracing:
            import jax

            with jax.profiler.TraceAnnotation("chipbench." + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    @contextlib.contextmanager
    def profile(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True
        try:
            yield
        finally:
            self._tracing = False
            jax.profiler.stop_trace()


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in manifest["workloads"])
    raise SystemExit(f"chipbench: no workload {name!r} in the manifest (has: {known})")


def metrics_of_cell(manifest: Dict[str, Any], cell: str, group: str) -> List[Dict[str, Any]]:
    """The manifest's metrics of ``group`` that this cell reports."""
    e2e_of_cell = {
        m["name"] for m in manifest["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    }
    if group == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e_of_cell]
    return [
        m for m in manifest["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e_of_cell)
    ]


def device_window(ctx: Ctx) -> Dict[str, Any]:
    """Busy and window seconds of the traced steady window, averaged over
    the devices, with the per-device idle shares and the breakdown."""
    from chipbench import trace_reduce as tr

    window = tr.span(ctx.reduced, "window")
    if window is None or not ctx.reduced.devices:
        raise RuntimeError("the trace holds no chipbench.window span or no device ops")
    lo, hi = window.start, window.end
    busy = [tr.busy_seconds(dev, lo, hi) for dev in ctx.reduced.devices]
    window_s = (hi - lo) * 1e-9
    first = ctx.reduced.devices[0]
    return {
        "busy_s": statistics.fmean(busy), "window_s": window_s,
        "idle_share_per_device": [1.0 - b / window_s for b in busy],
        "breakdown": {
            "device_ops": tr.top_ops(first, lo, hi),
            "idle_gaps": tr.idle_gaps(ctx.reduced, first, lo, hi),
        },
    }


def run_cell(
    manifest: Dict[str, Any], workload: str, *, seed: int, seconds: float, trace: bool,
    devices: Sequence[Any], t_process: float, control: Optional[str] = None,
    files_root: str = ROOT, emit: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """One run of one cell on ``devices``; prints its lines and returns
    the object of the last one."""
    import jax

    if not _compiles["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compiles["listening"] = True
    cell = find_cell(manifest, workload)
    config_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(files_root, config_entry["file"])
    mix = load_json(files_root, os.path.dirname(config_entry["file"]), "..", "traffic",
                    cell["traffic"] + ".json")
    ctx = Ctx(
        manifest=manifest, cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
        trace=trace, devices=list(devices), t_process=t_process, control=control,
        trace_dir=os.path.join(HERE, "out", "trace", cell["name"]),
        peaks=load_json(HERE, "peaks.json"),
    )
    first = ctx.devices[0]
    if first.platform == "tpu" and first.device_kind not in ctx.peaks["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind {first.device_kind!r}")
    ctx.say(run="start", seed=seed, weights_seed=ctx.weights_seed, seconds=seconds,
            trace=int(trace), control=control, config=cell["config"], traffic=cell["traffic"])
    driver = importlib.import_module("chipbench.drivers." + mix["driver"])
    out = driver.run(ctx)
    ctx.outcome = out

    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    line: Dict[str, Any] = {}
    if trace:
        from chipbench import trace_reduce as tr

        ctx.reduced = tr.reduce_trace(tr.find_xplane(ctx.trace_dir))
        window = device_window(ctx)
        out["measured"]["device_window"] = window
        device["busy_s"], device["window_s"] = window["busy_s"], window["window_s"]
        totals: Dict[str, float] = {}
        for name, t0, t1 in ctx.spans:
            totals[name] = totals.get(name, 0.0) + (t1 - t0)
        ctx.say(idle_share_per_device=window["idle_share_per_device"], span_seconds=totals)
        for metric in metrics_of_cell(manifest, cell["name"], "per_layer"):
            reader = load_by_path(
                os.path.join(HERE, "layer_metrics", metric["name"] + ".py"), metric["name"]
            )
            value = reader.read(ctx)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
        out["checks"].append(("device_busy_s", window["busy_s"], ">", 0.0))
        line["breakdown"] = window["breakdown"]
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for metric in metrics_of_cell(manifest, cell["name"], "end_to_end"):
            metrics[metric["name"]] = {"value": values[metric["name"]], "unit": metric["unit"]}

    correct = True
    compared: Dict[str, Any] = {}
    for name, value, op, limit in out["checks"]:
        ok = {"<=": value <= limit, "<": value < limit, "==": value == limit,
              ">": value > limit}[op]
        ok = bool(ok) and bool(value == value)  # a NaN never passes
        correct = correct and ok
        ctx.say(compared=name, value=value, must_be=op, limit=limit, ok=ok)
        # and once more where the record of a run that is not correct keeps them:
        # the end of standard error, and the result line's last key
        compared[name] = {"value": value, "must_be": op, "limit": limit, "ok": ok}
        print(json.dumps({"compared": name, **compared[name]}, default=float),
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, **line, "compared": compared}
    emit(json.dumps(line, default=float))
    return line
