"""The contract's rules for ``BENCHMARK.json``, as far as a file can be
checked without a chip. ``check`` returns the list of breaches."""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
CONFIG = {"name", "source", "file", "reduced", "why"}
CELL = {"name", "config", "traffic", "chips", "why"}
E2E = {"name", "unit", "better", "bound", "source"}
LAYER = {"name", "unit", "better", "source", "layer", "moves"}
MAX_CELLS, RUNS_PER_CELL, BUDGET_S = 24, 14, 43200


def _line(text: Any) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def _under(path: str, roots: List[str]) -> bool:
    return any(path == r or path.startswith(r.rstrip("/") + "/") for r in roots)


def check(manifest: Dict[str, Any], root: str, raw_bytes: int = 0) -> List[str]:
    bad: List[str] = []
    if set(manifest) != TOP:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP)}")
        return bad
    if raw_bytes > 64 * 1024:
        bad.append("file over 64 KiB")
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        bad.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r}")
        elif not os.path.isdir(os.path.join(root, p)):
            bad.append(f"path {p!r} is no directory")
    command = manifest["command"]
    if not 1 <= len(command) <= 32 or not all(_line(w) for w in command):
        bad.append("command: 1 to 32 words of 1 to 200 characters")
    for word in command[1:]:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not _under(word, paths):
            bad.append(f"command names {word!r}, outside paths")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        bad.append("run_seconds: a whole number from 1 to 51")
    elif (2 + RUNS_PER_CELL * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200 > BUDGET_S:
        bad.append("run_seconds: a full check with 24 cells does not fit 43200 s")

    names: Dict[str, set] = {"configs": set(), "workloads": set(), "metrics": set()}

    def name_of(entry, group, what):
        n = entry.get("name")
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{what} name {n!r}")
        if n in names[group]:
            bad.append(f"{what} name {n!r} twice")
        names[group].add(n)
        return n

    files = set()
    if not 1 <= len(manifest["configs"]) <= 24:
        bad.append("configs: 1 to 24")
    for c in manifest["configs"]:
        n = name_of(c, "configs", "config")
        if set(c) != CONFIG:
            bad.append(f"config {n}: keys {sorted(c)}")
            continue
        if not _line(c["source"]) or not _line(c["why"]):
            bad.append(f"config {n}: source/why 1 to 200 characters on one line")
        if not PATH.match(c["file"]) or not _under(c["file"], paths) or c["file"] in files:
            bad.append(f"config {n}: file {c['file']!r}")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {n}: file {c['file']!r} missing")
        else:
            with open(os.path.join(root, c["file"]), encoding="utf-8") as fh:
                json.load(fh)
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(isinstance(k, str) and NAME.match(k) for k in c["reduced"]):
            bad.append(f"config {n}: reduced")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        bad.append("workloads: 1 to 24")
    pairs = set()
    for w in cells:
        n = name_of(w, "workloads", "workload")
        if set(w) != CELL:
            bad.append(f"workload {n}: keys {sorted(w)}")
            continue
        if w["config"] not in names["configs"]:
            bad.append(f"workload {n}: unknown config {w['config']!r}")
        if not isinstance(w["traffic"], str) or not NAME.match(w["traffic"]):
            bad.append(f"workload {n}: traffic {w['traffic']!r}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {n}: config and traffic already paired")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"workload {n}: chips {w['chips']!r}")
        if not _line(w["why"]):
            bad.append(f"workload {n}: why 1 to 200 characters on one line")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}: over 25%")
    for c in manifest["configs"]:
        if not any(w.get("config") == c.get("name") for w in cells):
            bad.append(f"config {c.get('name')}: used by no cell")

    def common(m, keys, what):
        n = name_of(m, "metrics", what)
        if not keys <= set(m) <= keys | {"workloads"}:
            bad.append(f"{what} {n}: keys {sorted(m)}")
            return None
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            bad.append(f"{what} {n}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{what} {n}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{what} {n}: source {m['source']!r}")
        for cell in m.get("workloads", []):
            if cell not in names["workloads"]:
                bad.append(f"{what} {n}: unknown workload {cell!r}")
        return n

    e2e = manifest["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        bad.append("end_to_end: 1 to 16")
    reports: Dict[str, set] = {w["name"]: set() for w in cells if "name" in w}
    for m in e2e:
        n = common(m, E2E, "end_to_end")
        if n is None:
            continue
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {n}: source must be host_clock or device_trace")
        if not isinstance(m["bound"], (int, float)) or not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"end_to_end {n}: bound {m['bound']!r} outside 0.01 to 0.1")
        for cell in m.get("workloads", list(reports)):
            reports.setdefault(cell, set()).add(n)
    if "setup_s" not in {m.get("name") for m in e2e}:
        bad.append("end_to_end lacks setup_s")
    layered = {cell: 0 for cell in reports}
    if not 1 <= len(manifest["per_layer"]) <= 128:
        bad.append("per_layer: 1 to 128")
    for m in manifest["per_layer"]:
        n = common(m, LAYER, "per_layer")
        if n is None:
            continue
        if not isinstance(m["layer"], str) or not NAME.match(m["layer"]):
            bad.append(f"per_layer {n}: layer {m['layer']!r} is no identifier (PR 22 was refused for this)")
        if m["moves"] not in {e.get("name") for e in e2e}:
            bad.append(f"per_layer {n}: moves {m['moves']!r} is no end_to_end metric")
        in_cells = m.get("workloads", [c for c, r in reports.items() if m["moves"] in r])
        for cell in in_cells:
            if m["moves"] not in reports.get(cell, set()):
                bad.append(f"per_layer {n}: cell {cell} does not report {m['moves']}")
            layered[cell] = layered.get(cell, 0) + 1
        reader = os.path.join(root, paths[0], "layer_metrics", f"{n}.py")
        if not os.path.isfile(reader):
            bad.append(f"per_layer {n}: no reader {reader}")
    for cell, r in reports.items():
        if "setup_s" not in r or len(r) < 2:
            bad.append(f"cell {cell}: reports {sorted(r)}; needs setup_s and one more")
        if not layered.get(cell):
            bad.append(f"cell {cell}: no per_layer metric")
    return bad
