"""docs/observability.md ↔ observability/catalog.py parity.

The catalog is the machine-readable single source of truth the
byzlint ``METRIC-CONTRACT`` rule checks code against; the docs tables
are its human rendering. This test parses every metric, span, scope
and kernel row out of the markdown and pins BOTH directions: a docs row naming an
uncatalogued instrument is drift, and a catalogued instrument with no
docs row is an undocumented instrument. Metric types must match
cell-for-cell (one name, one type).
"""

from __future__ import annotations

import os
import re

from byzpy_tpu.observability import catalog

DOCS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs",
    "observability.md",
)

_TYPES = ("counter", "gauge", "histogram")
#: the docs section whose tables are the in-jit names (scopes: dotted;
#: kernels: not), not host spans
_IN_JIT_SECTION = "## In-jit names"


def _doc_tables():
    """Parse the markdown tables: ``(metrics, metric_prefixes, spans,
    span_prefixes, scopes, scope_prefixes, kernels)``. Metric rows may carry several
    backticked names per cell with one shared type or a slash-separated
    type per name; ``<...>`` placeholders declare prefix families."""
    with open(DOCS, encoding="utf-8") as fh:
        text = fh.read()
    metrics, metric_prefixes = {}, set()
    spans, span_prefixes = set(), set()
    scopes, scope_prefixes, kernels = set(), set(), set()
    in_jit = False
    for line in text.splitlines():
        if line.startswith("## "):
            in_jit = line.startswith(_IN_JIT_SECTION)
        if not line.startswith("| `"):
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        names = re.findall(r"`([a-zA-Z0-9_.<>]+)`", cells[0])
        if not names:
            continue
        if in_jit:
            for name in names:
                if "<" in name:
                    scope_prefixes.add(name.split("<", 1)[0])
                else:
                    (scopes if "." in name else kernels).add(name)
            continue
        types = [t.strip() for t in cells[1].split("/")] if len(cells) > 1 else []
        if all(t in _TYPES for t in types) and types:
            # a metric row: one shared type, or one type per name
            assert len(types) in (1, len(names)), f"ragged metric row: {line}"
            for i, name in enumerate(names):
                t = types[i] if len(types) == len(names) else types[0]
                if "<" in name:
                    metric_prefixes.add(name.split("<", 1)[0])
                else:
                    assert metrics.get(name, t) == t, (
                        f"{name} documented under two types"
                    )
                    metrics[name] = t
            continue
        for name in names:
            # span rows: dotted labels only (skip config/code lookalikes)
            if "." not in name or name.startswith("byzpy_"):
                continue
            if "<" in name:
                span_prefixes.add(name.split("<", 1)[0])
            else:
                spans.add(name)
    return metrics, metric_prefixes, spans, span_prefixes, scopes, scope_prefixes, kernels


def test_catalog_is_well_formed():
    assert catalog.METRICS, "empty metric catalog"
    assert catalog.SPANS, "empty span catalog"
    for name, mtype in catalog.METRICS.items():
        assert name.startswith("byzpy_"), name
        assert mtype in _TYPES, (name, mtype)
    for prefix in catalog.METRIC_PREFIXES:
        assert prefix.startswith("byzpy_"), prefix
    for scope in catalog.SCOPES:
        assert re.fullmatch(r"(round|serving|model|stream)\.[a-z_]+", scope), scope
    for prefix in catalog.SCOPE_PREFIXES:
        # a family never shadows a listed scope's namespace
        assert prefix.endswith(".") and not any(s.startswith(prefix) for s in catalog.SCOPES)
    for kernel in catalog.KERNELS:
        assert re.fullmatch(r"[a-z][a-z0-9_]+", kernel), kernel
    # one namespace: an in-jit scope never reuses a host span's label
    assert not set(catalog.SCOPES) & set(catalog.SPANS)


def test_docs_metric_tables_match_catalog_both_ways():
    metrics, prefixes, *_ = _doc_tables()
    assert metrics, "no metric rows parsed from docs/observability.md"
    mismatched = {
        n: (t, catalog.METRICS.get(n))
        for n, t in metrics.items()
        if catalog.METRICS.get(n) != t
    }
    assert not mismatched, f"docs rows drifting from catalog: {mismatched}"
    undocumented = sorted(set(catalog.METRICS) - set(metrics))
    assert not undocumented, f"catalogued but not in docs: {undocumented}"
    assert prefixes == set(catalog.METRIC_PREFIXES)


def test_docs_span_table_matches_catalog_both_ways():
    _m, _p, spans, span_prefixes, *_ = _doc_tables()
    assert spans, "no span rows parsed from docs/observability.md"
    unknown = sorted(spans - set(catalog.SPANS))
    assert not unknown, f"docs span rows drifting from catalog: {unknown}"
    undocumented = sorted(set(catalog.SPANS) - spans)
    assert not undocumented, f"catalogued but not in docs: {undocumented}"
    assert span_prefixes == set(catalog.SPAN_PREFIXES)


def test_docs_scope_table_matches_catalog_both_ways():
    *_, scopes, scope_prefixes, _kernels = _doc_tables()
    assert scopes, "no scope rows parsed from docs/observability.md"
    unknown = sorted(scopes - set(catalog.SCOPES))
    assert not unknown, f"docs scope rows drifting from catalog: {unknown}"
    undocumented = sorted(set(catalog.SCOPES) - scopes)
    assert not undocumented, f"catalogued but not in docs: {undocumented}"
    assert scope_prefixes == set(catalog.SCOPE_PREFIXES)


def test_docs_kernel_table_matches_catalog_both_ways():
    *_, kernels = _doc_tables()
    assert kernels, "no kernel rows parsed from docs/observability.md"
    unknown = sorted(kernels - set(catalog.KERNELS))
    assert not unknown, f"docs kernel rows drifting from catalog: {unknown}"
    undocumented = sorted(set(catalog.KERNELS) - kernels)
    assert not undocumented, f"catalogued but not in docs: {undocumented}"
