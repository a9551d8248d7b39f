"""Shared AST machinery for the byzlint rule engine.

Everything here is pure stdlib-``ast`` analysis: import-alias resolution
(so ``lax.psum`` and ``from jax.lax import psum`` both resolve to the
same qualified name), discovery of *traced contexts* (functions whose
bodies execute under ``jax.jit`` / ``shard_map`` / ``pmap`` tracing or as
``pallas_call`` kernels), string constant propagation for axis-name
resolution, and extraction of donation signatures from ``jax.jit``
calls. Rules in :mod:`byzpy_tpu.analysis.rules` are thin walks over
these primitives.

No jax import happens here — the linter must run in seconds on a machine
with no accelerator runtime.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

#: Qualified names that mean "this function body is traced by XLA".
JIT_QUALNAMES = {
    "jax.jit",
    "jax.pjit",
    "jax.experimental.pjit.pjit",
}

#: Last-component names of SPMD wrappers that trace their mapped function.
SPMD_WRAPPERS = {"shard_map", "pmap", "xmap"}

#: Known mesh-constructor helpers → the axis names they bind. The jax
#: constructors are resolved structurally (tuple-of-string argument); the
#: repo helpers carry their axis defaults so in-repo call sites resolve.
MESH_HELPER_AXES = {
    "node_mesh": ("nodes",),
    "feature_mesh": ("feat",),
    "grid_mesh": ("nodes", "data"),
}


def build_import_map(tree: ast.Module) -> Dict[str, str]:
    """Map local names to dotted import paths for one module.

    ``import jax.numpy as jnp`` → ``{"jnp": "jax.numpy"}``; ``from jax
    import lax`` → ``{"lax": "jax.lax"}``. Relative imports are stored
    with the leading dots stripped (``from ..ops import robust``
    → ``{"robust": "ops.robust"}``) — matching is therefore
    done on name suffixes, not full paths, where relative imports occur.
    """
    imports: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.asname:
                    imports[alias.asname] = alias.name
                else:
                    head = alias.name.split(".")[0]
                    imports[head] = head
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            for alias in node.names:
                local = alias.asname or alias.name
                imports[local] = f"{base}.{alias.name}" if base else alias.name
    return imports


def qualname(node: ast.AST, imports: Dict[str, str]) -> Optional[str]:
    """Resolve an attribute chain to a dotted qualified name, or ``None``.

    ``lax.psum`` with ``from jax import lax`` resolves to
    ``"jax.lax.psum"``; a chain rooted in anything other than a plain
    name (a call result, a subscript) resolves to ``None``.
    """
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(imports.get(cur.id, cur.id))
        return ".".join(reversed(parts))
    return None


def last_component(qual: Optional[str]) -> str:
    """Final dotted component of a qualified name (``""`` for ``None``)."""
    return qual.rsplit(".", 1)[-1] if qual else ""


def string_consts(scopes: Sequence[ast.AST]) -> Dict[str, Optional[str]]:
    """Best-effort constant propagation for string variables.

    Scans simple ``name = "literal"`` assignments in the given scopes
    (innermost last). A name assigned exactly one string literal maps to
    that literal; a name assigned twice with different values (or any
    non-literal) maps to ``None`` (ambiguous — callers must stay silent).
    """
    out: Dict[str, Optional[str]] = {}
    for scope in scopes:
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if not isinstance(tgt, ast.Name):
                    continue
                if isinstance(node.value, ast.Constant) and isinstance(
                    node.value.value, str
                ):
                    prev = out.get(tgt.id, node.value.value)
                    out[tgt.id] = (
                        node.value.value if prev == node.value.value else None
                    )
                else:
                    out[tgt.id] = None
    return out


def resolve_str(
    node: ast.AST, consts: Dict[str, Optional[str]]
) -> Optional[str]:
    """A string literal, or a name that constant-propagates to one."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _callable_qual(node: ast.AST, imports: Dict[str, str]) -> Optional[str]:
    """Qualified name of a decorator/callable expression, unwrapping
    ``functools.partial(f, ...)`` to ``f``."""
    if isinstance(node, ast.Call):
        fq = qualname(node.func, imports)
        if last_component(fq) == "partial" and node.args:
            return _callable_qual(node.args[0], imports)
        return fq
    return qualname(node, imports)


def traced_kind(dec: ast.AST, imports: Dict[str, str]) -> Optional[str]:
    """Classify a decorator: ``"jit"``, ``"shard_map"``, ``"pmap"``, or
    ``None`` when the decorator does not put the body under a trace."""
    qual = _callable_qual(dec, imports)
    if qual in JIT_QUALNAMES:
        return "jit"
    last = last_component(qual)
    if last in ("shard_map", "xmap"):
        return "shard_map"
    if last == "pmap":
        return "pmap"
    return None


FunctionNode = (ast.FunctionDef, ast.AsyncFunctionDef)


@dataclass
class TracedFn:
    """One function whose body runs under a JAX trace."""

    node: ast.AST  # FunctionDef / AsyncFunctionDef / Lambda
    kind: str  # "jit" | "shard_map" | "pmap" | "pallas"
    #: the shard_map/pmap wrapping Call when one exists (for axis specs)
    binding: Optional[ast.Call] = None
    #: parameters that are *static* under the trace (jit
    #: static_argnums/static_argnames, kwargs pre-bound via
    #: ``functools.partial`` at a pallas_call/wrap site) — host-side
    #: Python values, exempt from traced-value rules
    static_params: Set[str] = field(default_factory=set)


def _positional_params(fn: ast.AST) -> Tuple[str, ...]:
    args = getattr(fn, "args", None)
    if args is None:
        return ()
    return tuple(a.arg for a in args.posonlyargs + args.args)


def static_param_names(call: ast.Call, fn: ast.AST) -> Set[str]:
    """Static parameter names declared by a ``jax.jit`` call/decorator
    (``static_argnames`` literals; ``static_argnums`` mapped through the
    wrapped def's positional parameters)."""
    names: Set[str] = set()
    params = _positional_params(fn)
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            lits = _str_literals(kw.value)
            if lits:
                names |= lits
        elif kw.arg == "static_argnums":
            nums = _int_literals(kw.value)
            if nums:
                names |= {params[i] for i in nums if i < len(params)}
    return names


def _local_defs(tree: ast.Module) -> Dict[str, ast.AST]:
    """Every function definition in the module by name (first definition
    wins on shadowing — good enough to resolve wrap-call targets)."""
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, FunctionNode):
            defs.setdefault(node.name, node)
    return defs


def traced_functions(
    tree: ast.Module, imports: Dict[str, str]
) -> List[TracedFn]:
    """Every function in the module whose body executes under a trace.

    Four discovery paths: (1) decorators — ``@jax.jit``,
    ``@partial(jax.jit, ...)``, ``@partial(shard_map, ...)``; (2) wrap
    call sites — ``jax.jit(fn)``, ``shard_map(fn, ...)``, ``pmap(fn)``
    where ``fn`` names a local def or is an inline lambda; (3) kernels —
    the first argument of any ``pallas_call``; (4) nested defs inside any
    of the above are implicitly traced (callers should walk the returned
    nodes recursively, which covers them).
    """
    defs = _local_defs(tree)
    found: List[TracedFn] = []
    by_id: Dict[int, TracedFn] = {}

    def add(
        node: ast.AST,
        kind: str,
        binding: Optional[ast.Call],
        statics: Set[str],
    ) -> None:
        if id(node) in by_id:
            by_id[id(node)].static_params |= statics
        else:
            traced = TracedFn(node, kind, binding, statics)
            by_id[id(node)] = traced
            found.append(traced)

    # decorators
    for node in ast.walk(tree):
        if isinstance(node, FunctionNode):
            for dec in node.decorator_list:
                kind = traced_kind(dec, imports)
                if kind is not None:
                    binding = dec if isinstance(dec, ast.Call) else None
                    statics = (
                        static_param_names(dec, node)
                        if isinstance(dec, ast.Call)
                        else set()
                    )
                    add(node, kind, binding, statics)

    # wrap call sites + pallas kernels
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fq = qualname(node.func, imports)
        last = last_component(fq)
        kind: Optional[str] = None
        if fq in JIT_QUALNAMES:
            kind = "jit"
        elif last in ("shard_map", "xmap"):
            kind = "shard_map"
        elif last == "pmap":
            kind = "pmap"
        elif last == "pallas_call":
            kind = "pallas"
        if kind is None or not node.args:
            continue
        target = node.args[0]
        prebound: Set[str] = set()
        if isinstance(target, ast.Call):  # partial(kernel, k=3, ...)
            tq = qualname(target.func, imports)
            if last_component(tq) == "partial" and target.args:
                prebound = {kw.arg for kw in target.keywords if kw.arg}
                target = target.args[0]
        binding = node if kind in ("shard_map", "pmap") else None
        resolved: Optional[ast.AST] = None
        if isinstance(target, ast.Lambda):
            resolved = target
        elif isinstance(target, ast.Name) and target.id in defs:
            resolved = defs[target.id]
        if resolved is not None:
            statics = prebound | static_param_names(node, resolved)
            add(resolved, kind, binding, statics)
    return found


def enclosing_param_names(fn: ast.AST) -> Set[str]:
    """Parameter names of one function/lambda node."""
    args = getattr(fn, "args", None)
    if args is None:
        return set()
    names = {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    return names


# ---------------------------------------------------------------------------
# Donation signatures
# ---------------------------------------------------------------------------


@dataclass
class DonationSig:
    """Donated-argument positions/names of one jitted callable."""

    argnums: Set[int] = field(default_factory=set)
    argnames: Set[str] = field(default_factory=set)
    #: positional parameter names of the wrapped fn when statically known
    params: Tuple[str, ...] = ()

    def donated_args(self, call: ast.Call) -> List[Tuple[str, ast.AST]]:
        """``(variable-name, arg-node)`` pairs donated at this call site
        (only plain-name arguments are tracked)."""
        out: List[Tuple[str, ast.AST]] = []
        names = set(self.argnames)
        nums = set(self.argnums)
        for name in self.argnames:
            if name in self.params:
                nums.add(self.params.index(name))
        for i, arg in enumerate(call.args):
            donated = i in nums or (
                i < len(self.params) and self.params[i] in names
            )
            if donated and isinstance(arg, ast.Name):
                out.append((arg.id, arg))
        for kw in call.keywords:
            if kw.arg is None:
                continue
            donated = kw.arg in names or (
                kw.arg in self.params and self.params.index(kw.arg) in nums
            )
            if donated and isinstance(kw.value, ast.Name):
                out.append((kw.value.id, kw.value))
        return out


def _int_literals(node: ast.AST) -> Optional[Set[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for elt in node.elts:
            sub = _int_literals(elt)
            if sub is None:
                return None
            out |= sub
        return out
    return None


def _str_literals(node: ast.AST) -> Optional[Set[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for elt in node.elts:
            sub = _str_literals(elt)
            if sub is None:
                return None
            out |= sub
        return out
    return None


def donation_from_call(
    call: ast.Call, imports: Dict[str, str], defs: Dict[str, ast.AST]
) -> Optional[DonationSig]:
    """Donation signature of a ``jax.jit(fn, donate_arg...=...)`` call
    (or ``partial(jax.jit, donate_arg...=...)`` decorator), ``None`` when
    the call does not donate or the donation spec is not literal."""
    fq = _callable_qual(call, imports)
    if fq not in JIT_QUALNAMES:
        return None
    sig = DonationSig()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            nums = _int_literals(kw.value)
            if nums is None:
                return None
            sig.argnums |= nums
        elif kw.arg == "donate_argnames":
            names = _str_literals(kw.value)
            if names is None:
                return None
            sig.argnames |= names
    if not sig.argnums and not sig.argnames:
        return None
    # recover the wrapped fn's positional params when it is a local def
    target = call.args[0] if call.args else None
    if isinstance(target, ast.Name) and target.id in defs:
        fn = defs[target.id]
        args = getattr(fn, "args", None)
        if args is not None:
            sig.params = tuple(a.arg for a in args.posonlyargs + args.args)
    return sig


__all__ = [
    "JIT_QUALNAMES",
    "SPMD_WRAPPERS",
    "MESH_HELPER_AXES",
    "DonationSig",
    "TracedFn",
    "build_import_map",
    "donation_from_call",
    "enclosing_param_names",
    "last_component",
    "qualname",
    "resolve_str",
    "static_param_names",
    "string_consts",
    "traced_functions",
    "traced_kind",
]
