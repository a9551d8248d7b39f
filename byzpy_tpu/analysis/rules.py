"""The byzlint rule catalog.

Each rule encodes one *silent-until-runtime* JAX hazard this repo has
actually shipped and debugged (see ``docs/static_analysis.md`` for the
incident behind each one):

* ``TRACE-DISPATCH`` — env/tile-cache/dispatch-config reads inside a
  traced body (jit / shard_map / pmap / pallas kernel). Dispatch must
  resolve in the Python wrapper *before* trace, or the first-trace value
  is baked into the compiled executable forever.
* ``DONATION`` — a buffer donated via ``donate_argnums``/``argnames`` is
  read again after the jitted call (or re-passed on the next loop
  iteration without rebinding): XLA has already reused its memory.
* ``AXIS-BINDING`` — a collective inside ``shard_map``/``pmap`` names an
  axis the enclosing mesh/spec does not bind (an unbound-axis NameError
  at best, silent wrong-mesh reduction at worst).
* ``HOST-SYNC`` — ``.item()`` / ``np.asarray`` / ``float(param)`` on
  traced values inside traced bodies (TracerConversionError), or forced
  device syncs inside the PS/gossip round loops (kills the overlap
  pipeline).
* ``ASYNC-BLOCKING`` — blocking calls (``time.sleep``, sync process
  joins, raw-socket ops, ``open``) directly in an ``async def``: one
  stalled coroutine freezes every actor sharing the event loop.
* ``PYTREE-REG`` — an instance of a scanned-tree class passed into a
  collective without pytree registration (jax would treat it as a leaf
  and fail — or silently close over it as a constant).
* ``THREAD-SHARED`` — a ``self.*`` attribute written from two distinct
  execution contexts (event loop / reader thread / executor, per the
  :mod:`.contexts` classifier) with no common lock guard: the PR 19
  arrival-time staging race, as a rule.
* ``ACK-ORDER`` — in a function that both appends to a durability/WAL
  object and sends on a writer, every path must append *before* it
  sends: an ack is a durable promise (the PR 9 double-fold replay).
* ``PARITY-PURITY`` — functions reachable from the digest-parity set
  (``_agg_digest``, ``fold_merge_*``, ``combine_partials``,
  ``gram_block``, trace digests) must not call clocks/RNG or iterate
  bare sets into folded bytes (the PR 7 np.mean digest drift, class of).
* ``METRIC-CONTRACT`` — every metric registration, span label,
  ``named_scope`` label and ``pallas_call`` name must appear, with a
  matching type, in ``byzpy_tpu/observability/catalog.py`` (single
  source of truth; the docs tables are checked against it).

Rules are deliberately *precise over complete*: each stays silent when
static resolution fails rather than guessing, so a finding is worth
reading. The self-scan gate (``tests/test_analysis_selfclean.py``) keeps
the shipped tree clean.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .astutils import (
    FunctionNode,
    MESH_HELPER_AXES,
    donation_from_call,
    enclosing_param_names,
    last_component,
    qualname,
    resolve_str,
    string_consts,
    traced_functions,
    _local_defs,
)
from .contexts import (
    CONCURRENT_LABELS,
    ContextMap,
    FnInfo,
    build_context_map,
    receiver_text,
)
from .core import Finding, ModuleInfo
from ..observability import catalog

TRACE_DISPATCH = "TRACE-DISPATCH"
DONATION = "DONATION"
AXIS_BINDING = "AXIS-BINDING"
HOST_SYNC = "HOST-SYNC"
ASYNC_BLOCKING = "ASYNC-BLOCKING"
PYTREE_REG = "PYTREE-REG"
THREAD_SHARED = "THREAD-SHARED"
ACK_ORDER = "ACK-ORDER"
PARITY_PURITY = "PARITY-PURITY"
METRIC_CONTRACT = "METRIC-CONTRACT"

#: collective name → positional index of the axis-name argument
COLLECTIVE_AXIS_ARG: Dict[str, int] = {
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "psum_scatter": 1,
    "all_gather": 1,
    "ppermute": 1,
    "pshuffle": 1,
    "all_to_all": 1,
    "axis_index": 0,
    "axis_size": 0,
    # byzpy_tpu.parallel.collectives wrappers (same calling convention)
    "all_reduce_sum": 1,
    "all_reduce_mean": 1,
    "reduce_scatter_sum": 1,
    "neighbor_shift": 1,
    "ring_all_reduce_sum": 1,
    "all_gather_q": 1,
    "reduce_scatter_sum_q": 1,
    "all_to_all_q": 1,
}

#: the kernel dispatch gate and its parts that read ``BYZPY_TPU_PALLAS``
#: (``ops/pallas_kernels.py``, "Dispatch policy"): asked mid-trace, the
#: first call's answer is baked into the compiled executable — the PR-2
#: incident
DISPATCH_HELPERS = {"pallas_serves", "use_pallas_for", "aligned_width"}

#: blocking callables by resolved qualified name
BLOCKING_QUALNAMES = {
    "time.sleep",
    "select.select",
    "socket.create_connection",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
    "os.system",
    "os.waitpid",
    "urllib.request.urlopen",
}

#: sync-socket method names (never awaitable; asyncio code uses streams)
BLOCKING_SOCKET_ATTRS = {"recv", "recv_into", "recvfrom", "accept"}

#: receiver-name hints for blocking ``.join()`` (process/thread handles —
#: kept narrow so ``", ".join(...)`` never matches)
JOIN_RECEIVER_HINTS = ("proc", "thread", "worker", "child")


@dataclass
class ScanContext:
    """Cross-module facts collected before rules run (pass 0).

    ``PYTREE-REG`` needs the whole scanned tree: a class is defined in
    one module (``QuantizedBlocks`` in ``parallel/quantization.py``) and
    flowed through a collective in another (``parallel/collectives.py``).
    The concurrency rules (``THREAD-SHARED`` / ``PARITY-PURITY``) share
    one execution-context classification per module, built here so the
    per-module call graph is computed once, not once per rule.
    """

    #: every class name defined anywhere in the scanned tree
    class_names: Set[str] = field(default_factory=set)
    #: subset registered as pytrees (decorator, registration call,
    #: NamedTuple base, or flax.struct dataclass)
    registered_pytrees: Set[str] = field(default_factory=set)
    #: module relpath → execution-context classification (contexts.py)
    contexts: Dict[str, ContextMap] = field(default_factory=dict)

    @staticmethod
    def build(modules: Sequence[ModuleInfo]) -> "ScanContext":
        """Collect class definitions and pytree registrations tree-wide."""
        ctx = ScanContext()
        reg_decorators = {
            "register_pytree_node_class",
            "register_pytree_with_keys_class",
        }
        reg_calls = {
            "register_pytree_node",
            "register_pytree_with_keys",
            "register_dataclass",
            "register_static",
        }
        for mod in modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    ctx.class_names.add(node.name)
                    for dec in node.decorator_list:
                        target = dec.func if isinstance(dec, ast.Call) else dec
                        dq = qualname(target, mod.imports)
                        if last_component(dq) in reg_decorators or (
                            dq is not None and dq.endswith("struct.dataclass")
                        ):
                            ctx.registered_pytrees.add(node.name)
                    for base in node.bases:
                        if last_component(qualname(base, mod.imports)) in (
                            "NamedTuple",
                        ):
                            ctx.registered_pytrees.add(node.name)
                elif isinstance(node, ast.Call):
                    if (
                        last_component(qualname(node.func, mod.imports))
                        in reg_calls
                        and node.args
                        and isinstance(node.args[0], ast.Name)
                    ):
                        ctx.registered_pytrees.add(node.args[0].id)
        for mod in modules:
            ctx.contexts[mod.relpath] = build_context_map(mod)
        return ctx


class Rule:
    """Base class: one hazard, one ``check`` over a parsed module."""

    id: str = ""
    summary: str = ""

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Yield findings for ``mod`` (pure; no I/O)."""
        raise NotImplementedError

    def finding(self, mod: ModuleInfo, node: ast.AST, message: str) -> Finding:
        """Build a finding anchored at ``node``'s source location."""
        return Finding(
            self.id,
            mod.relpath,
            getattr(node, "lineno", 1),
            getattr(node, "col_offset", 0),
            message,
        )


# ---------------------------------------------------------------------------
# TRACE-DISPATCH
# ---------------------------------------------------------------------------


class TraceDispatchRule(Rule):
    """No env/dispatch-gate reads inside traced bodies."""

    id = TRACE_DISPATCH
    summary = (
        "os.environ / dispatch-gate reads must resolve in "
        "the Python wrapper before trace, never inside a jitted body"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Flag env and dispatch-gate reads lexically inside any traced
        function (jit/shard_map/pmap decorated, wrapped, or a pallas
        kernel), including nested defs."""
        seen: Set[Tuple[int, int]] = set()
        for traced in traced_functions(mod.tree, mod.imports):
            for node in ast.walk(traced.node):
                key = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
                if isinstance(node, ast.Attribute):
                    if qualname(node, mod.imports) == "os.environ" and key not in seen:
                        seen.add(key)
                        yield self.finding(
                            mod,
                            node,
                            "os.environ read inside a traced body — the "
                            "first-trace value is baked into the compiled "
                            "executable; resolve it in the Python wrapper "
                            "pre-trace (PR-2 wrapper pattern)",
                        )
                elif isinstance(node, ast.Call):
                    fq = qualname(node.func, mod.imports)
                    if fq == "os.getenv" and key not in seen:
                        seen.add(key)
                        yield self.finding(
                            mod,
                            node,
                            "os.getenv inside a traced body — resolve env "
                            "config in the wrapper pre-trace",
                        )
                    elif (
                        fq is not None
                        and last_component(fq) in DISPATCH_HELPERS
                        and key not in seen
                    ):
                        seen.add(key)
                        yield self.finding(
                            mod,
                            node,
                            f"dispatch helper {last_component(fq)!r} called "
                            "inside a traced body — the kernel route is a "
                            "static jit argument and must be read pre-trace",
                        )


# ---------------------------------------------------------------------------
# DONATION
# ---------------------------------------------------------------------------


def _store_names(stmt: ast.stmt) -> Set[str]:
    """Names (re)bound by one statement, including tuple unpacking and
    loop targets."""
    out: Set[str] = set()
    for node in ast.walk(stmt):
        if isinstance(node, ast.Name) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            out.add(node.id)
    return out


class DonationRule(Rule):
    """No reads of a donated buffer after the donating jitted call."""

    id = DONATION
    summary = (
        "an argument donated via donate_argnums/donate_argnames must not "
        "be referenced after the jitted call in the same scope"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Track ``jax.jit(..., donate_arg*)`` callables (decorators and
        local assignments), then scan each call site's scope for
        use-after-donate — straight-line reads after the call, sibling
        reads in the same statement, and loop re-entry without rebinding."""
        defs = _local_defs(mod.tree)
        donating: Dict[str, object] = {}
        # decorated defs
        for name, fn in defs.items():
            for dec in getattr(fn, "decorator_list", []):
                if isinstance(dec, ast.Call):
                    # @partial(jax.jit, donate_...) — reuse the extractor
                    # by treating the decorator like a jit call wrapping fn
                    sig = donation_from_call(dec, mod.imports, defs)
                    if sig is not None:
                        args = getattr(fn, "args", None)
                        if args is not None:
                            sig.params = tuple(
                                a.arg for a in args.posonlyargs + args.args
                            )
                        donating[name] = sig
        # local `jitted = jax.jit(f, donate_...)` assignments bind the
        # donating callable to ONE scope — a same-named, non-donating
        # `step` in a sibling function must not inherit the signature
        def scope_assigns(scope: ast.AST) -> Dict[str, object]:
            out: Dict[str, object] = {}
            for node in _scope_nodes_ordered(scope):
                if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call
                ):
                    sig = donation_from_call(node.value, mod.imports, defs)
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            if sig is not None:
                                out[tgt.id] = sig
                            else:
                                out.pop(tgt.id, None)
            return out

        module_assigns = scope_assigns(mod.tree)
        scopes: List[ast.AST] = [mod.tree]
        for node in ast.walk(mod.tree):
            if isinstance(node, FunctionNode):
                scopes.append(node)
        for scope in scopes:
            scoped = dict(donating)
            scoped.update(module_assigns)
            if scope is not mod.tree:
                local = scope_assigns(scope)
                # a local assignment SHADOWS any same-named outer binding,
                # donating or not
                for name in {
                    t.id
                    for n in _scope_nodes_ordered(scope)
                    if isinstance(n, ast.Assign)
                    for t in n.targets
                    if isinstance(t, ast.Name)
                }:
                    scoped.pop(name, None)
                scoped.update(local)
            if not scoped:
                continue
            yield from self._scan_block(mod, scope.body, scoped, loops=())

    def _scan_block(
        self,
        mod: ModuleInfo,
        block: Sequence[ast.stmt],
        donating: Dict[str, object],
        loops: Tuple[ast.stmt, ...],
    ) -> Iterator[Finding]:
        for idx, stmt in enumerate(block):
            if isinstance(stmt, FunctionNode):
                continue  # nested function bodies are their own scopes
            for call in self._donated_calls(stmt, donating):
                sig = donating[call.func.id]  # type: ignore[union-attr]
                for var, arg_node in sig.donated_args(call):  # type: ignore[attr-defined]
                    yield from self._check_use_after(
                        mod, block, idx, stmt, call, var, arg_node, loops
                    )
            # recurse into compound statements (their bodies are part of
            # this scope's control flow)
            for sub_block, is_loop in _sub_blocks(stmt):
                yield from self._scan_block(
                    mod,
                    sub_block,
                    donating,
                    loops + ((stmt,) if is_loop else ()),
                )

    @staticmethod
    def _donated_calls(
        stmt: ast.stmt, donating: Dict[str, object]
    ) -> List[ast.Call]:
        out = []
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                if node.func.id in donating:
                    out.append(node)
        return out

    def _check_use_after(
        self,
        mod: ModuleInfo,
        block: Sequence[ast.stmt],
        idx: int,
        stmt: ast.stmt,
        call: ast.Call,
        var: str,
        arg_node: ast.AST,
        loops: Tuple[ast.stmt, ...],
    ) -> Iterator[Finding]:
        call_arg_ids = {id(n) for n in ast.walk(call)}
        rebound_here = var in _store_names(stmt)
        # sibling read in the same statement, outside the call itself
        for node in ast.walk(stmt):
            if (
                isinstance(node, ast.Name)
                and node.id == var
                and isinstance(node.ctx, ast.Load)
                and id(node) not in call_arg_ids
            ):
                yield self.finding(
                    mod,
                    node,
                    f"{var!r} is donated to {call.func.id!r} in this same "  # type: ignore[union-attr]
                    "statement — its buffer may already be reused",
                )
                return
        if not rebound_here:
            # straight-line reads after the call until a rebind. Loads are
            # checked per-statement BEFORE the rebind stops the scan:
            # `state = state + 1` rebinds, but its RHS still reads the
            # donated buffer first
            for later in block[idx + 1 :]:
                load = next(
                    (
                        node
                        for node in ast.walk(later)
                        if isinstance(node, ast.Name)
                        and node.id == var
                        and isinstance(node.ctx, ast.Load)
                    ),
                    None,
                )
                if load is not None:
                    yield self.finding(
                        mod,
                        load,
                        f"{var!r} read after being donated to "
                        f"{call.func.id!r} (line {call.lineno}) — "  # type: ignore[union-attr]
                        "use the call's result, or drop it from "
                        "donate_argnums",
                    )
                    return
                if var in _store_names(later):
                    return  # rebound (without a read) — safe from here on
            # loop re-entry: donated var never rebound inside the loop
            if loops:
                loop = loops[-1]
                if var not in _store_names(loop):
                    yield self.finding(
                        mod,
                        arg_node,
                        f"{var!r} is donated to {call.func.id!r} inside a "  # type: ignore[union-attr]
                        "loop but never rebound — the second iteration "
                        "passes an already-donated buffer",
                    )


def _sub_blocks(stmt: ast.stmt) -> List[Tuple[Sequence[ast.stmt], bool]]:
    """(block, is_loop_body) pairs for a compound statement's bodies."""
    out: List[Tuple[Sequence[ast.stmt], bool]] = []
    if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
        out.append((stmt.body, True))
        out.append((stmt.orelse, False))
    elif isinstance(stmt, ast.If):
        out.append((stmt.body, False))
        out.append((stmt.orelse, False))
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        out.append((stmt.body, False))
    elif isinstance(stmt, ast.Try):
        out.append((stmt.body, False))
        for handler in stmt.handlers:
            out.append((handler.body, False))
        out.append((stmt.orelse, False))
        out.append((stmt.finalbody, False))
    elif isinstance(stmt, ast.Match):
        for case in stmt.cases:
            out.append((case.body, False))
    return [(b, l) for b, l in out if b]


# ---------------------------------------------------------------------------
# AXIS-BINDING
# ---------------------------------------------------------------------------


class AxisBindingRule(Rule):
    """Collective axis names inside shard_map/pmap must be bound."""

    id = AXIS_BINDING
    summary = (
        "lax collective axis names inside shard_map/pmap bodies must be "
        "bound by the enclosing mesh/axis spec"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """For every shard_map/pmap-wrapped body whose binding fully
        resolves to literal axis names, flag collectives naming an axis
        outside that set. Unresolvable bindings (mesh built elsewhere,
        non-literal axis variables) stay silent — precision over recall."""
        module_consts = string_consts([mod.tree])
        for traced in traced_functions(mod.tree, mod.imports):
            if traced.kind not in ("shard_map", "pmap") or traced.binding is None:
                continue
            bound, complete = self._bound_axes(
                traced.binding, mod, module_consts, kind=traced.kind
            )
            if not complete:
                continue
            consts = dict(module_consts)
            consts.update(string_consts([traced.node]))
            for node in ast.walk(traced.node):
                if not isinstance(node, ast.Call):
                    continue
                name = last_component(qualname(node.func, mod.imports))
                if name not in COLLECTIVE_AXIS_ARG:
                    continue
                axis_expr = self._axis_expr(node, COLLECTIVE_AXIS_ARG[name])
                if axis_expr is None:
                    continue
                axis = resolve_str(axis_expr, consts)
                if axis is not None and axis not in bound:
                    bound_desc = ", ".join(sorted(bound)) or "<none>"
                    yield self.finding(
                        mod,
                        axis_expr,
                        f"collective {name!r} uses axis {axis!r} but the "
                        f"enclosing {traced.kind} binds only [{bound_desc}]",
                    )

    @staticmethod
    def _axis_expr(call: ast.Call, pos: int) -> Optional[ast.AST]:
        for kw in call.keywords:
            if kw.arg == "axis_name":
                return kw.value
        if len(call.args) > pos:
            return call.args[pos]
        return None

    def _bound_axes(
        self,
        binding: ast.Call,
        mod: ModuleInfo,
        consts: Dict[str, Optional[str]],
        *,
        kind: str,
    ) -> Tuple[Set[str], bool]:
        """Literal axis names bound by a shard_map/pmap wrapping call,
        plus whether the binding resolved completely."""
        bound: Set[str] = set()
        complete = True
        if kind == "pmap":
            for kw in binding.keywords:
                if kw.arg == "axis_name":
                    axis = resolve_str(kw.value, consts)
                    if axis is None:
                        return set(), False
                    bound.add(axis)
            return bound, True
        # shard_map: the bound axes are the MESH's axis names (specs name
        # a subset — a collective may legally reduce over a mesh axis the
        # specs never mention). Enforcement therefore requires the mesh to
        # resolve statically; spec tokens only ever add to the bound set.
        mesh_axes = None
        for kw in binding.keywords:
            if kw.arg == "mesh":
                mesh_axes = self._mesh_axes(kw.value, mod, consts)
        if mesh_axes is None:
            for arg in list(binding.args)[1:]:
                mesh_axes = self._mesh_axes(arg, mod, consts)
                if mesh_axes is not None:
                    break
        if mesh_axes is None:
            return set(), False
        bound |= mesh_axes
        for arg in list(binding.args)[1:] + [
            kw.value for kw in binding.keywords if kw.arg != "mesh"
        ]:
            self._spec_tokens(arg, mod, consts, bound)
        return bound, complete

    @staticmethod
    def _spec_tokens(
        expr: ast.AST,
        mod: ModuleInfo,
        consts: Dict[str, Optional[str]],
        bound: Set[str],
    ) -> bool:
        """Collect literal axis tokens from P(...)/PartitionSpec(...)
        expressions; returns False when any token fails to resolve."""
        ok = True
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                name = last_component(qualname(node.func, mod.imports))
                if name not in ("P", "PartitionSpec"):
                    continue
                for sub in list(node.args) + [k.value for k in node.keywords]:
                    for leaf in ast.walk(sub):
                        if isinstance(leaf, ast.Constant):
                            if isinstance(leaf.value, str):
                                bound.add(leaf.value)
                            # None literals are fine (replicated dims)
                        elif isinstance(leaf, ast.Name):
                            lit = consts.get(leaf.id)
                            if lit is None:
                                ok = False
                            else:
                                bound.add(lit)
        return ok

    @staticmethod
    def _mesh_axes(
        expr: ast.AST, mod: ModuleInfo, consts: Dict[str, Optional[str]]
    ) -> Optional[Set[str]]:
        """Axis names of the mesh expression when statically derivable."""

        def from_call(call: ast.Call) -> Optional[Set[str]]:
            name = last_component(qualname(call.func, mod.imports))
            if name in MESH_HELPER_AXES:
                return set(MESH_HELPER_AXES[name])
            if name in ("Mesh", "make_mesh", "create_device_mesh"):
                for sub in list(call.args) + [
                    k.value for k in call.keywords
                ]:
                    if isinstance(sub, (ast.Tuple, ast.List)) and sub.elts:
                        axes: Set[str] = set()
                        for elt in sub.elts:
                            lit = resolve_str(elt, consts)
                            if lit is None:
                                break
                            axes.add(lit)
                        else:
                            return axes
            return None

        if isinstance(expr, ast.Call):
            return from_call(expr)
        if isinstance(expr, ast.Name):
            # one-hop resolution: mesh = Mesh(..., ("nodes",)) earlier
            for node in ast.walk(mod.tree):
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == expr.id
                    and isinstance(node.value, ast.Call)
                ):
                    return from_call(node.value)
        return None


# ---------------------------------------------------------------------------
# HOST-SYNC
# ---------------------------------------------------------------------------

ROUND_LOOP_DIRS = ("engine/parameter_server/", "engine/peer_to_peer/")


class HostSyncRule(Rule):
    """No host-sync forcing (``.item()``, ``np.asarray``) on traced values."""

    id = HOST_SYNC
    summary = (
        "no .item()/float()/np.asarray on traced values inside jitted "
        "bodies, and no forced device syncs in the PS/gossip round loops"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Two contexts: (a) traced bodies — any ``.item()`` /
        ``block_until_ready`` / ``jax.device_get`` / numpy materialization
        / ``float(param)``; (b) loop bodies of async round drivers under
        ``engine/parameter_server`` and ``engine/peer_to_peer`` — sync
        forcers that stall the overlap pipeline."""
        emitted: Set[Tuple[int, int]] = set()
        for traced in traced_functions(mod.tree, mod.imports):
            params = enclosing_param_names(traced.node)
            for inner in ast.walk(traced.node):
                if isinstance(inner, (*FunctionNode, ast.Lambda)):
                    params = params | enclosing_param_names(inner)
            params -= traced.static_params
            for node in ast.walk(traced.node):
                f = self._sync_finding(mod, node, params, "a traced body")
                if f is not None:
                    key = (f.line, f.col)
                    if key not in emitted:
                        emitted.add(key)
                        yield f
        rel = mod.relpath.replace("\\", "/")
        if any(d in rel for d in ROUND_LOOP_DIRS):
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.AsyncFunctionDef):
                    continue
                for loop in ast.walk(node):
                    if not isinstance(
                        loop, (ast.For, ast.While, ast.AsyncFor)
                    ):
                        continue
                    for sub in ast.walk(loop):
                        f = self._sync_finding(
                            mod, sub, set(), "the async round loop"
                        )
                        if f is not None:
                            key = (f.line, f.col)
                            if key not in emitted:
                                emitted.add(key)
                                yield f

    def _sync_finding(
        self,
        mod: ModuleInfo,
        node: ast.AST,
        params: Set[str],
        where: str,
    ) -> Optional[Finding]:
        if not isinstance(node, ast.Call):
            return None
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args:
                return self.finding(
                    mod,
                    node,
                    f".item() in {where} forces a host sync "
                    "(TracerConversionError under jit; a pipeline stall in "
                    "the round loop) — keep values on device or hoist to "
                    "the host boundary",
                )
            if node.func.attr == "block_until_ready":
                return self.finding(
                    mod,
                    node,
                    f"block_until_ready() in {where} forces a device sync",
                )
        fq = qualname(node.func, mod.imports)
        if fq == "jax.device_get":
            return self.finding(
                mod, node, f"jax.device_get in {where} forces a host transfer"
            )
        if (
            fq is not None
            and fq.startswith("numpy.")
            and last_component(fq) in ("asarray", "array")
        ):
            return self.finding(
                mod,
                node,
                f"{last_component(fq)} (numpy) in {where} materializes a "
                "traced value on host — use jnp, or move this out of the "
                "traced/round-loop region",
            )
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in ("float", "int", "bool")
            and params
            and len(node.args) == 1
            and isinstance(node.args[0], ast.Name)
            and node.args[0].id in params
        ):
            return self.finding(
                mod,
                node,
                f"{node.func.id}() on traced argument "
                f"{node.args[0].id!r} in {where} — python scalar "
                "conversion fails under trace",
            )
        return None


# ---------------------------------------------------------------------------
# ASYNC-BLOCKING
# ---------------------------------------------------------------------------


class AsyncBlockingRule(Rule):
    """No blocking calls directly inside ``async def`` bodies."""

    id = ASYNC_BLOCKING
    summary = (
        "no time.sleep / sync socket ops / blocking file-process I/O "
        "directly inside async def (actor/node fabric shares one loop)"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Walk each ``async def`` whose *nearest* function scope is that
        async def (nested sync defs are executor targets and exempt),
        flagging known blocking callables that are not awaited."""
        yield from self._visit(mod, mod.tree.body)

    def _visit(
        self, mod: ModuleInfo, body: Sequence[ast.stmt]
    ) -> Iterator[Finding]:
        for stmt in body:
            if isinstance(stmt, ast.AsyncFunctionDef):
                yield from self._scan_async_body(mod, stmt)
                yield from self._visit(mod, stmt.body)
            elif isinstance(stmt, (ast.FunctionDef, ast.ClassDef)):
                yield from self._visit(mod, stmt.body)
            else:
                for sub_block, _ in _sub_blocks(stmt):
                    yield from self._visit(mod, sub_block)

    def _scan_async_body(
        self, mod: ModuleInfo, fn: ast.AsyncFunctionDef
    ) -> Iterator[Finding]:
        awaited: Set[int] = set()
        skip: Set[int] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Await):
                for sub in ast.walk(node.value):
                    awaited.add(id(sub))
            # nested function bodies (sync defs = executor targets,
            # nested async defs are scanned on their own) are exempt
            if isinstance(node, (*FunctionNode, ast.Lambda)) and node is not fn:
                for sub in ast.walk(node):
                    skip.add(id(sub))
        for node in ast.walk(fn):
            if (
                not isinstance(node, ast.Call)
                or id(node) in skip
                or id(node) in awaited
            ):
                continue
            msg = self._blocking_reason(node, mod)
            if msg is not None:
                yield self.finding(
                    mod,
                    node,
                    f"{msg} inside async def {fn.name!r} stalls the shared "
                    "event loop — use the asyncio equivalent or "
                    "loop.run_in_executor",
                )

    @staticmethod
    def _blocking_reason(node: ast.Call, mod: ModuleInfo) -> Optional[str]:
        fq = qualname(node.func, mod.imports)
        if fq in BLOCKING_QUALNAMES:
            return f"blocking call {fq}"
        if isinstance(node.func, ast.Name) and node.func.id == "open":
            if "open" not in mod.imports:
                return "blocking file open()"
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in BLOCKING_SOCKET_ATTRS:
                return f"sync socket .{attr}()"
            if attr == "join":
                recv = node.func.value
                tail = ""
                if isinstance(recv, ast.Attribute):
                    tail = recv.attr
                elif isinstance(recv, ast.Name):
                    tail = recv.id
                if any(h in tail.lower() for h in JOIN_RECEIVER_HINTS):
                    return f"blocking {tail}.join()"
        return None


# ---------------------------------------------------------------------------
# PYTREE-REG
# ---------------------------------------------------------------------------


def _scope_nodes_ordered(scope: ast.AST) -> List[ast.AST]:
    """Nodes belonging to one scope (nested function/lambda subtrees
    excluded), sorted by source position so assignment→use order holds."""
    skip: Set[int] = set()
    for node in ast.walk(scope):
        if node is not scope and isinstance(node, (*FunctionNode, ast.Lambda)):
            for sub in ast.walk(node):
                skip.add(id(sub))
    nodes = [
        n
        for n in ast.walk(scope)
        if id(n) not in skip and hasattr(n, "lineno")
    ]
    nodes.sort(key=lambda n: (n.lineno, n.col_offset))
    return nodes


class PytreeRegRule(Rule):
    """Classes flowed through collectives must be registered pytrees."""

    id = PYTREE_REG
    summary = (
        "an instance of a scanned-tree class passed to a collective must "
        "be a registered pytree (register_pytree_node[_class], "
        "flax.struct, or NamedTuple)"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Flag collective payloads that are (or resolve one assignment
        back to) constructor calls of scanned-tree classes lacking pytree
        registration."""
        emitted: Set[Tuple[int, int]] = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, (*FunctionNode, ast.Module)):
                continue
            scope = node
            # latest constructor assignment per name, in textual order,
            # over this scope's OWN nodes (nested defs are their own
            # scopes — mixing their locals in would invent dataflow)
            ctor_of: Dict[str, str] = {}
            for sub in _scope_nodes_ordered(scope):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    tgt = sub.targets[0]
                    if isinstance(tgt, ast.Name):
                        cls = self._ctor_class(sub.value, mod, ctx)
                        if cls is not None:
                            ctor_of[tgt.id] = cls
                        elif tgt.id in ctor_of:
                            del ctor_of[tgt.id]
                if not isinstance(sub, ast.Call):
                    continue
                name = last_component(qualname(sub.func, mod.imports))
                if name not in COLLECTIVE_AXIS_ARG or not sub.args:
                    continue
                payload = sub.args[0]
                cls = self._ctor_class(payload, mod, ctx)
                if cls is None and isinstance(payload, ast.Name):
                    cls = ctor_of.get(payload.id)
                key = (payload.lineno, payload.col_offset)
                if (
                    cls is not None
                    and cls not in ctx.registered_pytrees
                    and key not in emitted
                ):
                    emitted.add(key)
                    yield self.finding(
                        mod,
                        payload,
                        f"{cls!r} flows through collective {name!r} but is "
                        "not a registered pytree — decorate it with "
                        "@jax.tree_util.register_pytree_node_class (see "
                        "QuantizedBlocks) or register it explicitly",
                    )

    @staticmethod
    def _ctor_class(
        expr: ast.AST, mod: ModuleInfo, ctx: ScanContext
    ) -> Optional[str]:
        if isinstance(expr, ast.Call):
            name = last_component(qualname(expr.func, mod.imports))
            if name in ctx.class_names:
                return name
        return None


# ---------------------------------------------------------------------------
# THREAD-SHARED
# ---------------------------------------------------------------------------

#: receiver-name hints that make a ``with`` context manager count as a
#: lock guard (identity = the full dotted receiver text)
LOCK_NAME_HINTS = ("lock", "mutex", "sem")

#: methods that run before the object is published to other contexts
CONSTRUCTOR_METHODS = {"__init__", "__new__", "__post_init__"}


def _self_root_attr(expr: ast.AST) -> Optional[str]:
    """The attribute directly on ``self`` at the root of a store target
    (``self.a`` / ``self.a[k]`` / ``self.a.b`` all root at ``a``) —
    container/field mutation counts as writing the root attribute."""
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
    ):
        return expr.attr
    if isinstance(expr, (ast.Attribute, ast.Subscript)):
        return _self_root_attr(expr.value)
    return None


def _lock_guard_name(expr: ast.AST) -> Optional[str]:
    """Guard identity of a ``with`` item when it looks like a lock."""
    text = receiver_text(expr)
    if any(h in text for h in LOCK_NAME_HINTS):
        return text
    return None


class ThreadSharedRule(Rule):
    """Cross-context ``self.*`` writes need a common lock guard."""

    id = THREAD_SHARED
    summary = (
        "a self.* attribute written from two execution contexts (event "
        "loop / reader thread / executor) needs a common lock guard"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Group ``self.*`` stores per class/attribute with the writing
        method's context labels (per the :mod:`.contexts` classifier) and
        the lock guards lexically held at the store. Flag attributes
        written from ≥2 distinct concurrent contexts when no single lock
        covers every write."""
        cmap = ctx.contexts.get(mod.relpath)
        if cmap is None:
            return
        # class → attr → [(anchor, labels, guards)]
        writes: Dict[str, Dict[str, List[Tuple[ast.AST, Set[str], Set[str]]]]]
        writes = {}
        for info in cmap.fns.values():
            if info.class_name is None or info.name in CONSTRUCTOR_METHODS:
                continue
            labels = info.labels & CONCURRENT_LABELS
            if not labels:
                continue
            for attr, anchor, guards in self._stores(info.node):
                writes.setdefault(info.class_name, {}).setdefault(
                    attr, []
                ).append((anchor, labels, guards))
        for cls in sorted(writes):
            for attr, sites in sorted(writes[cls].items()):
                contexts: Set[str] = set()
                for _, labels, _ in sites:
                    contexts |= labels
                if len(contexts) < 2:
                    continue
                common = set(sites[0][2])
                for _, _, guards in sites[1:]:
                    common &= guards
                if common:
                    continue
                anchor = min(
                    (a for a, _, _ in sites),
                    key=lambda n: (n.lineno, n.col_offset),
                )
                ctx_desc = "/".join(sorted(contexts))
                yield self.finding(
                    mod,
                    anchor,
                    f"{cls}.{attr} is written from {ctx_desc} contexts "
                    "with no common lock — serialize every write under "
                    "one `with self.<lock>:`, or confine mutation to a "
                    "single context via an epoch-stamped handoff (the "
                    "PR 19 staging split)",
                )

    @staticmethod
    def _stores(
        fn: ast.AST,
    ) -> Iterator[Tuple[str, ast.AST, Set[str]]]:
        """``(attr, anchor, lock-guards-held)`` for every ``self.*``
        store lexically in ``fn``'s own body (nested defs are their own
        functions and classified separately)."""

        def targets_of(stmt: ast.stmt) -> List[ast.AST]:
            if isinstance(stmt, ast.Assign):
                return list(stmt.targets)
            if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                return [stmt.target]
            if isinstance(stmt, ast.Delete):
                return list(stmt.targets)
            return []

        def scan(
            stmts: Sequence[ast.stmt], guards: Set[str]
        ) -> Iterator[Tuple[str, ast.AST, Set[str]]]:
            for stmt in stmts:
                if isinstance(stmt, FunctionNode):
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    held = set(guards)
                    for item in stmt.items:
                        g = _lock_guard_name(item.context_expr)
                        if g is not None:
                            held.add(g)
                    yield from scan(stmt.body, held)
                    continue
                for tgt in targets_of(stmt):
                    attr = _self_root_attr(tgt)
                    if attr is not None:
                        yield attr, tgt, set(guards)
                for sub, _ in _sub_blocks(stmt):
                    yield from scan(sub, guards)

        yield from scan(getattr(fn, "body", []), set())


# ---------------------------------------------------------------------------
# ACK-ORDER
# ---------------------------------------------------------------------------

#: writer-ish method names that emit an ack/reply toward a client
SEND_ATTRS = {"write", "sendall", "send", "send_bytes"}
SEND_RECEIVER_HINTS = (
    "writer", "sock", "conn", "transport", "stream", "wfile", "chan",
)
#: durability-object hints: appends on these are WAL records
WAL_RECEIVER_HINTS = ("durability", "wal", "journal")


def _ackish_name(name: str) -> bool:
    """Callable names that mean "emit the ack" (kept to word matches so
    ``pack``/``callback``/``track`` never count)."""
    low = name.lower()
    return (
        low == "ack"
        or low.endswith("_ack")
        or low.startswith("ack_")
        or "send_ack" in low
    )


class AckOrderRule(Rule):
    """The WAL append must dominate the ack on every path."""

    id = ACK_ORDER
    summary = (
        "in a function that both appends to a durability/WAL object and "
        "sends on a writer, the append must come before the send on "
        "every path — an ack is a durable promise"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Flow-sensitive single pass per function: track "a send has
        happened on this path" through branches (union on merge, return/
        raise kills the path) and flag any WAL append reached with a
        send already behind it. Runs only on functions containing both
        event kinds — everything else is out of contract."""
        for node in ast.walk(mod.tree):
            if isinstance(node, FunctionNode):
                yield from self._check_fn(mod, node)

    def _check_fn(
        self, mod: ModuleInfo, fn: ast.AST
    ) -> Iterator[Finding]:
        kinds = {
            self._event_kind(n, mod)
            for n in self._own_nodes(fn)
            if isinstance(n, ast.Call)
        }
        if not ({"send", "append"} <= kinds):
            return
        out: List[Finding] = []
        self._flow(mod, fn.body, False, out)
        yield from out

    @staticmethod
    def _own_nodes(fn: ast.AST) -> Iterator[ast.AST]:
        """Nodes in ``fn``'s own scope (nested def subtrees excluded)."""
        skip: Set[int] = set()
        for node in ast.walk(fn):
            if node is not fn and isinstance(node, (*FunctionNode, ast.Lambda)):
                for sub in ast.walk(node):
                    skip.add(id(sub))
        for node in ast.walk(fn):
            if id(node) not in skip:
                yield node

    def _event_kind(self, call: ast.Call, mod: ModuleInfo) -> Optional[str]:
        func = call.func
        if isinstance(func, ast.Attribute):
            recv = receiver_text(func.value)
            if func.attr in SEND_ATTRS and any(
                h in recv for h in SEND_RECEIVER_HINTS
            ):
                return "send"
            if (
                func.attr.startswith("record_") or func.attr == "append"
            ) and any(h in recv for h in WAL_RECEIVER_HINTS):
                return "append"
            if _ackish_name(func.attr):
                return "send"
        elif isinstance(func, ast.Name) and _ackish_name(func.id):
            return "send"
        return None

    def _header_events(
        self,
        mod: ModuleInfo,
        stmt: ast.stmt,
        sent: bool,
        out: List[Finding],
    ) -> bool:
        """Process the events of one statement's own expressions (its
        sub-blocks and nested defs excluded), in source order."""
        skip: Set[int] = set()
        for blk, _ in _sub_blocks(stmt):
            for s in blk:
                for n in ast.walk(s):
                    skip.add(id(n))
        for n in ast.walk(stmt):
            if isinstance(n, (*FunctionNode, ast.Lambda)):
                for sub in ast.walk(n):
                    skip.add(id(sub))
        events: List[Tuple[int, int, str, ast.Call]] = []
        for n in ast.walk(stmt):
            if id(n) in skip or not isinstance(n, ast.Call):
                continue
            kind = self._event_kind(n, mod)
            if kind is not None:
                events.append((n.lineno, n.col_offset, kind, n))
        for _, _, kind, n in sorted(events, key=lambda e: (e[0], e[1])):
            if kind == "send":
                sent = True
            elif sent:
                out.append(
                    self.finding(
                        mod,
                        n,
                        "durable append reached with an ack/send already "
                        "emitted on this path — the WAL append must "
                        "dominate the ack (a crash between them replays "
                        "an un-promised submission: the PR 9 double-fold "
                        "incident)",
                    )
                )
        return sent

    def _flow(
        self,
        mod: ModuleInfo,
        stmts: Sequence[ast.stmt],
        sent: bool,
        out: List[Finding],
    ) -> Tuple[bool, bool]:
        """Returns ``(sent_at_exit, path_alive)``."""
        alive = True
        for stmt in stmts:
            if isinstance(stmt, FunctionNode):
                continue
            sent = self._header_events(mod, stmt, sent, out)
            if isinstance(
                stmt, (ast.Return, ast.Raise, ast.Break, ast.Continue)
            ):
                return sent, False
            if isinstance(stmt, ast.If):
                s_a, a_a = self._flow(mod, stmt.body, sent, out)
                s_b, a_b = self._flow(mod, stmt.orelse, sent, out)
                alive = a_a or a_b
                sent = (a_a and s_a) or (a_b and s_b)
                if not alive:
                    return sent, False
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                s_body, _ = self._flow(mod, stmt.body, sent, out)
                # zero-iteration exit is always possible; break/return
                # subtleties are deliberately ignored (one pass, no
                # loop-carry — precision over completeness)
                sent = sent or s_body
                s_else, a_else = self._flow(mod, stmt.orelse, sent, out)
                if stmt.orelse and a_else:
                    sent = s_else
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                sent, alive = self._flow(mod, stmt.body, sent, out)
                if not alive:
                    return sent, False
            elif isinstance(stmt, ast.Try):
                s_body, a_body = self._flow(mod, stmt.body, sent, out)
                exits: List[bool] = []
                if a_body:
                    if stmt.orelse:
                        s_else, a_else = self._flow(
                            mod, stmt.orelse, s_body, out
                        )
                        if a_else:
                            exits.append(s_else)
                    else:
                        exits.append(s_body)
                for handler in stmt.handlers:
                    # an exception can fire before any send in the body:
                    # handlers start from the entry state
                    s_h, a_h = self._flow(mod, handler.body, sent, out)
                    if a_h:
                        exits.append(s_h)
                alive = bool(exits)
                sent = any(exits)
                s_fin, a_fin = self._flow(mod, stmt.finalbody, sent, out)
                if stmt.finalbody:
                    sent, alive = s_fin, alive and a_fin
                if not alive:
                    return sent, False
            elif isinstance(stmt, ast.Match):
                exits = []
                for case in stmt.cases:
                    s_c, a_c = self._flow(mod, case.body, sent, out)
                    if a_c:
                        exits.append(s_c)
                # no exhaustiveness check: fall-through keeps entry state
                sent = sent or any(exits)
        return sent, alive


# ---------------------------------------------------------------------------
# PARITY-PURITY
# ---------------------------------------------------------------------------

#: functions on the digest-parity contract by exact name
PARITY_ROOT_NAMES = {"combine_partials", "gram_block"}

#: nondeterminism sources by qualified-name prefix
IMPURE_CALL_PREFIXES = (
    "time.",
    "random.",
    "numpy.random.",
    "secrets.",
    "uuid.",
)
IMPURE_CALL_EXACT = {"os.urandom"}


def _is_parity_root(name: str) -> bool:
    """Whether a function name puts it on the digest-parity contract."""
    return (
        name in PARITY_ROOT_NAMES
        or "digest" in name
        or name.startswith("fold_merge")
    )


class ParityPurityRule(Rule):
    """No clocks/RNG/set-iteration in digest-parity code."""

    id = PARITY_PURITY
    summary = (
        "functions reachable from the digest-parity set (fold_merge_*, "
        "combine_partials, gram_block, *digest*) must not call clocks/"
        "RNG or iterate bare sets into folded bytes"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Close the module-local call graph over the parity roots, then
        flag nondeterminism inside every reachable function: clock/RNG
        calls by qualified name, and ``for``/comprehension iteration
        over bare set expressions (``sorted(...)`` launders the order)."""
        cmap = ctx.contexts.get(mod.relpath)
        if cmap is None:
            return
        reach: Dict[int, str] = {}
        queue: List[FnInfo] = []
        for info in cmap.fns.values():
            if _is_parity_root(info.name):
                reach[id(info.node)] = info.name
                queue.append(info)
        while queue:
            info = queue.pop()
            for cid in info.callees:
                if cid not in reach:
                    reach[cid] = reach[id(info.node)]
                    queue.append(cmap.fns[cid])
        for info in sorted(
            cmap.fns.values(), key=lambda i: getattr(i.node, "lineno", 0)
        ):
            root = reach.get(id(info.node))
            if root is None:
                continue
            yield from self._scan_fn(mod, cmap, info, root)

    def _scan_fn(
        self, mod: ModuleInfo, cmap: ContextMap, info: FnInfo, root: str
    ) -> Iterator[Finding]:
        via = "" if root == info.name else f" (parity-reachable from {root!r})"
        for node in ast.walk(info.node):
            if node is not info.node and cmap.owner.get(id(node)) is not info:
                continue  # nested defs are classified on their own
            if isinstance(node, ast.Call):
                fq = qualname(node.func, mod.imports)
                if fq is not None and (
                    fq in IMPURE_CALL_EXACT
                    or fq.startswith(IMPURE_CALL_PREFIXES)
                ):
                    yield self.finding(
                        mod,
                        node,
                        f"{fq} inside {info.name!r}{via} — digest-parity "
                        "code must be bit-deterministic; hoist clocks/RNG "
                        "to the caller (the PR 7 digest-drift class)",
                    )
            iters: List[ast.AST] = []
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters.append(node.iter)
            elif isinstance(
                node,
                (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp),
            ):
                iters.extend(gen.iter for gen in node.generators)
            for it in iters:
                if self._bare_set(it, mod):
                    yield self.finding(
                        mod,
                        it,
                        f"iterating a bare set inside {info.name!r}{via} — "
                        "set order is nondeterministic across processes; "
                        "wrap it in sorted(...) before it reaches folded "
                        "bytes",
                    )

    @staticmethod
    def _bare_set(expr: ast.AST, mod: ModuleInfo) -> bool:
        if isinstance(expr, ast.Set) or isinstance(expr, ast.SetComp):
            return True
        if isinstance(expr, ast.Call):
            return last_component(qualname(expr.func, mod.imports)) in (
                "set",
                "frozenset",
            )
        return False


# ---------------------------------------------------------------------------
# METRIC-CONTRACT
# ---------------------------------------------------------------------------

#: MetricsRegistry factory method names → instrument type
METRIC_FACTORY_ATTRS = {"counter", "gauge", "histogram"}
#: receiver hints for registry objects (``reg``, ``registry()``,
#: ``self._metrics``) — an unrelated ``.counter()`` never matches
METRIC_RECEIVER_HINTS = ("reg", "metric")
#: tracing entry points that take a span/instant label
SPAN_CALL_NAMES = {"span", "device_span", "begin_span", "instant"}
SPAN_RECEIVER_HINTS = ("tracing", "tracer", "trace")
#: in-jit names: the scope label of ``jax.named_scope`` and the kernel
#: name of ``pl.pallas_call(name=...)`` (both reach the compiled text)
SCOPE_CALL_NAME = "named_scope"
KERNEL_CALL_NAME = "pallas_call"


class MetricContractRule(Rule):
    """Metric and span names must match the observability catalog."""

    id = METRIC_CONTRACT
    summary = (
        "every Counter/Gauge/Histogram registration, span() label, "
        "named_scope label and pallas_call name must appear, with a "
        "matching type, in byzpy_tpu/observability/catalog.py (and the "
        "docs tables)"
    )

    def check(self, mod: ModuleInfo, ctx: ScanContext) -> Iterator[Finding]:
        """Check the literal first argument of registry factory calls,
        tracing span/instant calls and ``named_scope`` calls, and the
        ``name=`` of every ``pallas_call``, against the catalog. Computed
        metric, span and kernel names stay silent; a computed scope label
        has to start with the literal of a catalogued family — a new
        dynamic family must be catalogued as a prefix."""
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            called = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if called == KERNEL_CALL_NAME:
                yield from self._check_kernel(mod, node)
                continue
            name = self._literal_name(node)
            if called == SCOPE_CALL_NAME:
                yield from self._check_scope(mod, node, name)
                continue
            if name is None:
                continue
            if (
                isinstance(func, ast.Attribute)
                and func.attr in METRIC_FACTORY_ATTRS
            ):
                recv = receiver_text(func.value)
                if any(h in recv for h in METRIC_RECEIVER_HINTS):
                    yield from self._check_metric(mod, node, func.attr, name)
                continue
            fq = qualname(func, mod.imports) or ""
            last = last_component(fq) or (
                func.attr if isinstance(func, ast.Attribute) else ""
            )
            if last not in SPAN_CALL_NAMES:
                continue
            is_tracing = any(
                fq.endswith("tracing." + s) for s in SPAN_CALL_NAMES
            ) or (
                isinstance(func, ast.Attribute)
                and any(
                    h in receiver_text(func.value)
                    for h in SPAN_RECEIVER_HINTS
                )
            )
            if is_tracing:
                yield from self._check_span(mod, node, name)

    @staticmethod
    def _literal_name(call: ast.Call) -> Optional[str]:
        expr: Optional[ast.AST] = call.args[0] if call.args else None
        if expr is None:
            for kw in call.keywords:
                if kw.arg == "name":
                    expr = kw.value
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        return None

    def _check_metric(
        self, mod: ModuleInfo, node: ast.Call, kind: str, name: str
    ) -> Iterator[Finding]:
        want = catalog.METRICS.get(name)
        if want is None:
            if name.startswith(catalog.METRIC_PREFIXES):
                return
            yield self.finding(
                mod,
                node,
                f"metric {name!r} is not in the observability catalog — "
                "add it to byzpy_tpu/observability/catalog.py and the "
                "docs/observability.md table",
            )
        elif want != kind:
            yield self.finding(
                mod,
                node,
                f"metric {name!r} registered as a {kind} but catalogued "
                f"as a {want} — one name, one type",
            )

    def _check_span(
        self, mod: ModuleInfo, node: ast.Call, name: str
    ) -> Iterator[Finding]:
        if name in catalog.SPANS or name.startswith(catalog.SPAN_PREFIXES):
            return
        yield self.finding(
            mod,
            node,
            f"span label {name!r} is not in the observability catalog — "
            "add it to byzpy_tpu/observability/catalog.py and the "
            "docs/observability.md span catalog",
        )

    @staticmethod
    def _literal_head(call: ast.Call) -> str:
        """The literal a computed label starts with (``"segment." + key``,
        ``f"segment.{key}"``); empty where it starts with none."""
        expr: Optional[ast.AST] = call.args[0] if call.args else None
        while isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            expr = expr.left
        if isinstance(expr, ast.JoinedStr) and expr.values:
            expr = expr.values[0]
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        return ""

    def _check_scope(
        self, mod: ModuleInfo, node: ast.Call, name: Optional[str]
    ) -> Iterator[Finding]:
        """A literal label is catalogued; a computed one starts with the
        literal of a catalogued family (``SCOPE_PREFIXES``), the one way a
        reader of the compiled text can find labels it cannot list."""
        if name is None:
            head = self._literal_head(node)
            if not head.startswith(catalog.SCOPE_PREFIXES):
                yield self.finding(
                    mod,
                    node,
                    f"computed named_scope label (it starts with {head!r}) is "
                    "under no catalogued family — start it with a literal "
                    "prefix listed in SCOPE_PREFIXES in "
                    "byzpy_tpu/observability/catalog.py",
                )
            return
        if name in catalog.SCOPES or name.startswith(catalog.SCOPE_PREFIXES):
            return
        yield self.finding(
            mod,
            node,
            f"named_scope label {name!r} is not in the observability "
            "catalog — add it to SCOPES in "
            "byzpy_tpu/observability/catalog.py and the "
            "docs/observability.md scope table",
        )

    def _check_kernel(
        self, mod: ModuleInfo, node: ast.Call
    ) -> Iterator[Finding]:
        """A ``pallas_call`` names its kernel with a catalogued literal:
        without ``name=`` the custom call carries a name the compiler
        made, which no trace reader can hold on to."""
        given = [kw.value for kw in node.keywords if kw.arg == "name"]
        if not given:
            yield self.finding(
                mod,
                node,
                "pallas_call without name= — give the kernel a literal "
                "name from KERNELS in byzpy_tpu/observability/catalog.py",
            )
            return
        expr = given[0]
        if not (isinstance(expr, ast.Constant) and isinstance(expr.value, str)):
            return  # computed names stay silent, as computed labels do
        if expr.value not in catalog.KERNELS:
            yield self.finding(
                mod,
                node,
                f"kernel name {expr.value!r} is not in the observability "
                "catalog — add it to KERNELS in "
                "byzpy_tpu/observability/catalog.py and the "
                "docs/observability.md kernel table",
            )


#: the shipped rule set, in reporting order
ALL_RULES: Tuple[Rule, ...] = (
    TraceDispatchRule(),
    DonationRule(),
    AxisBindingRule(),
    HostSyncRule(),
    AsyncBlockingRule(),
    PytreeRegRule(),
    ThreadSharedRule(),
    AckOrderRule(),
    ParityPurityRule(),
    MetricContractRule(),
)

__all__ = [
    "ACK_ORDER",
    "ALL_RULES",
    "ASYNC_BLOCKING",
    "AXIS_BINDING",
    "AckOrderRule",
    "AsyncBlockingRule",
    "AxisBindingRule",
    "COLLECTIVE_AXIS_ARG",
    "DONATION",
    "DonationRule",
    "HOST_SYNC",
    "HostSyncRule",
    "METRIC_CONTRACT",
    "MetricContractRule",
    "PARITY_PURITY",
    "PYTREE_REG",
    "ParityPurityRule",
    "PytreeRegRule",
    "Rule",
    "ScanContext",
    "THREAD_SHARED",
    "TRACE_DISPATCH",
    "ThreadSharedRule",
    "TraceDispatchRule",
]
