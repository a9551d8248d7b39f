"""The fused training step names its own work, and the names are held.

``round.*`` scopes partition ``build_ps_train_step.train_step`` (every op
of the program lies in exactly one innermost scope), every
``pl.pallas_call`` of the package passes a catalogued literal ``name=``,
and byzlint's ``METRIC-CONTRACT`` holds both to
``observability/catalog.py``. The scopes are read off the compiled
program's text, where ``chipbench/scope_join.py`` finds them too: a TPU
trace event carries none.
"""

from __future__ import annotations

import ast
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from byzpy_tpu.analysis import scan_paths
from byzpy_tpu.analysis.rules import METRIC_CONTRACT
from byzpy_tpu.models.nets import mnist_mlp
from byzpy_tpu.observability import catalog
from byzpy_tpu.ops import attack_ops, preagg, robust
from byzpy_tpu.parallel.mesh import node_mesh, replicated
from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "analysis")
ROUND_SCOPE = re.compile(r"round\.[a-z_]+")
KERNEL_FILES = ("byzpy_tpu/ops/pallas_kernels.py", "byzpy_tpu/ops/pallas_attention.py",
                "byzpy_tpu/ops/pallas_rows_to_tokens.py", "byzpy_tpu/parallel/quantization.py")


def _sign_flip(honest, key):
    return attack_ops.sign_flip(jnp.mean(honest, axis=0))


def _toy_step(mesh=None, **kwargs):
    """A jitted toy round (the MNIST MLP, 8 workers, 2 byzantine) with
    the arguments of one call."""
    bundle = mnist_mlp(0, hidden=16)
    cfg = PSStepConfig(n_nodes=8, n_byzantine=2, learning_rate=0.05, momentum=0.9)
    step, opt_state = jit_ps_train_step(
        bundle, partial(robust.trimmed_mean, f=2), cfg, attack=_sign_flip, mesh=mesh, **kwargs
    )
    params = bundle.params if mesh is None else jax.device_put(bundle.params, replicated(mesh))
    xs = jnp.zeros((8, 4, 28, 28, 1), jnp.float32)
    ys = jnp.zeros((8, 4), jnp.int32)
    return step, (params, opt_state, xs, ys, jax.random.PRNGKey(0))


def _op_names(step, args):
    """``[(instruction line, op_name)]`` of the compiled step's text."""
    text = step.lower(*args).compile().as_text()
    found = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            found.append((line.strip(), m.group(1)))
    return found


@pytest.fixture(scope="module")
def one_device_names():
    return _op_names(*_toy_step())


@pytest.fixture(scope="module")
def mesh_names():
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    return _op_names(*_toy_step(node_mesh(4), sharded_update="on", comm_precision="bf16"))


def _outside_every_scope(names):
    """Lines of the program's own ops (their ``op_name`` starts at the
    jitted function; the compiler's regions and parameter copies are
    named ``add``, ``lt_to``, ``xs``) that lie in no ``round.*`` scope."""
    return [line for line, op_name in names
            if op_name.startswith("jit(train_step)") and not ROUND_SCOPE.search(op_name)]


@pytest.mark.parametrize(
    "scope", ["round.fwdbwd", "round.build_matrix", "round.aggregate", "round.update"])
def test_compiled_step_holds_each_round_scope(one_device_names, scope):
    assert scope in catalog.SCOPES
    assert any(f"/{scope}/" in op_name + "/" for _, op_name in one_device_names)


def test_no_op_of_the_step_lies_outside_every_round_scope(one_device_names):
    assert any(op.startswith("jit(train_step)") for _, op in one_device_names)
    assert _outside_every_scope(one_device_names) == []
    used = {s for _, op in one_device_names for s in ROUND_SCOPE.findall(op)}
    assert used <= set(catalog.SCOPES)


@pytest.mark.parametrize("scope", ["round.transpose", "round.param_gather"])
def test_mesh_step_holds_the_collectives_scopes(mesh_names, scope):
    assert any(f"/{scope}/" in op_name + "/" for _, op_name in mesh_names)
    assert _outside_every_scope(mesh_names) == []


def test_param_gather_is_nested_in_update_and_carries_the_all_gather(mesh_names):
    gathers = [(line, op) for line, op in mesh_names if " all-gather(" in line]
    assert gathers
    assert all("/round.update/round.param_gather/" in op for _, op in gathers)


def test_compressed_transpose_carries_the_all_to_all(mesh_names):
    hops = [op for line, op in mesh_names if " all-to-all(" in line]
    assert hops and all("/round.transpose/" in op for op in hops)


def test_pre_aggregate_scope_appears_only_where_one_is_given(one_device_names):
    assert not any("round.pre_aggregate" in op for _, op in one_device_names)
    step, args = _toy_step(pre_aggregate=partial(preagg.clip_rows, threshold=1.0))
    names = _op_names(step, args)
    assert any("/round.pre_aggregate/" in op for _, op in names)
    assert _outside_every_scope(names) == []


def test_two_steps_compile_the_scoped_step_once():
    step, (params, opt_state, xs, ys, key) = _toy_step()
    params, opt_state, _ = step(params, opt_state, xs, ys, key)
    params, opt_state, metrics = step(params, opt_state, xs, ys, key)
    assert step._cache_size() == 1
    assert bool(jnp.isfinite(metrics["honest_loss"]))


# -- kernel names ------------------------------------------------------------


def _pallas_call_sites():
    """``(file, enclosing function, lineno, name= literal or None)`` of
    every ``pallas_call`` under ``byzpy_tpu/``, by AST."""
    sites = []
    for base, _dirs, files in os.walk(os.path.join(ROOT, "byzpy_tpu")):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(base, fname)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
            for fn in ast.walk(tree):
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                for node in ast.walk(fn):
                    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                            and node.func.attr == "pallas_call"):
                        given = [kw.value for kw in node.keywords if kw.arg == "name"]
                        literal = (given[0].value if given and isinstance(given[0], ast.Constant)
                                   else None)
                        sites.append((os.path.relpath(path, ROOT), fn.name, node.lineno, literal))
    return sites


PALLAS_SITES = _pallas_call_sites()


def test_the_kernel_files_are_where_the_pallas_calls_are():
    assert len(PALLAS_SITES) == len(catalog.KERNELS)
    assert {site[0] for site in PALLAS_SITES} == set(KERNEL_FILES)


@pytest.mark.parametrize("site", PALLAS_SITES, ids=[f"{s[0].rsplit('/', 1)[-1]}:{s[1]}"
                                                    for s in PALLAS_SITES])
def test_every_pallas_call_names_its_kernel_after_its_function(site):
    _path, function, _lineno, literal = site
    assert literal is not None, "pallas_call without a literal name="
    assert literal in catalog.KERNELS
    assert function == f"_{literal}_call"


def test_a_kernels_name_reaches_the_lowered_text():
    """``name=`` is what the custom call is called in the program (here
    the interpreter's lowering; on a TPU the instruction and a segment of
    its ``op_name``: PERF.md section 5)."""
    from byzpy_tpu.ops import pallas_kernels as pk

    def agg(x):
        with jax.named_scope("round.aggregate"):
            return pk.sorted_reduce_stream_pallas(x[None], mode="trimmed", f=2, interpret=True)[0]

    text = jax.jit(agg).lower(jnp.zeros((8, 256), jnp.float32)).as_text(debug_info=True)
    assert "sorted_reduce_stream" in text.replace("_sorted_reduce_stream_call", "")


# -- byzlint holds both -------------------------------------------------------


def _contract_findings(name):
    result = scan_paths([os.path.join(FIXTURES, name)], select=[METRIC_CONTRACT])
    return [f.message for f in result.findings if f.rule == METRIC_CONTRACT]


def test_byzlint_flags_an_uncatalogued_named_scope():
    found = _contract_findings("metric_contract_scopes_tp.py")
    assert any("named_scope label 'round.bogus_stage'" in m for m in found)


def test_byzlint_flags_an_unnamed_and_an_uncatalogued_pallas_call():
    found = _contract_findings("metric_contract_scopes_tp.py")
    assert any("pallas_call without name=" in m for m in found)
    assert any("kernel name 'bogus_kernel'" in m for m in found)
    # with the literal scope and the two computed ones (tests/test_model_scopes.py)
    assert len(found) == 5


def test_byzlint_is_silent_on_catalogued_and_computed_in_jit_names():
    assert _contract_findings("metric_contract_scopes_fp.py") == []
