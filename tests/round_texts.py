"""The round programs of a tree as text, to hold one tree against another.

``python tests/round_texts.py write <tree> <out_dir> <group>`` builds every
program of ``group`` from the package in ``<tree>`` (a checkout, or a ``git
archive`` of another commit) and writes, a program, its lowered text
(``<name>.lowered.txt``: what the compiler is handed, every Mosaic kernel's
body decoded and printed without the file names and line numbers it carries)
and the multiset of ``op_name`` strings of its compiled text
(``<name>.op_names.txt``: the scopes the benchmark's readers join on).
``python tests/round_texts.py compare <dir> <dir>`` prints, a program, the
sha256 of both sides' texts and whether they are equal, and exits 1 where
any differs or is missing. Identical text in is an identical executable
out, so a refactor of ``parallel/ps.py`` that leaves every line of the table
``equal`` changed no program (``python tests/test_parallel_ps.py <parent
tree> <out dir> [group ...]`` runs both sides and the comparison).

Groups, one process each (a group sets its own backend before JAX loads):

* ``tpu``: one-device toys compiled for a DESCRIBED v5e with the kernels
  forced and compiled by Mosaic, as ``test_round_matrix_once._write_tpu_texts``
  does (no chip): every ``FOLDED_ROUNDS`` entry, the streamed toy with an
  attack its kernel forms, with two it does not (one reads the key) and with
  an optimizer that is not elementwise, a forced flat update, rows kept in
  bfloat16, no byzantine worker.
* ``mesh``: the toys on a mesh of four forced host devices: the update
  replicated and sharded, the transpose plain, int8 and s4 with error
  feedback, a compressed parameter gather, a segmented bundle.
* ``cell:<name>``: a cell of ``BENCHMARK.json`` at its configuration's
  size, built as its driver builds it, for a described v5e (the mesh
  cell: on forced host devices, where its builder can place its state). The language cells build zero parameters on the CPU
  first (minutes) and compile in one to five minutes each.
"""

from __future__ import annotations

import base64
import collections
import hashlib
import json
import os
import re
import sys
from functools import partial


def _decoded(text):
    """``text`` with every ``tpu_custom_call`` body (base64 MLIR bytecode
    that holds the checkout's path and line numbers) parsed and printed
    without debug info."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return "body: " + module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def _write(out_dir, name, jitted, args):
    lowered = jitted.lower(*args)
    with open(os.path.join(out_dir, name + ".lowered.txt"), "w", encoding="utf-8") as fh:
        fh.write(_decoded(lowered.as_text()))
    names = collections.Counter(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    with open(os.path.join(out_dir, name + ".op_names.txt"), "w", encoding="utf-8") as fh:
        fh.writelines(f"{count}\t{op_name}\n" for op_name, count in sorted(names.items()))
    print("wrote", name, flush=True)


def _described_v5e():
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def _shapes(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _toy_args(toys, bundle, opt_state, params_at, batch_at, streamed):
    import jax
    import jax.numpy as jnp

    x = (toys.N, 4, 16) if streamed else (toys.N, 4, 28, 28, 1)
    return (_shapes(bundle.params, params_at), _shapes(opt_state, params_at),
            jax.ShapeDtypeStruct(x, jnp.float32, sharding=batch_at),
            jax.ShapeDtypeStruct((toys.N, 4), jnp.int32, sharding=batch_at),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=params_at))


def write_tpu(out_dir):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    import test_round_matrix_once as toys
    from byzpy_tpu.models.nets import mnist_mlp
    from byzpy_tpu.ops import attack_ops, coordinatewise, pallas_kernels
    from byzpy_tpu.parallel.ps import PSStepConfig, build_ps_train_step

    os.environ["BYZPY_TPU_PALLAS"] = "1"
    pallas_kernels._resolve_interpret = lambda interpret: False
    one_chip = SingleDeviceSharding(_described_v5e().devices[0])
    mlp, streamed = mnist_mlp(0, hidden=16), toys._streamed_toy()
    trimmed = toys.AGGREGATORS["trimmed_mean"]
    sign_flip = coordinatewise.RoundAttack(attack_ops.sign_flip, of="honest_mean")
    little = coordinatewise.RoundAttack(
        attack_ops.little, kwargs={"f": toys.B, "n_total": toys.N})
    class Keyed(coordinatewise.RoundAttack):
        """A round attack that reads the key: the kernel does not form it."""

        def __call__(self, honest, key):
            return super().__call__(honest, key) * jax.random.rademacher(key, (), jnp.float32)

    factored = coordinatewise.leafwise(
        optax.chain(optax.scale_by_factored_rms(), optax.scale(-0.1)))
    no_byzantine = PSStepConfig(n_nodes=toys.N, n_byzantine=0)
    programs = {
        **{"folded_" + name: (mlp, toys.AGGREGATORS[agg], toys.CFG,
                              dict(attack=toys.ATTACKS["sign_flip"], pre_aggregate=pre))
           for name, (agg, pre, _) in toys.FOLDED_ROUNDS.items()},
        "folded_flat_update": (mlp, trimmed, toys.CFG,
                               dict(attack=toys.ATTACKS["sign_flip"], sharded_update="on")),
        "folded_no_byzantine": (mlp, toys._mean, no_byzantine, {}),
        "folded_grad_bf16": (mlp, trimmed, toys.CFG,
                             dict(attack=toys.ATTACKS["noise"], grad_dtype=jnp.bfloat16)),
        "streamed_kernel_formed": (streamed, trimmed, toys.CFG, dict(attack=sign_flip)),
        "streamed_little": (streamed, trimmed, toys.CFG, dict(attack=little)),
        "streamed_keyed": (streamed, trimmed, toys.CFG,
                           dict(attack=Keyed(attack_ops.sign_flip, of="honest_mean"))),
        "streamed_leafwise_factored": (streamed, trimmed, toys.CFG,
                                       dict(attack=sign_flip, optimizer=factored)),
        "streamed_no_byzantine": (streamed, trimmed, no_byzantine, {}),
    }
    for name, (bundle, aggregate, cfg, kwargs) in programs.items():
        step, opt_state = build_ps_train_step(bundle, aggregate, cfg, **kwargs)
        _write(out_dir, name, jax.jit(step), _toy_args(
            toys, bundle, opt_state, one_chip, one_chip, bundle.segments is not None))


def write_mesh(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import test_round_matrix_once as toys
    from byzpy_tpu.models.bundle import ModelBundle
    from byzpy_tpu.models.nets import mnist_mlp
    from byzpy_tpu.parallel.mesh import node_axis, node_mesh, replicated, sharding
    from byzpy_tpu.parallel.ps import ShardedUpdateConfig, build_ps_train_step
    from byzpy_tpu.parallel.quantization import CommPrecision

    mesh = node_mesh(4, devices=jax.devices()[:4])
    repl, on_nodes = replicated(mesh), sharding(mesh, node_axis(mesh))
    mlp, streamed = mnist_mlp(0, hidden=16), toys._streamed_toy()
    s4 = CommPrecision(mode="s4", error_feedback=True)
    programs = {
        "mesh_update_off": (mlp, dict(sharded_update="off")),
        "mesh_update_on": (mlp, dict(sharded_update="on")),
        "mesh_pre_aggregate": (mlp, dict(pre_aggregate=toys._clip)),
        "mesh_transpose_int8": (mlp, dict(comm_precision="int8")),
        "mesh_transpose_s4_ef": (mlp, dict(comm_precision=s4)),
        "mesh_transpose_int8_update_off": (mlp, dict(comm_precision="int8", sharded_update="off")),
        "mesh_gather_int8": (mlp, dict(sharded_update=ShardedUpdateConfig(
            mode="on", param_gather_precision="int8"))),
        "mesh_gather_s4_ef": (mlp, dict(comm_precision=s4, sharded_update=ShardedUpdateConfig(
            mode="on", param_gather_precision=s4))),
        "mesh_noise_rows": (mlp, dict(attack=toys.ATTACKS["noise"])),
        "mesh_echo_rows": (mlp, dict(attack=None)),
        "mesh_segmented_bundle": (streamed, {}),
        # d = 4096: whole tiles, which the mesh round's layout reads too
        "mesh_whole_tiles_d": (ModelBundle(
            lambda p, x: x.reshape(x.shape[0], -1)[:, :16] @ p["w"],
            {"w": jnp.zeros((16, 256), jnp.float32)}), {}),
    }
    for name, (bundle, kwargs) in programs.items():
        kwargs = {"attack": toys.ATTACKS["sign_flip"], **kwargs}
        step, opt_state = build_ps_train_step(
            bundle, toys.AGGREGATORS["trimmed_mean"], toys.CFG, mesh=mesh, **kwargs)
        args = _toy_args(toys, bundle, opt_state, repl, on_nodes, bundle.segments is not None)
        # the state the builder placed on the mesh stays where it was put
        placed = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if isinstance(a.sharding, NamedSharding) else repl), opt_state)
        _write(out_dir, name, jax.jit(step), (args[0], placed, *args[2:]))


def _cell(name):
    with open("BENCHMARK.json", encoding="utf-8") as fh:
        return next(w for w in json.load(fh)["workloads"] if w["name"] == name)


def write_cell(out_dir, cell_name):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from byzpy_tpu.ops import pallas_kernels
    from byzpy_tpu.ops.coordinatewise import RoundAttack
    from byzpy_tpu.parallel.mesh import node_axis, node_mesh, replicated, sharding
    from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step
    from chipbench.drivers.train_round import _attack_fn
    from chipbench.harness import resolve

    cell = _cell(cell_name)
    with open(f"chipbench/configs/{cell['config']}.json", encoding="utf-8") as fh:
        cfg = json.load(fh)
    with open(f"chipbench/traffic/{cell['traffic']}.json", encoding="utf-8") as fh:
        mix = json.load(fh)
    n = int(cfg["n_nodes"])
    ps_cfg = PSStepConfig(n_nodes=n, n_byzantine=int(cfg["n_byzantine"]),
                          learning_rate=float(cfg["learning_rate"]),
                          momentum=float(cfg["momentum"]))
    aggregate = partial(resolve(mix["aggregate"]["fn"]), **mix["aggregate"].get("kwargs", {}))
    kwargs = dict(cfg["model"].get("kwargs", {}))
    if "held_experts" in kwargs:
        kwargs["held_experts"] = tuple(kwargs["held_experts"])
    if int(cell["chips"]) > 1:  # the builder places state on its mesh: forced host devices
        mesh = node_mesh(int(cell["chips"]), devices=jax.devices()[:int(cell["chips"])])
        params_at, batch_at = replicated(mesh), sharding(mesh, node_axis(mesh))
    else:
        pallas_kernels._on_tpu = lambda: True
        mesh = None
        params_at = batch_at = SingleDeviceSharding(_described_v5e().devices[0])
    held = {}

    def abstract():
        held["bundle"] = resolve(cfg["model"]["factory"])(0, **kwargs)
        return held["bundle"].params

    shapes = jax.eval_shape(abstract)
    # an optimizer's state is made from arrays: zeros, on the CPU
    bundle = held["bundle"].with_params(
        jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), shapes))
    if mix["driver"] == "train_round":
        attack = _attack_fn(mix["attack"])
        x = jax.ShapeDtypeStruct((n, int(mix["batch"]), *cfg["input_shape"]), jnp.float32,
                                 sharding=batch_at)
        y = jax.ShapeDtypeStruct((n, int(mix["batch"])), jnp.int32, sharding=batch_at)
    else:
        attack = RoundAttack(resolve(mix["attack"]["fn"]), of=mix["attack"].get("input", "honest"),
                             kwargs=mix["attack"].get("kwargs", {}))
        x = y = jax.ShapeDtypeStruct((n, 1, int(mix["tokens_per_worker"])), jnp.int32,
                                     sharding=batch_at)
    step, opt_state = jit_ps_train_step(
        bundle, aggregate, ps_cfg, attack=attack, mesh=mesh, donate=True,
        **mix.get("step_kwargs", {}))
    opt_shapes = jax.eval_shape(lambda: opt_state)
    del bundle, opt_state
    _write(out_dir, "cell_" + cell_name, step,
           (_shapes(shapes, params_at), _shapes(opt_shapes, params_at), x, y,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=params_at)))


def compare(ours, theirs):
    """The table: a program, the digest of each side's two texts, equal or
    not. Returns the number of programs that differ or stand on one side."""
    names = sorted({f for side in (ours, theirs) for f in os.listdir(side) if f.endswith(".txt")})
    differing = 0
    for name in names:
        digests = []
        for side in (ours, theirs):
            try:
                with open(os.path.join(side, name), "rb") as fh:
                    digests.append(hashlib.sha256(fh.read()).hexdigest())
            except FileNotFoundError:
                digests.append("missing")
        equal = digests[0] == digests[1]
        differing += not equal
        print(f"{name[:-len('.txt')]}\t{digests[0][:16]}\t{digests[1][:16]}\t"
              f"{'equal' if equal else 'DIFFERENT'}")
    return differing


def main(argv):
    if argv[0] == "compare":
        return 1 if compare(argv[1], argv[2]) else 0
    _, tree, out_dir, group = argv
    tree, out_dir = os.path.abspath(tree), os.path.abspath(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(tree)
    chips = {"tpu": 1, "mesh": 4}.get(group) or int(_cell(group.split(":", 1)[1])["chips"])
    if chips > 1:
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    import byzpy_tpu

    if os.path.dirname(os.path.dirname(byzpy_tpu.__file__)) != tree:
        raise SystemExit(f"byzpy_tpu came from {byzpy_tpu.__file__}, not from {tree}")
    if group == "tpu":
        write_tpu(out_dir)
    elif group == "mesh":
        write_mesh(out_dir)
    else:
        write_cell(out_dir, group.split(":", 1)[1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
