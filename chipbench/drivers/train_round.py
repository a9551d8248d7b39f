"""Driver: the fused robust parameter-server round, on one chip or a mesh.

The cell's configuration names the model factory and the sizes; its
traffic mix names the aggregator, the attack, the images per worker and
how often the trainer reads the loss. Everything is resolved by dotted
path, so a new aggregator, attack or model is a new data file.

One compiled step and its state are built in set-up, driven through
their first three steps (which the reference follows afterwards), warmed,
and handed to the window.
"""

from __future__ import annotations

import os
import statistics
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

from chipbench import opcount, reference, seeded, stated_types
from chipbench.harness import Ctx, resolve


def _attack_fn(spec: Dict[str, Any]):
    """``(honest, key) -> rows`` from the mix's attack entry. ``input``
    says what the attack function is given: the honest rows or their mean."""
    import jax.numpy as jnp

    fn = resolve(spec["fn"])
    kwargs = spec.get("kwargs", {})
    if spec.get("input", "honest") == "honest_mean":
        return lambda honest, key: fn(jnp.mean(honest, axis=0), **kwargs)
    return lambda honest, key: fn(honest, **kwargs)


def _mean_rows(x):
    import jax.numpy as jnp

    return jnp.mean(x, axis=0)


class _Lane:
    """One jitted step with the state it carries and the batches it
    cycles through; every loss stays on the device until it is asked for."""

    def __init__(self, step, params, opt, xs, ys, keys):
        self.step, self.params, self.opt = step, params, opt
        self.xs, self.ys, self.keys = xs, ys, keys
        self.i = 0
        self.losses: List[Any] = []

    def advance(self) -> Dict[str, Any]:
        k = self.i % len(self.xs)
        self.params, self.opt, metrics = self.step(
            self.params, self.opt, self.xs[k], self.ys[k], self.keys[k]
        )
        self.losses.append(metrics["honest_loss"])
        self.i += 1
        return metrics


def _first_gradient(opt_state: Any, params_like: Any) -> Any:
    """The aggregate the optimizer got in step one, as a tree shaped like
    the parameters: SGD with momentum keeps it as its trace (momentum
    times zero plus the gradient). One chip: the trace mirrors the
    parameter tree. Sharded update: the state is flat vectors padded to
    the shard grid, parameters first, then the trace."""
    import jax

    leaves = jax.tree_util.tree_leaves(opt_state)
    like = jax.tree_util.tree_leaves(params_like)
    shapes = [tuple(leaf.shape) for leaf in like]
    for at in range(len(leaves) - len(like), -1, -1):
        if [tuple(x.shape) for x in leaves[at : at + len(like)]] == shapes:
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(params_like), leaves[at : at + len(like)]
            )
    d = sum(leaf.size for leaf in like)
    flat = [x for x in leaves if x.ndim == 1 and x.shape[0] >= d]
    if not flat:
        raise RuntimeError("no momentum trace found in the optimizer state")
    return reference.unflatten(np.asarray(flat[-1])[:d], params_like)


def run(ctx: Ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step

    cfg, mix = ctx.config, ctx.mix
    control = ctx.control_spec()
    n, b = int(cfg["n_nodes"]), int(cfg["n_byzantine"])
    batch = int(mix["batch"])
    pool = int(mix["pool_batches"])
    sync_every = int(mix["sync_every"])
    lr, momentum = float(cfg["learning_rate"]), float(cfg["momentum"])
    chips = int(ctx.cell["chips"])

    mesh = repl = node_sharding = None
    if chips > 1:
        from byzpy_tpu.parallel.mesh import node_axis, node_mesh, replicated, sharding

        mesh = node_mesh(chips, devices=ctx.devices)
        repl = replicated(mesh)
        node_sharding = sharding(mesh, node_axis(mesh))

    # -- the system under test: model, aggregator, attack, one jitted step
    factory = resolve(cfg["model"]["factory"])
    factory_kwargs = dict(cfg["model"].get("kwargs", {}))
    for key, value in control.get("factory_kwargs", {}).items():
        factory_kwargs[key] = jnp.dtype(value) if key == "dtype" else value
    held: Dict[str, Any] = {}

    def abstract_params():
        held["bundle"] = factory(0, **factory_kwargs)
        return held["bundle"].params

    shapes = jax.eval_shape(abstract_params)
    d = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))

    def fresh_params(sharding_=None):
        return seeded.make_params(shapes, ctx.seed, sharding=sharding_)

    bundle = held["bundle"].with_params(fresh_params(repl))
    aggregate = partial(resolve(mix["aggregate"]["fn"]), **mix["aggregate"].get("kwargs", {}))
    attack = _attack_fn(mix["attack"])
    step_kwargs = dict(mix.get("step_kwargs", {}))
    step_kwargs.update(control.get("step_kwargs", {}))
    if "grad_dtype" in step_kwargs:
        step_kwargs["grad_dtype"] = jnp.dtype(step_kwargs["grad_dtype"])
    ps_cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=lr, momentum=momentum)
    step, opt_state = jit_ps_train_step(
        bundle, aggregate, ps_cfg, attack=attack, mesh=mesh, donate=True, **step_kwargs
    )
    params = fresh_params(repl)  # the step donates: bundle.params stays whole
    xs, ys = seeded.make_batches(
        ctx.seed, pool=pool, n_nodes=n, batch=batch,
        input_shape=cfg["input_shape"], num_classes=int(cfg["num_classes"]),
        sharding=node_sharding,
    )
    keys = seeded.step_keys(ctx.seed, pool)
    copy_tree = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
    ctx.say(setup="built", d=d, n_nodes=n, n_byzantine=b, images_per_worker=batch,
            mesh=None if mesh is None else dict(mesh.shape))

    robust = _Lane(step, params, opt_state, xs, ys, keys)

    def steps(lane: "_Lane", count: int) -> float:
        """``count`` steps back to back, the loss read every
        ``sync_every``; returns the seconds they took, device included.
        The host's three activities are spans, so that a traced run can
        say which of them an idle gap of the device fell into."""
        t0 = time.perf_counter()
        for k in range(0, count, sync_every):
            with ctx.span("dispatch_steps"):
                for _ in range(min(sync_every, count - k)):
                    metrics = lane.advance()
            if k + sync_every <= count:
                with ctx.span("read_loss"):
                    float(metrics["honest_loss"])
        with ctx.span("wait_for_device"):
            jax.block_until_ready((lane.params, lane.opt))
        return time.perf_counter() - t0

    # -- set-up: the first three steps (followed by the reference), warm-up
    robust.advance()
    opt_after_one = copy_tree(robust.opt)
    robust.advance()
    robust.advance()
    params_after_three = copy_tree(robust.params)
    steps(robust, sync_every)
    # the types the window's program asks for, from its lowered text (the
    # jit has traced it already: no second trace, and nothing compiles)
    t_lower = time.perf_counter()
    lowered = step.lower(robust.params, robust.opt, xs[0], ys[0], keys[0])
    lowered_text = lowered.as_text()
    narrow = stated_types.narrow_elements(lowered_text, cfg["stated_dtype"])
    ctx.say(lowered_text_bytes=len(lowered_text), reading_it_s=time.perf_counter() - t_lower,
            largest_tensor_by_type=stated_types.largest_by_type(lowered_text))
    del lowered_text
    compiled_text = lowered.compile().as_text() if ctx.trace else ""
    del lowered

    out: Dict[str, Any] = {"end_to_end": {}, "measured": {}, "compiled_text": compiled_text}
    compiles_before = ctx.compiles()
    window_from = robust.i
    if not ctx.trace:
        # -- the measured window
        ctx.window_opens()
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        reads = [t0]  # when each loss was read: a stall shows as one long block
        while True:
            for _ in range(sync_every):
                metrics = robust.advance()
            float(metrics["honest_loss"])  # the trainer logs
            reads.append(time.perf_counter())
            if reads[-1] >= deadline:
                break
        jax.block_until_ready((robust.params, robust.opt))
        elapsed = time.perf_counter() - t0
        done = robust.i - window_from
        out["end_to_end"]["train_samples_per_s"] = n * batch * done / elapsed
        blocks = [b - a for a, b in zip(reads, reads[1:])]
        ctx.say(window_s=elapsed, steps=done, step_ms=1e3 * elapsed / done,
                median_block_s=statistics.median(blocks), slowest_block_s=max(blocks),
                slowest_block_at=blocks.index(max(blocks)), host_loadavg=os.getloadavg())
        compiles_in_window = ctx.compiles() - compiles_before
    else:
        # -- the traced run: extra programs compile first, outside the trace
        out["memory_peak_bytes"] = ctx.memory_peak()  # the cell's own programs only
        plain_cfg = PSStepConfig(n_nodes=n, n_byzantine=0, learning_rate=lr, momentum=momentum)
        plain, plain_opt = jit_ps_train_step(
            bundle, _mean_rows, plain_cfg, attack=None, mesh=mesh, donate=True
        )
        plain_lane = _Lane(plain, fresh_params(repl), plain_opt, xs, ys, keys)
        steps(plain_lane, 3)
        matrix_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            matrix_sharding = NamedSharding(mesh, PartitionSpec(None, mesh.axis_names))
        d_cols = -(-d // chips) * chips  # the step pads the columns to the shard grid
        matrix = seeded.make_matrix(ctx.seed, n, d_cols, sharding=matrix_sharding)

        def chipbench_agg_alone(x):
            return aggregate(x)

        agg_alone = jax.jit(chipbench_agg_alone)
        jax.block_until_ready(agg_alone(matrix))
        compiles_before = ctx.compiles()
        traced_steps, agg_calls, block = int(mix["traced_steps"]), 20, 30
        with ctx.profile():
            with ctx.span("window"):
                steps(robust, traced_steps)
            with ctx.span("agg_alone"):
                for _ in range(agg_calls):
                    result = agg_alone(matrix)
                jax.block_until_ready(result)
        compiles_in_window = ctx.compiles() - compiles_before
        del matrix, result
        # robust and plain blocks by the host clock, outside the profiler
        t_robust = steps(robust, block)
        t_plain = steps(plain_lane, block)
        del plain_lane
        out["measured"].update(
            t_robust_block_s=t_robust, t_plain_block_s=t_plain, block_steps=block,
            traced_steps=traced_steps, agg_calls=agg_calls,
            step_module="train_step", agg_module="chipbench_agg_alone",
            agg_matrix_bytes_per_device=opcount.aggregate_bytes(n, d_cols) / chips,
        )
        ctx.say(robust_block_s=t_robust, plain_block_s=t_plain, block_steps=block)

    out.setdefault("memory_peak_bytes", ctx.memory_peak())
    t_closed = time.perf_counter()
    host_losses = np.asarray([float(v) for v in robust.losses], np.float64)
    out["attempted"] = int(host_losses.size)
    out["failed"] = int(np.count_nonzero(~np.isfinite(host_losses)))
    tail = host_losses[-min(pool, host_losses.size):]
    ctx.say(first_loss=host_losses[0], last_losses_mean=float(np.mean(tail)),
            median_loss=statistics.median(host_losses.tolist()))
    platforms = {
        dev.platform
        for leaf in jax.tree_util.tree_leaves((robust.params, robust.opt))
        for dev in leaf.devices()
    }

    # -- the reference follows the first three steps, after the window
    def on_host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    def minus(tree, other):
        return jax.tree_util.tree_map(lambda a, b_: a - b_, tree, other)

    def rel_diff(got, want):
        got, want = reference.flatten_host(got), reference.flatten_host(want)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    params0 = fresh_params()
    got_first = on_host(_first_gradient(opt_after_one, shapes))
    got_change = minus(on_host(params_after_three), on_host(params0))
    del opt_after_one, params_after_three
    ref_cfg, ref_mix = cfg["reference"], mix["reference"]
    first = ctx.devices[0]
    followed = reference.follow_rounds(
        resolve(ref_cfg["logits"]), ref_cfg["arch"], params0,
        [(jax.device_put(xs[i], first), jax.device_put(ys[i], first)) for i in range(3)],
        n_nodes=n, n_byzantine=b,
        aggregate=partial(resolve(ref_mix["aggregate"]), **ref_mix.get("aggregate_kwargs", {})),
        attack=resolve(ref_mix["attack"]),
        lr=lr, momentum=momentum,
        precision=ref_cfg.get("precision"), dtype=jnp.dtype(ref_cfg["dtype"]),
    )
    want_first = on_host(followed["first_grad"])
    want_change = minus(on_host(followed["params"]), on_host(params0))
    ctx.say(
        info="norm of the difference over the reference's norm, whole vector (not compared)",
        first_gradient=rel_diff(got_first, want_first),
        param_change=rel_diff(got_change, want_change),
    )
    limits = cfg["limits"]
    loss_gaps = [
        abs(got - want) / abs(want)
        for got, want in zip(host_losses[:3].tolist(), followed["losses"])
    ]
    checks = [
        ("loss_gap_steps_1_to_3", max(loss_gaps), "<=", limits["loss_gap"]),
        ("first_gradient_norm_gap_worst_leaf",
         reference.worst_leaf_norm_gap(
             reference.leaf_norms(got_first), reference.leaf_norms(want_first)),
         "<=", limits["first_gradient_norm_gap"]),
        ("param_change_norm_gap_worst_leaf",
         reference.worst_leaf_norm_gap(
             reference.leaf_norms(got_change), reference.leaf_norms(want_change)),
         "<=", limits["param_change_norm_gap"]),
        ("first_gradient_short_mantissa_share",
         reference.short_mantissa_share(reference.flatten_host(got_first)), "<=",
         limits["first_gradient_short_mantissa_share"]),
        ("elements_narrower_than_" + cfg["stated_dtype"], narrow, "==", 0),
        ("nonfinite_losses", out["failed"], "==", 0),
        ("last_losses_mean_over_first_loss", float(np.mean(tail)) / host_losses[0], "<", 1.0),
        ("compilations_in_window", compiles_in_window, "==", 0),
        ("step_cache_size", step._cache_size(), "==", 1),
        ("state_off_platform", len(platforms - {first.platform}), "==", 0),
    ]
    ctx.say(reference_losses=followed["losses"], program_losses=host_losses[:3].tolist(),
            seconds_after_the_window=time.perf_counter() - t_closed)
    out["checks"] = checks
    return out
