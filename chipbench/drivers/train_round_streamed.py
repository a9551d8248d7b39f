"""Driver: the robust parameter-server round of a language model whose
round streams segment by segment, on one chip.

The cell's configuration names the model factory (a bundle that declares
segments) and its reference; the traffic mix names the aggregator, the
attack, the tokens a worker packs into its one sequence a step, and the
pool of seeded batches. Aggregator and attack are resolved by dotted path
and have to be listed in the program's ``ops/coordinatewise.py``.

What differs from ``train_round``, and why: the model is 667M parameters,
so nothing here ever holds a second copy of the parameters or of the
optimizer state beside the step's own (the chip has 16 GB and the step
needs most of it). The step is compiled once, ahead of time, and that one
executable is what set-up, window and trace run. What the comparison
needs of the program (the norm of every leaf of the first aggregate, the
share of its values with a short mantissa, the norm of every leaf's change
after the rounds followed) is reduced on the device in set-up, at the
moment the state holds it. After the window the program's state and
executable are dropped, and the reference (``chipbench/
reference_nemotron_h.py``) follows the same rounds from the same seeded
weights with the chip to itself.
"""

from __future__ import annotations

import functools
import gc
import os
import statistics
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

from chipbench import (
    expert_round, opcount, reference, seeded, seeded_nemotron_h, stated_types)
from chipbench.harness import Ctx, resolve


def _leaf_norms(tree: Any) -> List[Any]:
    import jax.numpy as jnp
    import jax

    return [jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for leaf in jax.tree_util.tree_leaves(tree)]


def _short_mantissa(tree: Any, low_bits: int = 8):
    """``reference.short_mantissa_share`` where the values lie: (values
    whose ``low_bits`` lowest mantissa bits are all zero, values that are
    not zero), summed over the tree's float32 leaves. Per leaf the counts
    fit an int32; their sums are taken on the host."""
    import jax
    import jax.numpy as jnp

    short, nonzero = [], []
    for leaf in jax.tree_util.tree_leaves(tree):
        bits = jax.lax.bitcast_convert_type(leaf.astype(jnp.float32), jnp.uint32)
        is_nonzero = (bits & jnp.uint32(0x7FFFFFFF)) != 0
        is_short = ((bits & jnp.uint32((1 << low_bits) - 1)) == 0) & is_nonzero
        short.append(jnp.sum(is_short, dtype=jnp.int32))
        nonzero.append(jnp.sum(is_nonzero, dtype=jnp.int32))
    return short, nonzero


def _change_norms(params: Dict[str, Any], shapes: Dict[str, Any], seed: int,
                  arch: Dict[str, Any]) -> List[float]:
    """The norm of every leaf of ``params - params0``, the seeded starting
    weights made again one segment at a time (never a second whole copy)."""
    out: List[float] = []
    for segment in sorted(shapes):
        start = seeded_nemotron_h.make_segment(shapes, seed, segment, arch)
        out.extend(float(v) for v in _difference_norms()(params[segment], start))
        del start
    return out


@functools.lru_cache(maxsize=None)
def _difference_norms():
    """One jitted function for every call (a new one a call would trace
    every segment again, before and after the window)."""
    import jax

    return jax.jit(lambda now, start: _leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, now, start)))


class _Lane:
    """The compiled step with the state it carries and the batches it
    cycles through; losses and expert counts stay on the device until
    they are asked for."""

    def __init__(self, step, params, opt, xs, ys, keys):
        self.step, self.params, self.opt = step, params, opt
        self.xs, self.ys, self.keys = xs, ys, keys
        self.i = 0
        self.losses: List[Any] = []
        self.aux: List[Any] = []

    def advance(self) -> Dict[str, Any]:
        k = self.i % len(self.xs)
        self.params, self.opt, metrics = self.step(
            self.params, self.opt, self.xs[k], self.ys[k], self.keys[k])
        self.losses.append(metrics["honest_loss"])
        self.aux.append(metrics.get("segment_aux", {}))
        self.i += 1
        return metrics


def _expert_counts(aux_of_steps: List[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    """``held_expert_tokens`` as (steps, h, expert blocks, held),
    ``tokens_dropped`` and ``expert_rounds`` as (steps, h, expert blocks),
    on the host."""
    names = {"tokens": "held_expert_tokens", "dropped": "tokens_dropped",
             "rounds": "expert_rounds"}
    return {short: np.stack([
        np.stack([np.asarray(aux[key][name]) for key in sorted(aux)], axis=1)
        for aux in aux_of_steps]) for short, name in names.items()}


def run(ctx: Ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from byzpy_tpu.ops.coordinatewise import RoundAttack
    from byzpy_tpu.parallel.ps import PSStepConfig, jit_ps_train_step

    cfg, mix = ctx.config, ctx.mix
    control = ctx.control_spec()
    if int(ctx.cell["chips"]) != 1:
        raise SystemExit("chipbench: the streamed round runs on one chip")
    n, b = int(cfg["n_nodes"]), int(cfg["n_byzantine"])
    seq_len, pool = int(mix["tokens_per_worker"]), int(mix["pool_batches"])
    lr, momentum = float(cfg["learning_rate"]), float(cfg["momentum"])
    ref_cfg, ref_mix = cfg["reference"], mix["reference"]
    arch, followed_rounds = ref_cfg["arch"], int(ref_cfg["rounds"])
    first = ctx.devices[0]

    # -- the system under test: model, aggregator, attack, one compiled step
    factory = resolve(cfg["model"]["factory"])
    factory_kwargs = dict(cfg["model"].get("kwargs", {}))
    for key, value in control.get("factory_kwargs", {}).items():
        factory_kwargs[key] = jnp.dtype(value) if key == "dtype" else value
    if "held_experts" in factory_kwargs:  # JSON has no tuples
        factory_kwargs["held_experts"] = tuple(factory_kwargs["held_experts"])
    held: Dict[str, Any] = {}

    def abstract_params():
        held["bundle"] = factory(0, **factory_kwargs)
        return held["bundle"].params

    shapes = jax.eval_shape(abstract_params)
    d = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(shapes))
    d_largest = max(sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(sub))
                    for sub in shapes.values())
    params = seeded_nemotron_h.make_params(shapes, ctx.weights_seed, arch)
    bundle = held.pop("bundle").with_params(params)
    aggregate = partial(resolve(mix["aggregate"]["fn"]), **mix["aggregate"].get("kwargs", {}))
    attack = RoundAttack(resolve(mix["attack"]["fn"]), of=mix["attack"].get("input", "honest"),
                         kwargs=mix["attack"].get("kwargs", {}))
    step_kwargs = dict(mix.get("step_kwargs", {}))
    step_kwargs.update(control.get("step_kwargs", {}))
    if "grad_dtype" in step_kwargs:
        step_kwargs["grad_dtype"] = jnp.dtype(step_kwargs["grad_dtype"])
    ps_cfg = PSStepConfig(n_nodes=n, n_byzantine=b, learning_rate=lr, momentum=momentum)
    jitted, opt_state = jit_ps_train_step(
        bundle, aggregate, ps_cfg, attack=attack, donate=True, **step_kwargs)
    del bundle  # the step donates its state: `params` is the one reference
    xs, ys = seeded_nemotron_h.make_token_batches(
        ctx.seed, pool=pool, n_nodes=n, seq_len=seq_len, vocab=int(cfg["vocab_size"]))
    keys = seeded.step_keys(ctx.seed, pool)
    ctx.say(setup="built", d=d, d_largest_segment=d_largest, segments=len(shapes), n_nodes=n,
            n_byzantine=b, tokens_per_worker=seq_len, mesh=None)

    # one program, compiled ahead of time: its lowered text says which types
    # it asks for, its compiled text is what a traced run's readers join
    t_lower = time.perf_counter()
    lowered = jitted.lower(params, opt_state, xs[0], ys[0], keys[0])
    lowered_text = lowered.as_text()
    narrow = stated_types.narrow_elements(lowered_text, cfg["stated_dtype"])
    ctx.say(lowered_text_bytes=len(lowered_text), reading_it_s=time.perf_counter() - t_lower,
            largest_tensor_by_type=stated_types.largest_by_type(lowered_text))
    del lowered_text
    step = lowered.compile()
    compiled_text = step.as_text() if ctx.trace else ""
    del lowered, jitted
    robust = _Lane(step, params, opt_state, xs, ys, keys)
    del params, opt_state

    def steps(lane: "_Lane", count: int) -> float:
        """``count`` steps, the loss of every one read (a step is a second
        or two: the trainer logs each), one step late: the next step is
        dispatched before the last one's loss is waited for, so the device
        has its next program queued while the host reads and logs. Returns
        the seconds they took. The host's activities are spans, so that a
        traced run can say which of them an idle gap of the device fell
        into."""
        t0 = time.perf_counter()
        pending = None
        for _ in range(count):
            with ctx.span("dispatch_steps"):
                metrics = lane.advance()
            if pending is not None:
                with ctx.span("read_loss"):
                    float(pending["honest_loss"])
            pending = metrics
        with ctx.span("read_loss"):
            float(pending["honest_loss"])
        with ctx.span("wait_for_device"):
            jax.block_until_ready((lane.params, lane.opt))
        return time.perf_counter() - t0

    # -- set-up: the rounds the reference follows, reduced where they lie
    robust.advance()
    trace_now = jax.tree_util.tree_leaves(robust.opt)  # momentum 0.9 * 0 + the aggregate
    got_first = [float(v) for v in jax.jit(_leaf_norms)(trace_now)]
    short, nonzero = jax.jit(_short_mantissa)(trace_now)
    del trace_now
    short_share = (sum(int(v) for v in short) / max(1, sum(int(v) for v in nonzero)))
    for _ in range(followed_rounds - 1):
        robust.advance()
    got_change = _change_norms(robust.params, shapes, ctx.weights_seed, arch)
    steps(robust, 1)
    platforms = {dev.platform
                 for leaf in jax.tree_util.tree_leaves((robust.params, robust.opt))
                 for dev in leaf.devices()}

    out: Dict[str, Any] = {"end_to_end": {}, "measured": {}, "compiled_text": compiled_text}
    compiles_before = ctx.compiles()
    window_from = robust.i
    # tracing a program of this size leaves a large heap of cyclic garbage and
    # of live tracer caches; one full collection of it inside the window would
    # cost a step (a window is a dozen steps). Collect now, and park what is
    # left where the window's own collections do not walk it.
    gc.collect()
    gc.freeze()
    if not ctx.trace:
        ctx.window_opens()
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        reads = [t0]
        pending = robust.advance()
        while True:
            metrics = robust.advance()  # queued behind the step whose loss is read next
            float(pending["honest_loss"])  # the trainer logs, one step late
            reads.append(time.perf_counter())
            pending = metrics
            if reads[-1] >= deadline:
                break
        float(pending["honest_loss"])
        jax.block_until_ready((robust.params, robust.opt))
        elapsed = time.perf_counter() - t0
        done = robust.i - window_from
        # a worker's batch is one packed sequence: n sequences a step
        out["end_to_end"]["train_samples_per_s"] = n * done / elapsed
        blocks = [b_ - a for a, b_ in zip(reads, reads[1:])]
        ctx.say(window_s=elapsed, steps=done, step_ms=1e3 * elapsed / done,
                tokens_per_s=n * seq_len * done / elapsed,
                median_block_s=statistics.median(blocks), slowest_block_s=max(blocks),
                slowest_block_at=blocks.index(max(blocks)), host_loadavg=os.getloadavg())
        compiles_in_window = ctx.compiles() - compiles_before
        out["memory_peak_bytes"] = ctx.memory_peak()
    else:
        # -- the traced run: the aggregator alone is compiled first, outside
        # the trace; its matrix (n rows of the largest segment) is made inside
        # it, once the step's state is gone: the two do not fit side by side
        out["memory_peak_bytes"] = ctx.memory_peak()  # the cell's own programs only
        from byzpy_tpu.ops.pallas_kernels import aligned_width

        width = aligned_width(n, d_largest)
        make_matrix = jax.jit(lambda k: jax.random.normal(k, (n, width), jnp.float32)).lower(
            seeded.root_key(ctx.seed)).compile()

        def chipbench_agg_alone(x):
            return aggregate(x)

        agg_alone = jax.jit(chipbench_agg_alone).lower(
            jax.ShapeDtypeStruct((n, width), jnp.float32)).compile()
        compiles_before = ctx.compiles()
        traced_steps, agg_calls = int(mix["traced_steps"]), 10
        with ctx.profile():
            with ctx.span("window"):
                steps(robust, traced_steps)
            robust.params = robust.opt = None
            with ctx.span("agg_alone"):
                matrix = make_matrix(seeded.root_key(ctx.seed))
                for _ in range(agg_calls):
                    result = agg_alone(matrix)
                jax.block_until_ready(result)
        compiles_in_window = ctx.compiles() - compiles_before
        del matrix, result, agg_alone, make_matrix
        out["measured"].update(
            traced_steps=traced_steps, agg_calls=agg_calls,
            step_module="train_step", agg_module="chipbench_agg_alone",
            agg_matrix_bytes_per_device=opcount.aggregate_bytes(n, width),
        )

    gc.unfreeze()
    t_closed = time.perf_counter()
    host_losses = np.asarray([float(v) for v in robust.losses], np.float64)
    counts = _expert_counts(robust.aux)
    out["attempted"] = int(host_losses.size)
    out["failed"] = int(np.count_nonzero(~np.isfinite(host_losses)))
    tail = host_losses[-min(pool, host_losses.size):]
    ctx.say(first_loss=host_losses[0], last_losses_mean=float(np.mean(tail)),
            median_loss=statistics.median(host_losses.tolist()), losses=host_losses.tolist())
    out["measured"]["held_expert_tokens_min"] = int(counts["tokens"].min())
    out["measured"]["expert_rounds_max"] = int(counts["rounds"].max())
    ctx.say(held_expert_tokens_min=int(counts["tokens"].min()),
            held_expert_tokens_max=int(counts["tokens"].max()),
            held_expert_tokens_mean=float(counts["tokens"].mean()),
            expert_rounds_max=int(counts["rounds"].max()),
            expert_layer_passes_with_more_than_one_round=int(
                np.count_nonzero(counts["rounds"] > 1)),
            of_expert_layer_passes=int(counts["rounds"].size))
    # each layer's fullest expert beside the rows of its round (the untraced
    # run reads the compiled text here, after the window: no part of set-up)
    loads = expert_round.facts(robust.aux[0], counts["tokens"], compiled_text or step.as_text())
    out["measured"].update(loads)
    ctx.say(**loads)

    # -- the chip to the reference: the program's state and executable go
    del step
    robust.params = robust.opt = robust.step = None
    gc.collect()
    params0 = seeded_nemotron_h.make_params(shapes, ctx.weights_seed, arch)
    followed = resolve(ref_cfg["follow_rounds"])(
        arch, params0,
        [(xs[i % pool], ys[i % pool]) for i in range(followed_rounds)],
        n_nodes=n, n_byzantine=b,
        aggregate=partial(resolve(ref_mix["aggregate"]), **ref_mix.get("aggregate_kwargs", {})),
        attack=resolve(ref_mix["attack"]), lr=lr, momentum=momentum,
        dtype=jnp.dtype(ref_cfg["dtype"]), precision=ref_cfg["precision"], report=ctx.say,
    )
    del params0
    want_change = _change_norms(followed.pop("params"), shapes, ctx.weights_seed, arch)
    want_first = followed["first_aggregate_leaf_norms"]
    got_tokens = counts["tokens"][:followed_rounds]
    want_tokens = followed["held_expert_tokens"]
    # program and reference pick a different sixth expert for a few tokens
    # (their inputs to the router differ by the default-precision
    # contractions before it); a wrong share of the experts differs by
    # hundreds (limits, `held_expert_tokens_difference`)
    tokens_difference = int(np.max(np.abs(got_tokens - want_tokens)))
    ctx.say(
        info="held experts' token counts, program against reference",
        counts_that_differ=int(np.count_nonzero(got_tokens != want_tokens)),
        of=int(got_tokens.size),
        largest_difference=tokens_difference,
        least_from_one_worker=[int(got_tokens.min()), int(want_tokens.min())],
    )
    limits = cfg["limits"]
    loss_gaps = [
        abs(got - want) / abs(want)
        for got, want in zip(host_losses[:followed_rounds].tolist(), followed["losses"])
    ]
    checks = [
        ("loss_gap_rounds_followed", max(loss_gaps), "<=", limits["loss_gap"]),
        ("first_gradient_norm_gap_worst_leaf",
         reference.worst_leaf_norm_gap(got_first, want_first),
         "<=", limits["first_gradient_norm_gap"]),
        ("param_change_norm_gap_worst_leaf",
         reference.worst_leaf_norm_gap(got_change, want_change),
         "<=", limits["param_change_norm_gap"]),
        ("first_gradient_short_mantissa_share", short_share, "<=",
         limits["first_gradient_short_mantissa_share"]),
        ("elements_narrower_than_" + cfg["stated_dtype"], narrow, "==", 0),
        ("nonfinite_losses", out["failed"], "==", 0),
        ("last_losses_mean_over_first_loss", float(np.mean(tail)) / host_losses[0], "<", 1.0),
        ("compilations_in_window", compiles_in_window, "==", 0),
        ("state_off_platform", len(platforms - {first.platform}), "==", 0),
        ("tokens_dropped", int(counts["dropped"].sum()), "==", 0),
        # the share the program computes is the share the reference computes:
        # every held expert's tokens, worker by worker, in the rounds followed
        ("held_expert_tokens_largest_difference_from_reference", tokens_difference, "<=",
         limits["held_expert_tokens_difference"]),
        # summed over the honest workers: every held expert's gradient is in
        # what the rounds followed compare. (From ONE worker an expert may
        # hear next to nothing: at the seeded initialisation, with no balancing
        # bias, the tokens share a direction that shifts every expert's score;
        # that least is reported, `held_expert_tokens_min`, and not held.)
        ("held_expert_tokens_min_over_workers_summed",
         int(got_tokens.sum(axis=1).min()), ">", 0),
        ("reference_held_expert_tokens_min_over_workers_summed",
         int(want_tokens.sum(axis=1).min()), ">", 0),
    ]
    ctx.say(reference_losses=followed["losses"],
            program_losses=host_losses[:followed_rounds].tolist(),
            first_gradient_leaf_norms=[got_first, want_first],
            param_change_leaf_norms=[got_change, want_change],
            seconds_after_the_window=time.perf_counter() - t_closed)
    out["checks"] = checks
    return out
