"""SPMD parameter-server training: the whole PS round as ONE jitted program.

The reference's PS round is host-orchestrated actor traffic — stream honest
gradients as-completed, feed them to byzantine actors, pickle everything
through pipes/shm, aggregate, fan the update back out
(ref: ``byzpy/engine/parameter_server/ps.py:103-144``). On TPU that entire
round collapses into a single compiled step, which
:func:`build_ps_train_step` picks among three (its docstring is the table):

* per-node gradients: on a mesh data is sharded ``P("nodes", ...)`` and
  every chip computes the gradients of the nodes it holds, one after
  another, in lockstep with the other chips (:func:`_mesh_train_step`); on
  one device the honest nodes' only, one after another
  (:func:`_one_device_train_step`; segment by segment for a model that is
  a chain: :func:`_streamed_train_step`);
* byzantine behavior: honest rows are a static slice of the stacked
  gradient matrix; the attack is a pure function of them writing the
  byzantine rows (SURVEY §7e — functional masking instead of separate
  actor code paths);
* aggregation: on a mesh the ``(n, d)`` matrix is re-laid-out
  feature-sharded via a sharding constraint — XLA inserts the
  ``all_to_all`` "gradient transpose" over ICI — so coordinate-wise
  aggregators run fully locally per chip and geometric ones psum an
  ``(n, n)`` Gram block;
* update: a mesh round stays sharded end-to-end
  (:class:`ShardedUpdateConfig`: optimizer state carried feature-sharded
  over the same grid, ONE params all-gather in place of the aggregated
  gradient's; "Automatic Cross-Replica Sharding of Weight Update in
  Data-Parallel Training", PAPERS.md).

No pickling, no shm, no host round-trips — the collectives ARE the
parameter server.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.bundle import ModelBundle
from ..utils.trees import leaf_view, ravel_pytree_fn, row_layout, tile_views, tree_size
from .collectives import reshard_q, reshard_q_ef
from .mesh import node_axis
from .quantization import (
    CommPrecision,
    as_comm_precision,
)

AggFn = Callable[[jnp.ndarray], jnp.ndarray]          # (n, d) -> (d,)
PreAggFn = Callable[[jnp.ndarray], jnp.ndarray]       # (n, d) -> (m, d)
# attack: (honest (h, d), key) -> (n_byz, d)
AttackFn = Callable[[jnp.ndarray, jax.Array], jnp.ndarray]


@dataclass(frozen=True)
class PSStepConfig:
    n_nodes: int
    n_byzantine: int = 0
    learning_rate: float = 0.05
    momentum: float = 0.9

    @property
    def n_honest(self) -> int:
        return self.n_nodes - self.n_byzantine


def default_optimizer(cfg: PSStepConfig) -> optax.GradientTransformation:
    """SGD+momentum, matching the reference examples' torch SGD
    (ref: ``examples/ps/nodes.py:70-74``)."""
    return optax.sgd(cfg.learning_rate, momentum=cfg.momentum)


_SHARDED_UPDATE_MODES = ("off", "on", "auto")


@dataclass(frozen=True)
class ShardedUpdateConfig:
    """Policy for the feature-sharded weight update.

    ``mode``:

    * ``"off"`` — replicated update: the aggregated gradient is gathered
      to every chip, every chip holds a full optimizer-state replica and
      redundantly applies the full d-dim update (the pre-round-8
      program, kept bit-identical).
    * ``"on"`` — the flat aggregated gradient, flat params, and the
      optimizer state all stay feature-sharded through ``opt.update`` /
      ``apply_updates``; one all-gather of the refreshed flat params
      replaces the aggregated-gradient gather. Per-chip opt-state HBM
      and update flops drop by the feature-grid size.
    * ``"auto"`` (default) — ``"on"`` whenever the mesh's feature grid
      spans more than one chip, else ``"off"``.

    ``param_gather_precision`` (``None``/``"off"``/``"bf16"``/``"int8"``/
    ``"fp8"``/``"fp8_e5m2"``/``"s4"`` or a
    :class:`~byzpy_tpu.parallel.quantization.CommPrecision`)
    compresses the params all-gather wire payload. The carried state
    always leads with each chip's authoritative EXACT flat param shard;
    the (possibly lossy) gathered replica only feeds the next round's
    forward/backward, so compression error is bounded per round and
    never compounds into the optimizer state. ``off`` (default) keeps
    the gather f32 and the sharded round bit-identical (coordinate-wise
    aggregators; elementwise optimizers) to the replicated one. A
    precision with ``error_feedback=True`` additionally carries the
    gather's quantization residual BESIDE the optimizer state
    (feature-sharded over the same grid, donated with it): each round's
    encode folds the previous round's residual in, so the gathered
    replica's error dithers around zero instead of re-rounding the same
    way every round — the sub-int8 modes (fp8/s4) lean on this.

    Trajectory contract: with an elementwise optimizer (SGD, momentum,
    Adam — anything whose update is a per-coordinate function of
    gradient/state/param) the sharded update is semantics-preserving.
    Optimizers keyed on the *tree structure* (per-layer scales,
    parameter-label partitioning) see one flat vector instead and must
    keep ``mode="off"``.
    """

    mode: str = "auto"
    param_gather_precision: Any = None

    def __post_init__(self):
        if self.mode not in _SHARDED_UPDATE_MODES:
            raise ValueError(
                f"mode must be one of {_SHARDED_UPDATE_MODES}, got {self.mode!r}"
            )
        as_comm_precision(self.param_gather_precision)  # validate eagerly

    def resolve(self, feat_shards: int) -> bool:
        """Whether the sharded update is active on a ``feat_shards``-way
        feature grid."""
        if self.mode == "on":
            return True
        if self.mode == "off":
            return False
        return feat_shards > 1


def as_sharded_update(
    value: Union["ShardedUpdateConfig", str, bool, None],
) -> "ShardedUpdateConfig":
    """Coerce a user-facing argument (``ShardedUpdateConfig``, a mode
    string, a bool, or ``None``) into a :class:`ShardedUpdateConfig`."""
    if value is None:
        return ShardedUpdateConfig()
    if isinstance(value, ShardedUpdateConfig):
        return value
    if isinstance(value, bool):
        return ShardedUpdateConfig(mode="on" if value else "off")
    if isinstance(value, str):
        return ShardedUpdateConfig(mode=value)
    raise TypeError(f"cannot interpret {value!r} as a ShardedUpdateConfig")


def _byzantine_rows(attack, honest, key, b: int, d: int):
    """The ``b`` byzantine workers' rows from the honest ``(h, width)``
    rows, as ``(1, width)`` where the attack gives every one of them the
    same row (its broadcast then fuses into the write that follows) and
    ``(b, width)`` elsewhere; the columns past ``d`` exactly zero."""
    h, width = honest.shape
    if attack is not None:
        byz = jnp.asarray(attack(honest, key))
    else:
        # no attack configured: byzantine nodes echo honest
        # gradients (cycled, so any b < n works)
        byz = jnp.tile(honest, ((b + h - 1) // h, 1))[:b]
    rows_given = 1 if byz.ndim == 1 or byz.shape[0] == 1 else b
    byz = jnp.broadcast_to(byz, (rows_given, width)).astype(honest.dtype)
    if width != d:
        # an attack need not map zero columns to zero (additive
        # noise): the pad tail of its rows is forced back to zero
        byz = jnp.where(jnp.arange(width) < d, byz, 0)
    return byz


# -- what the two one-device rounds share --------------------------------------
# Plain functions that trace into their caller: a jit around one would put its
# name into every op_name the benchmark's readers join on.


def _folded(shape):
    """``shape`` as whole (8, 128) tiles in row-major order wherever its
    size allows: ``(size / 128, 128)``. A row of a stack kept so is
    contiguous at one index of the stack; as one row of an ``(n, width)``
    array it is a sublane of every tile, ten times the cost to write and an
    eighth of every vreg to compute on. (Any width the stream kernels read
    in place is a multiple of 1024.) A boundary kept so is written by one
    pass and read by another in one layout, with no relaid copy of the
    whole stack between them."""
    size = math.prod(shape)
    return (size // 128, 128) if size % 1024 == 0 else tuple(shape)


def _one_device_layout(n: int, tree):
    """The row of a parameter (sub)tree on one device: as wide as the
    stream kernels read in place, with no padded copy of the whole matrix
    (:func:`~byzpy_tpu.ops.pallas_kernels.aligned_width`; ``d`` itself
    wherever they will not serve an ``n``-row matrix), the ``d`` real
    columns first and an exactly-zero tail after them. Folded wherever
    that width is whole tiles, and then the order of its columns is the
    round's own (:func:`~byzpy_tpu.utils.trees.row_layout`): every leaf
    that is whole tiles has a place to itself, in the order its gradient
    lies in memory, so that a worker's loop writes it there as it is made;
    elsewhere the order is ``ravel_pytree``'s. The same for every row, the
    aggregate and whatever flat state is carried."""
    from ..ops.pallas_kernels import aligned_width

    width = aligned_width(n, tree_size(tree))
    return row_layout(tree, width, folded=len(_folded((width,))) == 2)


def _write_row(stack, i, offsets, pieces, add=False):
    """Row ``i`` of a stack of rows, folded or flat, written piece by piece
    (``layout.place`` of one worker's gradient, first columns
    ``layout.offsets``): a leaf's gradient, whole tiles of the row, is
    written once, from where the backward pass left it, with no row-wide
    ``concatenate`` and no relayout of a weight gradient in front. With
    ``add`` the pieces are added to what the row holds (a parameter's
    gradient through a second link, ``Segment.reads``)."""
    # a row's first axis counts units of `lane` columns: 128 where rows are
    # folded, single columns where they are flat
    lanes = stack.shape[2:]
    lane = math.prod(lanes)
    for first, piece in zip(offsets, pieces):
        piece = jax.lax.expand_dims(piece.reshape(-1, *lanes), (0,))
        at = (i, first // lane, *(0 for _ in lanes))
        if add:
            piece = jax.lax.dynamic_slice(stack, at, piece.shape) + piece
        stack = jax.lax.dynamic_update_slice(stack, piece, at)
    return stack


def _set_byzantine_rows(stack, h: int, byz):
    """Rows ``h..n-1`` of the loop's own ``(n, *row)`` stack, folded or
    flat, set to what :func:`_byzantine_rows` made of its first h (the
    attack is the caller's ``(h, width)`` function: it is handed
    ``stack[:h].reshape(h, width)``, and a reduction over workers reads
    folded rows all the same). A folded row is whole tiles, so b rows cost b
    rows' bytes and the other rows are not touched."""
    n, *row = stack.shape
    return stack.at[h:].set(jnp.broadcast_to(byz.reshape(byz.shape[0], *row), (n - h, *row)))


def _update_leaves(opt, layout, agg, state, params):
    """``(params, state)`` after the optimizer's update of whole leaves:
    ``agg``, the aggregate in the layout's order, goes back to the tree
    first (``unravel`` reads its first ``d`` columns alone)."""
    updates, state = opt.update(layout.unravel(agg), state, params)
    return optax.apply_updates(params, updates), state


# -- a worker's loss and gradient, in passes that stay in fast memory ----------
# Shared by the two (n, d) rounds. The streamed round is not a caller: a
# language cell's worker holds one packed sequence.

#: The share of a TPU's fast memory (VMEM; ``get_tpu_info``) that one pass's
#: largest activation may take. Set from ``round.fwdbwd`` of a step of two
#: workers of 512 images on ONE v5e (ResNet-18 in float32, a stage-1
#: activation 256 KiB an image; 128 MiB of VMEM), passes of 512 / 256 / 128 /
#: 64 images: PERF.md section 3 has the four readings. 128 images (32 MiB, a
#: quarter) is what the share has to let through whole and 256 (a half) what
#: it has to split.
_PASS_BUDGET = 3 / 8


def _fast_memory_bytes(device) -> Optional[int]:
    """The fast memory (VMEM) of the TPU core a step is built for, and
    ``None`` for a device that is no TPU: nothing is known of a cache there
    that a pass could be sized for, and a step is then never split."""
    if device.platform != "tpu":
        return None
    from jax.experimental.pallas import tpu as pltpu

    with jax.default_device(device):  # whose kind get_tpu_info reads
        return pltpu.get_tpu_info().vmem_capacity_bytes


def _default_device():
    """The device a one-device step is built for: the one its caller's
    ``jax.default_device`` names, else the process's first."""
    dev = jax.config.jax_default_device
    return jax.devices(dev)[0] if dev is None or isinstance(dev, str) else dev


def _largest_activation_bytes(loss_fn, params, x, y) -> int:
    """Bytes of the largest array the forward pass of ``loss_fn`` makes for
    ONE example: one abstract trace of the forward at a batch of one (shapes
    alone: nothing compiles, and the backward is not traced), every result
    of every equation counted, those of the calls it holds too."""
    def struct(a, lead=None):
        return jax.ShapeDtypeStruct(a.shape if lead is None else (lead, *a.shape[1:]), a.dtype)

    def largest(jaxpr):
        sizes = [math.prod(v.aval.shape) * v.aval.dtype.itemsize
                 for eqn in jaxpr.eqns for v in eqn.outvars if hasattr(v.aval, "shape")]
        sizes += [largest(sub) for eqn in jaxpr.eqns
                  for sub in jax.core.jaxprs_in_params(eqn.params)]
        return max(sizes, default=0)

    return largest(jax.make_jaxpr(loss_fn)(
        jax.tree_util.tree_map(struct, params), struct(x, 1), struct(y, 1)).jaxpr)


def _passes_for(batch: int, bytes_per_example: int, budget: int) -> int:
    """The smallest divisor ``p`` of ``batch`` for which ``batch / p``
    examples' largest activation fits ``budget`` bytes: passes are equal,
    or the mean of their means is not the batch's mean. 1 where the whole
    batch fits, and 1 where not even one example does (no divisor fits:
    splitting buys nothing the rule can see)."""
    for p in range(1, batch + 1):
        if batch % p == 0 and (batch // p) * bytes_per_example <= budget:
            return p
    return 1


def _one_worker_of(xs, ys):
    """The shapes of one worker's batch of the stacked ``(n, B, ...)``
    batches (shapes alone: an index into a traced array is an op)."""
    return tuple(jax.ShapeDtypeStruct(a.shape[1:], a.dtype) for a in (xs, ys))


def _worker_passes(bundle, params, x, y, device) -> int:
    """In how many equal passes a worker's batch ``x, y`` (``(B, ...)``)
    goes through the model: from the batch's shape, the bundle's declaration
    and the device alone. 1 (the batch whole) unless the bundle SAYS that its
    loss is a mean of per-example terms (``ModelBundle.example_mean_loss``:
    only then is the gradient of the batch the mean of its parts'), the
    device is a TPU, and the batch's largest activation does not fit
    ``_PASS_BUDGET`` of its fast memory; then the fewest passes whose own
    do (:func:`_passes_for`)."""
    if not bundle.example_mean_loss:
        return 1
    fast = _fast_memory_bytes(device)
    if fast is None:
        return 1
    return _passes_for(x.shape[0], _largest_activation_bytes(bundle.loss_fn, params, x, y),
                       int(fast * _PASS_BUDGET))


def _worker_loss_and_grad(grad_of, passes: int):
    """``(params, x, y) -> (loss, gradient)`` of one worker, its batch
    taken in ``passes`` equal passes. With one pass it IS ``grad_of``, the
    very function: a program that does not split is the program it was,
    traced at the depth it was. With more, a ``scan`` (one trace of the
    model) over ``x.reshape(passes, B / passes, ...)`` that carries the
    float32 gradient tree, adds each pass's gradient into it in place and
    scales once by ``1 / passes``; the loss is the mean of the passes'
    losses. The same sum in another order, exact to float32 summation
    error, ONLY for a loss that is a mean over examples of per-example
    terms: :func:`_worker_passes` is who says how many."""
    if passes == 1:
        return grad_of

    def parts(a):
        return a.reshape(passes, a.shape[0] // passes, *a.shape[1:])

    def in_passes(params, x, y):
        def one_pass(total, part):
            loss, g = grad_of(params, *part)
            return jax.tree_util.tree_map(
                lambda t, leaf: t + leaf.astype(t.dtype), total, g), loss

        # stream.passes: no round.* name, so round.fwdbwd stays the innermost
        # round.* scope of everything the model computes
        with jax.named_scope("stream.passes"):
            total, losses = jax.lax.scan(
                one_pass,
                jax.tree_util.tree_map(lambda leaf: jnp.zeros(leaf.shape, jnp.float32), params),
                (parts(x), parts(y)))
            grads = jax.tree_util.tree_map(
                lambda t, leaf: (t / passes).astype(leaf.dtype), total, params)
            return jnp.mean(losses.astype(jnp.float32)).astype(losses.dtype), grads

    return in_passes


def _streamed_train_step(bundle, aggregate, cfg, *, attack, optimizer, grad_dtype, unstreamable):
    """:func:`build_ps_train_step` for a bundle that declares segments, on
    one device: the round whose working set is not ``(n, d)``, so that a
    parameter costs 8 bytes plus n rows of one segment
    (``docs/performance.md``, "A round that is not (n, d)").

    Forward for the h honest workers, one after another, keeping each
    segment's output (a block-boundary activation) for every one of them.
    Then from the loss head back to the first segment, for each segment:
    every honest worker's vector-Jacobian product of that segment, its
    forward recomputed from the boundary kept; the h gradients placed,
    folded, in the segment's ``(n, width_segment)`` stack; the byzantine
    rows written into it; the aggregate; the optimizer's update of that
    segment's leaves. The next segment starts only when this one's
    parameters are updated, so its n rows are dead by then: at most one
    segment's rows exist at a time, and no ``(n, d)`` array ever does.

    A segment that reads an earlier one's parameters (``Segment.reads``: a
    tied table) is handed them as they stand before the step; a worker's
    gradient of them through the reader starts the OWNER's row of that
    worker there and then (``stream.shared_rows``), and the owner's own
    turn, and any reader between, adds to it. So the owner's rows live from
    its first reader's turn to its own (the one exception to "one segment's
    rows at a time"), they hold both paths before the one aggregate and the
    one update, and nothing else of the parameters' size is kept.

    Where the sort kernel can form the byzantine rows itself (a trimmed
    mean or median, an attack that ``ops/coordinatewise.py`` declares
    formable in a kernel, ``b > 0``, and the gate serving the segment's
    ``(n, width_segment)`` matrix: ``coordinatewise.attacked_in_kernel``,
    ``robust.attacked_serves``, both asked here before anything traces)
    the stack is ``(h, width_segment)``, nothing is written into it but
    the honest gradients, and the aggregate is one call on it. Anything
    else keeps the three sweeps above, the (n, d) round's own
    ``_byzantine_rows`` and ``aggregate(matrix)``.

    Exact where aggregate and attack treat every column alone and the
    optimizer every leaf alone: ``ops/coordinatewise.py`` is the table of
    those, and anything it does not list (a Gram-type aggregate, a
    global-norm clip) or that ``unstreamable`` names as given (a
    ``pre_aggregate``, a forced flat update) is refused here.
    ``opt_state0`` is ``{segment: opt.init(subtree)}``.
    """
    from ..ops import coordinatewise, robust

    refused = coordinatewise.refusal(aggregate, attack, optimizer)
    refused.update({name: "given" for name, given in unstreamable.items() if given})
    if refused:
        raise ValueError(
            "a bundle that declares segments streams its round segment by segment on one "
            "device, which is exact only for what byzpy_tpu/ops/coordinatewise.py lists "
            "(AGGREGATES, ATTACKS, an optimizer marked leafwise(...); no pre-aggregator, no sharded "
            f"update); not listed there: {refused}. A Gram-type aggregate needs a second "
            "pass over the segments (ROADMAP.md)."
        )
    opt = optimizer or default_optimizer(cfg)
    segs = bundle.segments
    n, h, b = cfg.n_nodes, cfg.n_honest, cfg.n_byzantine
    last = len(segs) - 1
    def put(stack, value, i):
        return jax.lax.dynamic_update_index_in_dim(stack, value.reshape(stack.shape[1:]), i, 0)

    # each segment's own row, as the (n, d) round's, a segment at a time
    layouts = [_one_device_layout(n, bundle.params[seg.key]) for seg in segs]
    opt_state0 = {seg.key: opt.init(bundle.params[seg.key]) for seg in segs}

    def in_row_order(seg, layout):
        """An update that computes every element from the elements at its
        place alone (read off the optimizer's own jaxpr: SGD, momentum, Adam
        are such) gives the same elements in whatever order a leaf is handed
        to it: where the segment's row is whole tiles it is run on the leaves'
        tiles in the row's order. Any other is handed whole leaves."""
        if layout.unravel_tiles is None:
            return False
        whole = (bundle.params[seg.key], opt_state0[seg.key])
        tiles = jax.eval_shape(tile_views, whole)[0]
        return all(coordinatewise.is_elementwise(opt, sub, state) for sub, state in (whole, tiles))

    in_rows = [in_row_order(seg, layout) for seg, layout in zip(segs, layouts)]
    # Where the table declares aggregate and attack so and the gate serves a
    # segment's (n, width) matrix, nobody builds that matrix: the segment's
    # stack holds the h honest rows and the sort kernel forms the other b in
    # its body (ops/coordinatewise.attacked_in_kernel). Elsewhere the round
    # writes them, as the (n, d) round does.
    attacked = coordinatewise.attacked_in_kernel(aggregate, attack) if b else None
    rows_dtypes = [grad_dtype if grad_dtype is not None else layout.dtype for layout in layouts]
    formed = [attacked is not None and robust.attacked_serves(
        jax.ShapeDtypeStruct((h, layout.width), dtype), b)
        for layout, dtype in zip(layouts, rows_dtypes)]

    def train_step(params, opt_state, xs, ys, key):
        xs_h, ys_h = xs[:h], ys[:h]
        # A boundary is an array or a tree of arrays. An array that a segment
        # returns untouched (the very array it was handed) is kept ONCE, under
        # the boundary that made it: `made` lists the arrays the chain makes
        # (the boundary each comes from), `wires` which of them each
        # boundary's leaves are. Both are read off the chain as it is traced.
        wiring = {}

        def forward_of(x):
            outs, auxes, before = [], {}, {}
            made, wires = [], []
            for at, seg in enumerate(segs[:last]):
                with jax.named_scope("segment." + seg.key):
                    x = seg.apply(params[seg.key], x, *seg.read_of(params))
                if seg.aux:
                    x, auxes[seg.key] = x
                leaves, treedef = jax.tree_util.tree_flatten(x)
                here = {}
                for leaf in leaves:
                    if id(leaf) not in before:
                        made.append(at)
                        outs.append(leaf)
                    here[id(leaf)] = before.get(id(leaf), len(outs) - 1)
                if len(here) != len(leaves):
                    raise ValueError(
                        f"segment {seg.key!r} returns one array twice in its boundary")
                wires.append((treedef, [here[id(leaf)] for leaf in leaves]))
                before = here
            wiring.update(made=made, wires=wires)
            return outs, auxes

        # round.fwdbwd is the innermost round.* scope of everything the
        # model computes, here as in the (n, d) round; the segment_* scope
        # around it says which of the three passes an op belongs to
        with jax.named_scope("round.segment_fwd"), jax.named_scope("round.fwdbwd"):
            kept = jax.eval_shape(forward_of, xs_h[0])

            def one_forward(i, carry):
                with jax.named_scope("stream.boundary"):
                    x = xs_h[i]
                out = forward_of(x)
                with jax.named_scope("stream.boundary"):
                    return jax.tree_util.tree_map(
                        lambda stack, value: put(stack, value, i), carry, out)

            with jax.named_scope("stream.boundary"):
                stacks = [jax.lax.empty((h, *_folded(leaf.shape)), leaf.dtype) for leaf in kept[0]]
            vals, auxes = jax.lax.fori_loop(0, h, one_forward, (
                stacks, jax.tree_util.tree_map(
                    lambda leaf: jnp.zeros((h, *leaf.shape), leaf.dtype), kept[1])))
        made, wires = wiring["made"], wiring["wires"]

        def boundary(k, stack_of, i):
            """Boundary ``k`` of worker ``i``, from the stack ``stack_of(j)``
            that holds its array ``j``."""
            treedef, slots = wires[k]
            return jax.tree_util.tree_unflatten(
                treedef, [stack_of(j)[i].reshape(kept[0][j].shape) for j in slots])

        new_params, new_opt = {}, {}
        sum_sq = jnp.zeros((), jnp.float32)
        losses = head_aux = None
        # what flows back: the cotangent of every kept array, once a segment
        # that reads the array has run backwards
        cots = [None] * len(vals)
        # the rows of a segment whose parameters a later one reads, from that
        # reader's turn to its own
        at_key = {seg.key: k for k, seg in enumerate(segs)}
        started = {}

        def fresh_rows(k):
            return jax.lax.empty(
                (h if formed[k] else n, *_folded((layouts[k].width,))), rows_dtypes[k])
        # A segment's leaves are handed to its loop through the barrier that
        # closes the segment after it (the head's: through one with the last
        # boundary). Whatever the compiler derives from them alone (a
        # weight's transposed copy, hoisted out of the loop) then cannot be
        # made before that point, and so not for all segments at once.
        reads = wires[last - 1][1]
        (sub, read), held_back = jax.lax.optimization_barrier(
            ((params[segs[last].key], segs[last].read_of(params)), [vals[j] for j in reads]))
        for j, stack in zip(reads, held_back):
            vals[j] = stack
        for k in range(last, -1, -1):
            with jax.named_scope("segment." + segs[k].key):
                seg, layout = segs[k], layouts[k]
                width = layout.width
                reads = wires[k - 1][1] if k else []
                # An array this segment's input holds was made by the segment
                # before it, or handed on by it. The first is read here for the
                # last time: its cotangent is written over it. The second is still
                # to be read by earlier segments, so its cotangent has a stack of
                # its own (from the first segment, going backwards, that reads it).
                over = [made[j] == k - 1 for j in reads]
                at = {j: place for place, j in enumerate(reads)}
                io = [vals[j] if last_read else cots[j] if cots[j] is not None
                      else jax.lax.empty(vals[j].shape, vals[j].dtype)
                      for j, last_read in zip(reads, over)]

                def one_backward(i, carry, k=k, seg=seg, layout=layout, sub=sub, read=read,
                                 at=at, over=over, cots=tuple(cots), vals=tuple(vals),
                                 adds=seg.key in started, known=frozenset(started)):
                    # worker i's input to this segment is read from the stacks of
                    # boundaries kept, and the cotangent of that input is written
                    # over it: after the loop the stack holds what the segment
                    # before this one pulls back, and nothing else was allocated
                    with jax.named_scope("round.segment_recompute"), \
                            jax.named_scope("round.fwdbwd"):
                        # stream.boundary: what a worker's turn reads of the
                        # stacks and writes back to them; stream.rows: its
                        # gradient placed in the segment's rows
                        with jax.named_scope("stream.boundary"):
                            x = boundary(k - 1, lambda j: (
                                carry["io"][at[j]] if over[at[j]] else vals[j]), i
                            ) if k else xs_h[i]
                            y = ys_h[i] if k == last else None
                        if k == last:
                            def apply(p, x, *read):
                                return seg.apply(p, x, y, *read)
                        elif seg.aux:
                            def apply(p, x, *read):
                                return seg.apply(p, x, *read)[0]
                        else:
                            apply = seg.apply
                        if k:
                            out, pullback, *aux = jax.vjp(
                                apply, sub, x, *read, has_aux=seg.aux and k == last)
                        else:  # the batch itself: nothing flows back into it
                            out, pullback = jax.vjp(lambda p: apply(p, x), sub)
                    with jax.named_scope("round.segment_bwd"), jax.named_scope("round.fwdbwd"):
                        # what an array handed on pulls back so far stands in the
                        # stack this loop writes, not in the one it started from
                        with jax.named_scope("stream.boundary"):
                            back = jnp.ones_like(out) if k == last else boundary(k, lambda j: (
                                carry["io"][at[j]] if j in at and not over[at[j]] else cots[j]), i)
                        pulled = pullback(back)
                        with jax.named_scope("stream.rows"):
                            carry = dict(carry, rows=_write_row(
                                carry["rows"], i, layout.offsets,
                                layout.place(pulled[0], grad_dtype), add=adds))
                        if read:
                            # what reaches another segment's parameters through
                            # this one: into THEIR row of worker i, kept to their turn
                            with jax.named_scope("stream.shared_rows"):
                                carry["shared"] = {
                                    key_: _write_row(
                                        carry["shared"][key_], i, layouts[at_key[key_]].offsets,
                                        layouts[at_key[key_]].place(pulled[2][key_], grad_dtype),
                                        add=key_ in known)
                                    for key_ in seg.reads}
                        with jax.named_scope("stream.boundary"):
                            if k:
                                carry["io"] = [put(stack, leaf, i) for stack, leaf in zip(
                                    carry["io"], jax.tree_util.tree_leaves(pulled[1]))]
                            if k == last:
                                carry["losses"] = put(carry["losses"], out, i)
                                if aux:
                                    carry["head_aux"] = jax.tree_util.tree_map(
                                        lambda stack, value: put(stack, value, i),
                                        carry["head_aux"], aux[0])
                    return carry

                carry = {"rows": started.pop(seg.key) if seg.key in started else fresh_rows(k)}
                if read:
                    carry["shared"] = {key_: started[key_] if key_ in started
                                       else fresh_rows(at_key[key_]) for key_ in seg.reads}
                if k:
                    carry["io"] = io
                if k == last:
                    loss0 = jax.eval_shape(
                        lambda x, seg=seg, sub=sub, read=read: seg.apply(sub, x, ys_h[0], *read),
                        jax.tree_util.tree_unflatten(
                            wires[k - 1][0], [kept[0][j] for j in wires[k - 1][1]]))
                    if seg.aux:
                        loss0, aux0 = loss0
                        carry["head_aux"] = jax.tree_util.tree_map(
                            lambda leaf: jnp.zeros((h, *leaf.shape), leaf.dtype), aux0)
                    carry["losses"] = jnp.zeros((h,), loss0.dtype)
                carry = jax.lax.fori_loop(0, h, one_backward, carry)
                started.update(carry.get("shared", {}))
                losses = carry.get("losses", losses)
                head_aux = carry.get("head_aux", head_aux)
                if formed[k]:
                    with jax.named_scope("round.aggregate"):
                        agg = attacked(carry["rows"].reshape(h, width), b=b).astype(layout.dtype)
                else:
                    with jax.named_scope("round.build_matrix"):
                        stack = carry["rows"]
                        if b:
                            stack = _set_byzantine_rows(stack, h, _byzantine_rows(
                                attack, stack[:h].reshape(h, width), jax.random.fold_in(key, k),
                                b, layout.d))
                        matrix = stack.reshape(n, width)
                    with jax.named_scope("round.aggregate"):
                        agg = aggregate(matrix).astype(layout.dtype)
                with jax.named_scope("round.update"):
                    # (the columns past d are exactly zero: they add nothing to
                    # the norm, and unravel reads the first d alone)
                    if in_rows[k]:
                        # one pass a leaf: its stretch of the row read where the
                        # kernel wrote it, parameter and state seen as those tiles
                        (tiles, state), agg = tile_views((sub, opt_state[seg.key]), beside=agg)
                        grads = layout.unravel_tiles(agg)
                        sum_sq = sum_sq + sum(
                            jnp.sum(jnp.square(leaf)).astype(jnp.float32)
                            for leaf in jax.tree_util.tree_leaves(grads))
                        updates, state = opt.update(grads, state, tiles)
                        done = jax.tree_util.tree_map(
                            lambda view, leaf: leaf_view(view, leaf.shape),
                            (optax.apply_updates(tiles, updates), state),
                            (sub, opt_state[seg.key]))
                    else:
                        sum_sq = sum_sq + jnp.sum(jnp.square(agg)).astype(jnp.float32)
                        done = _update_leaves(opt, layout, agg, opt_state[seg.key], sub)
                    if k:
                        # the segment before this one starts from the cotangents
                        # only once this one's leaves are updated: its rows are
                        # dead by then, and the next rows take their place
                        done, flowing, (sub, read) = jax.lax.optimization_barrier(
                            (done, carry["io"],
                             (params[segs[k - 1].key], segs[k - 1].read_of(params))))
                        for j, stack in zip(reads, flowing):
                            cots[j] = stack
                    new_params[seg.key], new_opt[seg.key] = done
        with jax.named_scope("round.update"):
            metrics = {"honest_loss": jnp.mean(losses), "agg_grad_norm": jnp.sqrt(sum_sq)}
            if head_aux is not None:
                auxes = dict(auxes, **{segs[last].key: head_aux})
            if auxes:
                metrics["segment_aux"] = auxes
        order = list(params)
        return ({key_: new_params[key_] for key_ in order},
                {key_: new_opt[key_] for key_ in order}, metrics)

    return train_step, opt_state0


def _one_device_train_step(
        bundle, aggregate, cfg, *, attack, pre_aggregate, optimizer, grad_dtype, flat_update):
    """:func:`build_ps_train_step` on one device, for a bundle without
    segments: the ``(n, d_pad)`` round.

    Nothing reads a byzantine worker's own gradient or loss (its row of
    the matrix is the attack's, or an honest row echoed; ``honest_loss`` is
    the honest mean), so forward/backward runs for the first ``h = n_nodes -
    n_byzantine`` workers only, one after another (a ``fori_loop``;
    ``xs[h:]``, ``ys[h:]`` are not read): under ``vmap`` the TPU compiler
    turns each convolution's per-worker weight gradient into one grouped
    convolution over the worker axis and relays activations out around the
    merged-batch convolutions, at about twice the cost a worker for
    ResNet-18 and none less for an MLP (``docs/performance.md``).
    Signature, shapes, state, metrics and values are those of a round that
    computes all n rows and overwrites b of them. A worker's batch goes
    through the model whole, or in equal passes where whole it would not
    stay in the chip's fast memory (:func:`_worker_passes` says how many,
    :func:`_worker_loss_and_grad` runs them).

    The loop carries the n-row stack, each row ``d_pad`` wide and folded
    wherever that is whole tiles (:func:`_one_device_layout`,
    :func:`_folded`: ``(n, d_pad / 128, 128)``), and writes row i of it
    leaf by leaf (:func:`_write_row`); the byzantine rows (their tail
    forced to zero: :func:`_byzantine_rows`) are written into rows h..n-1
    of the same buffer (:func:`_set_byzantine_rows`), and
    ``pre_aggregate`` / ``aggregate`` are handed ``stack.reshape(n,
    d_pad)``. Who relays out is decided by the compiler from what that
    function does with it: the sort family's kernel folds its argument
    again and reads the loop's buffer; a consumer that wants the workers in
    sublanes (Multi-Krum's Gram, any XLA sort) gets the one relayout pass
    it needs, where it reads the matrix (``docs/performance.md``, "A
    folded row").

    The aggregate's zero tail is cut before the update of the parameter
    tree. ``flat_update`` (a caller's ``sharded_update="on"``; there is no
    grid to shard over here) carries ``(flat_params, inner_opt_state)``
    over the ``d_pad``-wide flat vector in the layout's order instead, and
    re-zeroes the tail.
    """
    opt = optimizer or default_optimizer(cfg)
    grad_of = jax.value_and_grad(bundle.loss_fn)
    n, h, b = cfg.n_nodes, cfg.n_honest, cfg.n_byzantine
    layout = _one_device_layout(n, bundle.params)
    d, d_pad = layout.d, layout.width
    if flat_update:
        flat0 = layout.ravel(bundle.params)
        opt_state0 = (flat0, opt.init(flat0))
    else:
        opt_state0 = opt.init(bundle.params)

    def train_step(params, opt_state, xs, ys, key):
        # Every op lies in exactly one innermost round.* scope
        # (observability.catalog.SCOPES): the label rides each HLO
        # instruction's op_name metadata, and the benchmark reads
        # per-scope device time through the compiled text (the note in
        # build_serving_ps_step says how).
        with jax.named_scope("round.fwdbwd"):
            # With no byzantine worker the slices are the whole arrays and
            # emit nothing.
            xs_h, ys_h = xs[:h], ys[:h]
            loss0, _ = jax.eval_shape(lambda: grad_of(params, xs_h[0], ys_h[0]))
            worker_grad = _worker_loss_and_grad(grad_of, _worker_passes(
                bundle, params, *_one_worker_of(xs_h, ys_h), _default_device()))

            def one_worker(i, carry):
                losses, grads = carry
                loss, g = worker_grad(params, xs_h[i], ys_h[i])
                pieces = layout.place(g, grad_dtype)
                losses = jax.lax.dynamic_update_index_in_dim(losses, loss, i, 0)
                return losses, _write_row(grads, i, layout.offsets, pieces)

            # (an uninitialised buffer: every row is written, h here and b
            # by the attack; zeros would cost a pass over it)
            losses, stack = jax.lax.fori_loop(0, h, one_worker, (
                jnp.zeros((h,), loss0.dtype),
                jax.lax.empty((n, *_folded((d_pad,))),
                              grad_dtype if grad_dtype is not None else layout.dtype)))
        with jax.named_scope("round.build_matrix"):
            if b:
                stack = _set_byzantine_rows(
                    stack, h, _byzantine_rows(attack, stack[:h].reshape(h, d_pad), key, b, d))
            matrix = stack.reshape(n, d_pad)
        if pre_aggregate is not None:
            with jax.named_scope("round.pre_aggregate"):
                matrix = pre_aggregate(matrix)
        with jax.named_scope("round.aggregate"):
            agg_flat = aggregate(matrix).astype(layout.dtype)
        with jax.named_scope("round.update"):
            if d_pad != d and flat_update:
                # the flat state is carried d_pad wide: pin the pad tail to
                # exactly zero so padded params and momenta never drift (and
                # the norm matches the unpadded round)
                agg_flat = jnp.where(jnp.arange(d_pad) < d, agg_flat, 0.0)
            elif d_pad != d:
                # the state mirrors the parameter tree: the tail is cut
                agg_flat = agg_flat[:d]
            agg_norm = jnp.sqrt(jnp.sum(jnp.square(agg_flat)))
            if flat_update:
                flat_params, inner = opt_state
                updates, inner = opt.update(agg_flat, inner, flat_params)
                new_flat = optax.apply_updates(flat_params, updates)
                params = layout.unravel(new_flat[:d])
                opt_state = (new_flat, inner)
            else:
                params, opt_state = _update_leaves(opt, layout, agg_flat, opt_state, params)
            metrics = {"honest_loss": jnp.mean(losses), "agg_grad_norm": agg_norm}
        return params, opt_state, metrics

    return train_step, opt_state0


def _select_byzantine_rows(matrix, h: int, byz):
    """The ``(n, width)`` matrix of all n workers' rows with rows h..n-1
    replaced by what :func:`_byzantine_rows` made of its first h: one
    elementwise pass over it, in place. (Its rows are sublanes of the
    TPU's (8, 128) tiles, so a two-row ``dynamic_update_slice`` touches
    every tile too, as 1 KB DMA chunks, and measured slower than this pass
    or the concatenate it replaces.) A pure function of the rows: it runs
    node-sharded in the uncompressed fabric and feature-sharded after a
    compressed transpose, and every attack is coordinate-wise over the node
    axis, so both layouts partition cleanly."""
    n = matrix.shape[0]
    at = jnp.arange(n)[:, None]
    if byz.shape[0] == 1:
        return jnp.where(at >= h, byz, matrix)
    for r in range(n - h):
        matrix = jnp.where(at == h + r, byz[r], matrix)
    return matrix


def _mesh_train_step(bundle, aggregate, cfg, mesh, *, attack, pre_aggregate, optimizer,
                     grad_dtype, comm, su):
    """:func:`build_ps_train_step` on a mesh: the ``(n, d)`` round across
    chips, a segmented bundle's too (its ``loss_fn`` is the chain's).

    Batches are constrained to ``P("nodes", ...)`` and all n workers'
    gradients are computed: the node axis carries every worker, a
    byzantine worker's chip runs in lockstep with the others (skipping it
    frees no time) and h need not divide the axis. Where the node axis
    divides n, each chip runs the ``n / k`` workers it holds one after
    another (a ``shard_map`` over the node axis around a ``lax.map``,
    whose loop writes row i of the chip's ``(n / k, d)`` block; any
    further mesh axis, a worker's batch sharded over it, is left to the
    partitioner): side by side under ``vmap`` they cost a convolutional
    model about twice as much a worker (:func:`_one_device_train_step`
    says why). Where it does not, no chip holds whole workers and the
    partitioner is handed a ``vmap`` over all n. The choice is read off
    the mesh's shape and n alone. A worker of the loop takes its batch in
    passes as on one device (:func:`_worker_passes`, asked for the mesh's
    first device). On a mesh with a further axis the rule is handed the
    worker's WHOLE batch, as the body of the ``shard_map`` sees it: a chip
    then holds ``1 / axis`` of a pass, and the passes are more and smaller
    than the chip needs (no cell runs such a mesh).

    Rows are ``d`` wide and flat, in ``ravel_pytree``'s order (the note at
    ``layout`` below has the one exception), cross the wire so, and the
    byzantine ones are selected into the matrix in one pass
    (:func:`_select_byzantine_rows`). The matrix then transposes to feature
    sharding, is padded to the sharded update's grid where that is on, and
    ``pre_aggregate`` / ``aggregate`` run chip-local per coordinate.

    ``comm`` (a :class:`~byzpy_tpu.parallel.quantization.CommPrecision`)
    compresses the gradient-transpose wire traffic — the round's dominant
    collective at ``d >= 1e5``: the stacked gradient matrix is encoded
    *before* the node->feature resharding constraint, so the all-to-all
    XLA inserts moves coded bytes (int8/fp8 codes + per-block f32 scales,
    packed s4 nibbles at half a byte per value, or bf16) instead of f32,
    and every device decodes after the transpose. Aggregation always runs
    on the decoded full-precision matrix. ``"off"`` produces a program
    bit-identical to the uncompressed fabric. With ``error_feedback=True``
    on the precision, each node's ``(n, d)`` residual rides the carried
    state (node-sharded, donated): round ``t`` transmits ``g_t + e_{t-1}``
    and carries ``e_t = (g_t + e_{t-1}) - decode(encode(g_t + e_{t-1}))``,
    so the per-node transmitted stream telescopes to the true gradient
    stream plus one round's bounded error — sub-int8 compression stops
    compounding (the EF convergence study in
    ``benchmarks/ef_convergence_study.py`` measures exactly this).

    ``su`` (a :class:`ShardedUpdateConfig`) controls the weight update's
    layout. When active, the flat param vector is padded to the shard
    grid (and to the quantization block for a blockwise params gather),
    ``opt_state0`` is ``(flat_params, inner_opt_state)`` over the padded
    FLAT vector, carried feature-sharded — each chip owns the
    authoritative exact shard of the flat params and of every optimizer
    moment — and ``train_step`` applies the update per shard, all-gathers
    only the refreshed flat params (optionally compressed), and unravels
    once. The returned params pytree stays replicated either way, so
    callers thread state identically.

    Error feedback (of the transpose or of the gather) changes the
    carried-state STRUCTURE: ``opt_state0`` becomes ``(base_opt_state,
    ef_state)`` and the step returns the updated residuals in the same
    slot — callers thread it opaquely. The aggregated-gradient norm is
    computed shard-locally as a psum of per-shard partial sums of squares:
    the aggregated gradient is never gathered just for the norm.
    """
    opt = optimizer or default_optimizer(cfg)
    gather_p = as_comm_precision(su.param_gather_precision)
    grad_of = jax.value_and_grad(bundle.loss_fn)
    n, h, b = cfg.n_nodes, cfg.n_honest, cfg.n_byzantine
    axis = node_axis(mesh)
    chips = mesh.shape[axis]  # along the node axis: each holds n / chips workers
    # extra mesh axes join in: per-node batches shard over the FIRST
    # extra axis (intra-node data parallelism — XLA psums the
    # batch-mean gradient automatically), and the aggregation matrix
    # feature-shards over ALL axes so no chip idles during the
    # robust reduce (a 1-D mesh degenerates to the plain layout)
    extra = tuple(
        a for a in mesh.axis_names if a != axis and mesh.shape[a] > 1
    )
    node_spec = NamedSharding(mesh, P(axis, *extra[:1]))
    feat_spec = NamedSharding(mesh, P(None, (axis, *extra)))
    # rows of the stacked (n, d) gradient matrix live on the node axis
    # before the transpose; pinning the encoded payload there first
    # forces the reshard (the wire hop) to move the COMPRESSED tensor
    # — with only the post-transpose constraint XLA may reshard the
    # f32 input and encode/decode locally, moving full-precision bytes
    row_spec = NamedSharding(mesh, P(axis))
    # The flat (d,) layout matching the aggregation matrix's feature
    # columns: a (d,) vector sharded over (axis, *extra) lines up
    # coordinate-for-coordinate with the feature-sharded (n, d) matrix, so
    # opt.update consumes the aggregate with NO reshard at all. The norm
    # metric reduces over it shard-locally in both update modes, and the
    # sharded update carries state in it.
    flat_sharding = NamedSharding(mesh, P((axis, *extra)))
    repl_sharding = NamedSharding(mesh, P())
    feat_shards = math.prod(mesh.shape[a] for a in (axis, *extra))
    su_on = su.resolve(feat_shards)

    d = tree_size(bundle.params)
    # The rows cross the wire d wide; after the transpose the matrix, the
    # aggregate and the carried flat state have d_pad columns, of which
    # the last d_pad - d are exactly zero (the tail is re-zeroed after the
    # aggregate regardless).
    d_pad = d
    if su_on and feat_shards > 1:
        # the shard grid, so every chip owns an equal slice; blockwise
        # gathers (int8/fp8/s4) pad to the quantization block too, so no
        # shard ever splits a block (scales shard alongside the codes,
        # and the packed s4 payload's half-length stays grid-divisible)
        pad_grid = feat_shards * (gather_p.block if gather_p.blockwise else 1)
        d_pad = -(-d // pad_grid) * pad_grid
    # (a d that is whole tiles takes row_layout's tile order here too, as
    # it did when one builder served every round: ROADMAP.md, D18)
    layout = row_layout(bundle.params, d, folded=d % 1024 == 0)
    param_dtype = layout.dtype

    if su_on:
        # optax init builds state via zeros_like, so every (d_pad,) moment
        # is BORN sharded like the flat params — nothing replicated to
        # re-slice later; scalar leaves (e.g. Adam's count) stay tiny.
        # The carried state leads with each chip's authoritative flat
        # param shard: re-deriving it from ravel(params) per round would
        # be free in principle (a local slice of the replicated pytree),
        # but GSPMD partitions the ravel concat into a d-size all-reduce
        # however the pytree/flat constraints are pinned — one extra
        # d_pad/g buffer per chip buys a clean single-gather program AND
        # makes a lossy params gather safe (the exact shard never passes
        # through the compressed wire).
        flat_padded0 = jax.device_put(
            jnp.pad(layout.ravel(bundle.params), (0, d_pad - d)), flat_sharding)
        opt_state0 = (flat_padded0, opt.init(flat_padded0))
    else:
        opt_state0 = opt.init(bundle.params)

    # The EF residuals are ROUND STATE: they live beside the optimizer
    # state (donated with it, feature-/node-sharded like the tensors
    # they compensate) and change the carried-state structure only when
    # EF is actually on — the default round's opt_state is untouched.
    ef_transpose = comm.enabled and comm.error_feedback
    ef_gather = su_on and gather_p.enabled and gather_p.error_feedback
    ef0 = {}
    if ef_transpose:
        ef0["transpose"] = jax.device_put(
            jnp.zeros((n, d), grad_dtype if grad_dtype is not None else param_dtype), row_spec)
    if ef_gather:
        ef0["gather"] = jax.device_put(jnp.zeros((d_pad,), param_dtype), flat_sharding)
    if ef0:
        opt_state0 = (opt_state0, ef0)

    def train_step(params, opt_state, xs, ys, key):
        # (scopes: as in the one-device round)
        ef_state = {}
        if ef0:
            opt_state, ef_state = opt_state
        with jax.named_scope("round.fwdbwd"):
            xs = jax.lax.with_sharding_constraint(xs, node_spec)
            ys = jax.lax.with_sharding_constraint(ys, node_spec)

            # the loop's workers alone take their batch in passes: the vmap
            # over all n is left to the partitioner as it is
            passes = _worker_passes(
                bundle, params, *_one_worker_of(xs, ys), mesh.devices.flat[0]
            ) if n % chips == 0 else 1
            worker_grad = _worker_loss_and_grad(grad_of, passes)

            def per_node_row(params, x, y):
                loss, g = worker_grad(params, x, y)
                return loss, layout.ravel(g, grad_dtype)

            def chip_rows(params, xs, ys):
                # one chip's block of the node axis: the workers it holds one
                # after another, row i of the block written as it is made
                return jax.lax.map(lambda held: per_node_row(params, *held), (xs, ys))

            if n % chips == 0:
                # Every chip runs the same loop over the workers it holds,
                # in lockstep with the others; any further mesh axis (a
                # worker's batch sharded over it) is left to the partitioner.
                # (check_vma off: nothing in the body crosses chips, and
                # tracking what varies over the axis through every op of the
                # model is paid in each run's trace.)
                losses, grads = jax.shard_map(
                    chip_rows, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
                    out_specs=(P(axis), P(axis)), axis_names={axis},
                    check_vma=False)(params, xs, ys)
            else:
                # the node axis does not divide n: no chip holds whole
                # workers, the partitioner splits a vmap over all n
                losses, grads = jax.vmap(per_node_row, in_axes=(None, 0, 0))(
                    params, xs, ys
                )
        if comm.enabled:
            # Compressed fabric: every node's RAW gradient row crosses the
            # wire encoded (exactly what a deployment ships — byzantine
            # nodes transmit too), and the attack/masking runs on the
            # decoded, feature-sharded rows: the omniscient adversary sees
            # the wire view of the honest gradients. The encoded payload
            # is pinned to the node layout and re-pinned to the feature
            # layout (the reshard between the two constraints IS the wire
            # hop), and the decoded matrix is constrained too, else the
            # partitioner replicates the aggregation input with an (n, d)
            # f32 all-reduce that dwarfs the transpose.
            with jax.named_scope("round.transpose"):
                if ef_transpose:
                    # EF: the wire carries g + e, the new residual stays
                    # node-sharded beside the optimizer state
                    grads, new_tr = reshard_q_ef(
                        grads, ef_state["transpose"], row_spec, feat_spec,
                        precision=comm,
                    )
                    ef_state = {**ef_state, "transpose": new_tr}
                else:
                    grads = reshard_q(grads, row_spec, feat_spec, precision=comm)
        with jax.named_scope("round.build_matrix"):
            matrix = grads
            if b:
                matrix = _select_byzantine_rows(
                    grads, h, _byzantine_rows(attack, grads[:h], key, b, d))
        # Gradient transpose: node-sharded rows -> feature-sharded
        # columns (XLA lowers this constraint to an all_to_all over
        # ICI), so the robust aggregation below is chip-local per
        # coordinate.
        with jax.named_scope("round.transpose"):
            matrix = jax.lax.with_sharding_constraint(matrix, feat_spec)
        if d_pad != d:
            # zero-pad the feature axis to the shard grid BEFORE the
            # robust reduce
            with jax.named_scope("round.build_matrix"):
                matrix = jnp.pad(matrix, ((0, 0), (0, d_pad - d)))
                matrix = jax.lax.with_sharding_constraint(matrix, feat_spec)
        if pre_aggregate is not None:
            with jax.named_scope("round.pre_aggregate"):
                matrix = pre_aggregate(matrix)
        with jax.named_scope("round.aggregate"):
            agg_flat = aggregate(matrix).astype(param_dtype)
            agg_flat = jax.lax.with_sharding_constraint(agg_flat, flat_sharding)
        with jax.named_scope("round.update"):
            if d_pad != d:
                # the flat state is carried d_pad wide: pin the pad tail to
                # exactly zero so padded params/momenta never drift (and
                # the norm below matches the unpadded round)
                agg_flat = jnp.where(jnp.arange(d_pad) < d, agg_flat, 0.0)
                agg_flat = jax.lax.with_sharding_constraint(agg_flat, flat_sharding)
            # shard-local norm: per-shard partial sums of squares + a scalar
            # psum — the aggregated gradient is never gathered for a metric
            agg_norm = jnp.sqrt(jnp.sum(jnp.square(agg_flat)))
            if su_on:
                flat_params, inner = opt_state
                flat_params = jax.lax.with_sharding_constraint(flat_params, flat_sharding)
                updates, inner = opt.update(agg_flat, inner, flat_params)
                new_flat = optax.apply_updates(flat_params, updates)
                new_flat = jax.lax.with_sharding_constraint(new_flat, flat_sharding)
                inner = jax.tree_util.tree_map(
                    lambda leaf: jax.lax.with_sharding_constraint(
                        leaf, flat_sharding
                    )
                    if getattr(leaf, "shape", None) == (d_pad,)
                    else leaf,
                    inner,
                )
                # The sharded round's ONE parameter collective: all-gather
                # the refreshed flat params from the feature shards back to
                # every chip (optionally compressed on the wire — the exact
                # shard each chip owns stays in the carried opt state, so
                # gather loss never compounds across rounds; with EF the
                # gather residual rides ``ef_state`` and dithers the replica
                # error around zero).
                with jax.named_scope("round.param_gather"):
                    if ef_gather:
                        gathered, new_r = reshard_q_ef(
                            new_flat, ef_state["gather"], flat_sharding, repl_sharding,
                            precision=gather_p,
                        )
                        ef_state = {**ef_state, "gather": new_r}
                    else:
                        gathered = reshard_q(
                            new_flat, flat_sharding, repl_sharding, precision=gather_p)
                params = layout.unravel(gathered[:d])
                opt_state = (new_flat, inner)
            else:
                params, opt_state = _update_leaves(opt, layout, agg_flat, opt_state, params)
                if n % chips == 0:
                    # the loop reads the parameters replicated: handed back
                    # any other way, the next call is another program
                    params = jax.lax.with_sharding_constraint(params, repl_sharding)
            metrics = {
                "honest_loss": jnp.mean(losses[:h]),
                "agg_grad_norm": agg_norm,
            }
            # shard-local residual-energy metrics (the convergence study
            # watches these stay bounded — a drifting residual is the
            # "EF compounding" failure mode)
            for name in ef0:
                metrics[f"ef_{name}_norm"] = jnp.sqrt(
                    jnp.sum(jnp.square(ef_state[name].astype(jnp.float32))))
            if ef0:
                opt_state = (opt_state, ef_state)
        return params, opt_state, metrics

    return train_step, opt_state0


def build_ps_train_step(
    bundle: ModelBundle,
    aggregate: AggFn,
    cfg: PSStepConfig,
    *,
    attack: Optional[AttackFn] = None,
    pre_aggregate: Optional[PreAggFn] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    mesh: Optional[Mesh] = None,
    grad_dtype: Any = None,
    comm_precision: Any = None,
    sharded_update: Any = None,
) -> Tuple[Callable, Any]:
    """Build ``(train_step, opt_state0)``: decide which round a call gets.

    ==========================  ==========  =================================
    ``bundle``                  ``mesh``    the round
    ==========================  ==========  =================================
    declares ``segments``       none        :func:`_streamed_train_step`
    no segments                 none        :func:`_one_device_train_step`
    either                      given       :func:`_mesh_train_step`
    ==========================  ==========  =================================

    ``mesh=None`` means the default mesh (``configs.mesh``), and one
    device where none is set. Each round's docstring says what its program
    is; what a caller has to know is here.

    ``train_step(params, opt_state, xs, ys, key)`` expects per-node batches
    stacked on a leading node axis: ``xs: (n_nodes, B, ...)``,
    ``ys: (n_nodes, B)``. It returns ``(params, opt_state, metrics)``,
    metrics with the mean honest loss (``honest_loss``), the
    aggregated-gradient norm (``agg_grad_norm``) and, from the streamed
    round, what its segments report (``segment_aux``).

    ``pre_aggregate``, ``aggregate`` and ``attack`` see ``(·, d_pad)``
    matrices whose columns are the parameters in an order the round fixes
    (the same for every row and for the aggregate), the ``d`` real columns
    first and an exactly-zero tail after them; the streamed round hands
    them one segment's columns at a time. They must map all-zero columns
    to zero and give the other columns what they would give from the
    ``(n, d)`` matrix with its columns in the same order: true of anything
    that works coordinate by coordinate or reads rows through norms and
    inner products, so of every shipped aggregator, pre-aggregator and
    attack (``docs/performance.md``, "The contract an aggregator or
    pre-aggregator meets"). The streamed round is exact only for what
    ``ops/coordinatewise.py`` lists, and raises a ``ValueError`` that
    names that table for anything else (a Gram-type aggregate, a
    ``pre_aggregate``, a global-norm clip, ``sharded_update="on"``).

    ``opt_state0``, which callers thread opaquely: the streamed round's is
    ``{segment: opt.init(subtree)}``; the other two rounds' is
    ``opt.init(params)``, or ``(flat_params, opt.init(flat_params))`` over
    the padded flat vector where the sharded update is on
    (``sharded_update``: a :class:`ShardedUpdateConfig`, a mode string, a
    bool, or ``None`` = auto: on where the mesh's feature grid spans more
    than one chip; on one device only a caller's ``"on"`` turns it on).
    ``comm_precision`` (``"off"``/``"bf16"``/``"int8"``/``"fp8"``/
    ``"fp8_e5m2"``/``"s4"`` or a
    :class:`~byzpy_tpu.parallel.quantization.CommPrecision`) compresses
    the mesh round's gradient transpose; one device has no wire and
    ignores it. With error feedback on either precision the mesh round's
    state is ``(that, ef_state)``.
    """
    comm = as_comm_precision(comm_precision)
    su = as_sharded_update(sharded_update)
    if not 0 <= cfg.n_byzantine < cfg.n_nodes:
        raise ValueError(
            f"need 0 <= n_byzantine < n_nodes (got {cfg.n_byzantine}/{cfg.n_nodes})")
    if mesh is None:
        from ..configs.mesh import get_default_mesh

        mesh = get_default_mesh()
    if mesh is not None:
        return _mesh_train_step(
            bundle, aggregate, cfg, mesh, attack=attack, pre_aggregate=pre_aggregate,
            optimizer=optimizer, grad_dtype=grad_dtype, comm=comm, su=su)
    forced_flat = su.resolve(1)  # no grid here: only a caller's "on"
    if bundle.segments is not None:
        # one device and a model that is a chain: segment by segment
        return _streamed_train_step(
            bundle, aggregate, cfg, attack=attack, optimizer=optimizer, grad_dtype=grad_dtype,
            unstreamable={"pre_aggregate": pre_aggregate is not None, "sharded_update": forced_flat})
    return _one_device_train_step(
        bundle, aggregate, cfg, attack=attack, pre_aggregate=pre_aggregate,
        optimizer=optimizer, grad_dtype=grad_dtype, flat_update=forced_flat)


def build_serving_ps_step(
    bundle: ModelBundle,
    masked_aggregate: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    *,
    optimizer: Optional[optax.GradientTransformation] = None,
    learning_rate: float = 0.05,
    momentum: float = 0.9,
    mesh: Optional[Mesh] = None,
) -> Tuple[Callable, Any]:
    """Build the serving tier's bucketed update step.

    Unlike :func:`build_ps_train_step` — which computes every node's
    gradient inside the program — the serving step consumes a COHORT the
    front end assembled from client submissions
    (``byzpy_tpu.serving.cohort.Cohort``): ``step(params, opt_state,
    matrix, valid, weights)`` where ``matrix`` is the ``(bucket, d)``
    zero-padded gradient stack, ``valid`` the ``(bucket,)`` row mask and
    ``weights`` the per-row staleness discounts (1.0 = fresh; padding
    rows carry 0). The masked aggregate (an
    ``Aggregator.masked_matrix_fn()``) reduces the valid rows EXACTLY as
    the unpadded aggregate would, with the actual cohort size ``m``
    traced — so ``jax.jit``'s shape keying compiles ONE program per
    bucket in the ladder instead of one per distinct cohort size (the
    jit-cache economics ``benchmarks/serving_bench.py`` measures).

    PRECONDITIONS (the caller's, because ``m`` is traced and a jitted
    program can neither ``validate_n`` nor fall back): the cohort must
    be admissible for the aggregator (``m`` at least its smallest valid
    n — e.g. 2f+1 for a trimmed mean, where a smaller cohort makes the
    trim window empty and the 1/(m-2f) reciprocal a silent NaN; the
    serving front end enforces this via ``TenantConfig.min_cohort``)
    and the valid rows finite (the masked programs' exactness contract
    is finite-only; the guarded door with the exact non-finite fallback
    is ``Aggregator.aggregate_masked``, which ``CohortAggregator``
    uses). This mirrors the rest of the SPMD layer: every in-jit
    aggregator call trusts its inputs at trace-checked shapes.

    With ``mesh``, the cohort matrix is constrained feature-sharded over
    every mesh axis before the reduce, the same layout as the fused PS
    round. Returns ``(step, opt_state0)``; the step is NOT jitted here —
    wrap with ``jax.jit`` (see :func:`jit_serving_ps_step`) so callers
    control donation.
    """
    opt = optimizer or optax.sgd(learning_rate, momentum=momentum)
    ravel, unravel = ravel_pytree_fn(bundle.params)
    param_dtype = ravel(bundle.params).dtype
    feat_spec = None
    if mesh is not None:
        axis = node_axis(mesh)
        extra = tuple(
            a for a in mesh.axis_names if a != axis and mesh.shape[a] > 1
        )
        feat_spec = NamedSharding(mesh, P(None, (axis, *extra)))

    def step(params, opt_state, matrix, valid, weights):
        # named_scope = the in-jit analogue of the host tracing spans,
        # here and in the training step (round.*). How a scope reaches
        # a metric: the label becomes a segment of each instruction's
        # op_name in the COMPILED program's text, not of any trace event
        # (a TPU trace names an op by its instruction alone). A reader
        # joins trace event -> instruction name -> compiled-text line ->
        # op_name -> innermost scope (chipbench/scope_join.py); labels
        # are catalogued in observability.catalog.SCOPES.
        with jax.named_scope("serving.staleness_scale"):
            # staleness discount: scale each row before the robust
            # reduce (a weight of exactly 1.0 leaves the row
            # bit-identical; the padding rows are zero and stay zero)
            matrix = matrix * weights[:, None].astype(matrix.dtype)
        if feat_spec is not None:
            matrix = jax.lax.with_sharding_constraint(matrix, feat_spec)
        with jax.named_scope("serving.masked_aggregate"):
            agg_flat = masked_aggregate(matrix, valid).astype(param_dtype)
        agg = unravel(agg_flat)
        with jax.named_scope("serving.opt_update"):
            updates, new_opt_state = opt.update(agg, opt_state, params)
            params = optax.apply_updates(params, updates)
        metrics = {
            "agg_grad_norm": jnp.sqrt(jnp.sum(jnp.square(agg_flat))),
            "cohort_m": jnp.sum(valid.astype(jnp.int32)),
        }
        return params, new_opt_state, metrics

    return step, opt.init(bundle.params)


def build_ragged_serving_ps_step(
    bundle: ModelBundle,
    ragged_aggregate: Callable,
    *,
    row_capacity: int,
    optimizer: Optional[optax.GradientTransformation] = None,
    learning_rate: float = 0.05,
    momentum: float = 0.9,
    mesh: Optional[Mesh] = None,
) -> Tuple[Callable, Any]:
    """The serving update step over the RAGGED flat-rows layout — the
    ladder-free twin of :func:`build_serving_ps_step`.

    ``step(params, opt_state, flat, offsets, lengths, weights)``
    consumes the tenant's round as ``flat: (row_capacity, d)`` (cohort
    rows first, zero rows after), ``offsets``/``lengths``: ``(1,)``
    int32 (the cohort's placement — traced, so the ACTUAL cohort size
    is data), and ``weights``: ``(row_capacity,)`` staleness discounts
    (0 for capacity rows). ``ragged_aggregate`` is an
    ``Aggregator.ragged_matrix_fn()``; its per-cohort bit-parity
    contract makes this step's aggregate bit-identical to the bucketed
    step's for the same cohort. The jit-cache economics are the point:
    the compiled shape is ``(row_capacity, d)`` ALONE — one program per
    tenant for every cohort-size distribution, vs one per ladder rung
    (``jax.jit`` via :func:`jit_ragged_serving_ps_step`).

    Same preconditions as the bucketed step (admissible ``m``, finite
    rows — the guarded doors live in ``serving``); with ``mesh`` the
    flat matrix is constrained feature-sharded like every other round
    path. Returns ``(step, opt_state0)``.
    """
    opt = optimizer or optax.sgd(learning_rate, momentum=momentum)
    ravel, unravel = ravel_pytree_fn(bundle.params)
    param_dtype = ravel(bundle.params).dtype
    feat_spec = None
    if mesh is not None:
        axis = node_axis(mesh)
        extra = tuple(
            a for a in mesh.axis_names if a != axis and mesh.shape[a] > 1
        )
        feat_spec = NamedSharding(mesh, P(None, (axis, *extra)))
    rows = int(row_capacity)

    def step(params, opt_state, flat, offsets, lengths, weights):
        from ..ops import ragged as ragged_ops

        with jax.named_scope("serving.ragged_scale"):
            flat = flat * weights[:, None].astype(flat.dtype)
        if feat_spec is not None:
            flat = jax.lax.with_sharding_constraint(flat, feat_spec)
        seg = ragged_ops.segment_ids(offsets, lengths, rows, 1)
        with jax.named_scope("serving.ragged_aggregate"):
            aggs, _, _ = ragged_aggregate(
                flat, seg, offsets, lengths, n_cohorts=1
            )
            agg_flat = aggs[0].astype(param_dtype)
        agg = unravel(agg_flat)
        with jax.named_scope("serving.opt_update"):
            updates, new_opt_state = opt.update(agg, opt_state, params)
            params = optax.apply_updates(params, updates)
        metrics = {
            "agg_grad_norm": jnp.sqrt(jnp.sum(jnp.square(agg_flat))),
            "cohort_m": lengths[0],
        }
        return params, new_opt_state, metrics

    return step, opt.init(bundle.params)


def jit_ragged_serving_ps_step(
    bundle: ModelBundle,
    ragged_aggregate: Callable,
    *,
    row_capacity: int,
    donate: bool = False,
    **kwargs: Any,
) -> Tuple[Callable, Any]:
    """:func:`build_ragged_serving_ps_step` + ``jax.jit`` — ONE
    compiled program per tenant (the flat capacity is the only shape
    key; cohort size is traced data). ``donate=True`` donates
    params/opt-state as in :func:`jit_serving_ps_step`."""
    step, opt_state0 = build_ragged_serving_ps_step(
        bundle, ragged_aggregate, row_capacity=row_capacity, **kwargs
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums), opt_state0


def adaptive_attack_rows(
    attack: Any, n_byz: int, *, honest: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Host-side bridge from the stateful adaptive-attack API to the
    fused SPMD round.

    A static :data:`AttackFn` is traced INTO ``build_ps_train_step``'s
    program; an adaptive attack (``attacks.adaptive``) cannot be — its
    ``observe_round`` mutates Python state between rounds, which has no
    trace-time meaning (exactly the hazard class byzlint's
    TRACE-DISPATCH rule exists for). The fused-fabric pattern is
    therefore: compute the byzantine rows OUTSIDE the step with this
    helper, then pass them in as data (a ``(n_byz, d)`` array argument
    replacing the traced attack), and feed the step's broadcast output
    back through ``attack.observe_round``. The chaos harness's ``spmd``
    engine and ``tests/test_chaos_adaptive.py`` use this to pin
    actor-mode vs fused-SPMD attacker parity.

    ``honest`` (optional ``(h, d)`` matrix) is forwarded to attacks that
    declare ``uses_honest_grads``; public-feed-only adaptive attacks
    ignore it.
    """
    if n_byz < 1:
        raise ValueError(f"n_byz must be >= 1 (got {n_byz})")
    kwargs: dict = {}
    if getattr(attack, "uses_honest_grads", False):
        if honest is None:
            raise ValueError(f"{attack.name} needs the honest matrix")
        kwargs["honest_grads"] = list(honest)
    row = jnp.asarray(attack.apply(**kwargs))
    return jnp.tile(row[None, :], (n_byz, 1))


def jit_serving_ps_step(
    bundle: ModelBundle,
    masked_aggregate: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    *,
    donate: bool = False,
    **kwargs: Any,
) -> Tuple[Callable, Any]:
    """:func:`build_serving_ps_step` + ``jax.jit``. One compiled program
    per BUCKET shape (jit keys on the padded matrix shape; the cohort
    size only flows through the validity mask). ``donate=True`` donates
    params/opt-state for in-place HBM updates — only when the caller
    never reuses the previous round's references."""
    step, opt_state0 = build_serving_ps_step(bundle, masked_aggregate, **kwargs)
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums), opt_state0


def jit_ps_train_step(
    bundle: ModelBundle,
    aggregate: AggFn,
    cfg: PSStepConfig,
    *,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    **kwargs: Any,
) -> Tuple[Callable, Any]:
    """``build_ps_train_step`` + ``jax.jit`` with params/opt-state donation
    (in-place HBM update, the TPU idiom for training loops)."""
    step, opt_state0 = build_ps_train_step(
        bundle, aggregate, cfg, mesh=mesh, **kwargs
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums), opt_state0


__all__ = [
    "PSStepConfig",
    "ShardedUpdateConfig",
    "adaptive_attack_rows",
    "as_sharded_update",
    "default_optimizer",
    "build_ps_train_step",
    "build_ragged_serving_ps_step",
    "build_serving_ps_step",
    "jit_ps_train_step",
    "jit_ragged_serving_ps_step",
    "jit_serving_ps_step",
]
