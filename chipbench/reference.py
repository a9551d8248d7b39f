"""Plain references the benchmark holds the system to.

Straight ``jax.numpy`` in float32, written from the definitions. Nothing
here imports the program (``byzpy_tpu``) and nothing takes anything the
program made: weights and data come from ``chipbench.seeded``.

``precision`` is the MXU precision of every contraction (``None`` = the
backend's default, which on a TPU multiplies bf16-rounded operands and
accumulates in f32; ``"highest"`` = six passes, full f32). ``dtype`` is
the type activations and parameters are computed in. The configuration
file names the pair the reference is run at; the control lowers it.

Parameter trees use the names of the configuration's checkpoint layout
(``Conv_i`` / ``GroupNorm_i`` / ``ResNetBlock_i`` / ``Dense_i``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# robust aggregation, attacks, optimizer: (n, d) float32 rows
# --------------------------------------------------------------------------


def trimmed_mean(x: jax.Array, *, f: int) -> jax.Array:
    """Per coordinate: sort the n values, drop the f smallest and the f
    largest, average the rest (Yin et al. 2018)."""
    n = x.shape[0]
    s = jnp.sort(x, axis=0)
    return jnp.mean(s[f : n - f], axis=0)


def pairwise_sq_dists(x: jax.Array) -> jax.Array:
    """``D[i, j] = sum_k (x[i, k] - x[j, k])**2`` from the differences
    themselves (no Gram identity, so no cancellation), one row at a time."""
    rows = [jnp.sum(jnp.square(x - x[i][None, :]), axis=1) for i in range(x.shape[0])]
    return jnp.stack(rows)


def multi_krum(x: jax.Array, *, f: int, q: int) -> jax.Array:
    """Score of a row = sum of its squared distances to its n-f-1 nearest
    other rows; the aggregate is the mean of the q rows of lowest score,
    ties by row index (Blanchard et al. 2017, upstream ByzPy's reading)."""
    n = x.shape[0]
    d2 = pairwise_sq_dists(x)
    # self-distance is exactly 0 and sorts first; skip it
    scores = jnp.sum(jnp.sort(d2, axis=1)[:, 1 : n - f], axis=1)
    chosen = jnp.argsort(scores, stable=True)[:q]
    return jnp.mean(x[chosen], axis=0)


def krum_scores(x: jax.Array, *, f: int) -> jax.Array:
    n = x.shape[0]
    return jnp.sum(jnp.sort(pairwise_sq_dists(x), axis=1)[:, 1 : n - f], axis=1)


def neg_honest_mean(honest: jax.Array, n_byzantine: int) -> jax.Array:
    """Sign-flip of the honest mean, and Empire at scale -1: every
    byzantine row is ``-mean(honest rows)``."""
    row = -jnp.mean(honest, axis=0)
    return jnp.broadcast_to(row, (n_byzantine, honest.shape[1]))


def sgd_momentum(
    params: jax.Array, trace: jax.Array, grad: jax.Array, *, lr: float, momentum: float
) -> Tuple[jax.Array, jax.Array]:
    """``trace <- momentum * trace + grad``; ``params <- params - lr * trace``
    (torch.optim.SGD without dampening or Nesterov)."""
    trace = momentum * trace + grad
    return params - lr * trace, trace


# --------------------------------------------------------------------------
# models
# --------------------------------------------------------------------------


def _conv(x, kernel, stride: int, precision):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
    )


def _group_norm(x, scale, bias, groups: int, eps: float):
    """Normalise each sample over (H, W, channels of one group)."""
    b, h, w, c = x.shape
    g = x.reshape(b, h, w, groups, c // groups).astype(jnp.float32)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    out = g.reshape(b, h, w, c) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def resnet_gn_logits(params: Dict[str, Any], x: jax.Array, arch: Dict[str, Any],
                     *, precision=None, dtype=jnp.float32) -> jax.Array:
    """CIFAR-style ResNet of basic blocks (He et al. 2016: 3x3 stem, no
    max-pool, stages of ``stage_sizes`` blocks at 1x, 2x, 4x, 8x
    ``num_filters``, stride 2 at the head of stages 2..4 with a 1x1
    projection shortcut, global average pool, linear head), GroupNorm in
    the place of BatchNorm (Wu & He 2018), no conv bias."""
    p = params["params"]
    groups, eps = int(arch["norm_groups"]), float(arch["norm_eps"])

    def norm(y, name, scope):
        return _group_norm(y, scope[name]["scale"], scope[name]["bias"], groups, eps)

    x = x.astype(dtype)
    x = jax.nn.relu(norm(_conv(x, p["Conv_0"]["kernel"], 1, precision), "GroupNorm_0", p))
    block = 0
    for stage, size in enumerate(arch["stage_sizes"]):
        for j in range(size):
            s = p[f"ResNetBlock_{block}"]
            stride = 2 if stage > 0 and j == 0 else 1
            y = jax.nn.relu(norm(_conv(x, s["Conv_0"]["kernel"], stride, precision),
                                 "GroupNorm_0", s))
            y = norm(_conv(y, s["Conv_1"]["kernel"], 1, precision), "GroupNorm_1", s)
            if "Conv_2" in s:
                x = norm(_conv(x, s["Conv_2"]["kernel"], stride, precision),
                         "GroupNorm_2", s)
            x = jax.nn.relu(y + x)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    head = p["Dense_0"]
    logits = jnp.dot(x, head["kernel"].astype(dtype), precision=precision)
    return (logits + head["bias"].astype(dtype)).astype(jnp.float32)


def mlp_logits(params: Dict[str, Any], x: jax.Array, arch: Dict[str, Any],
               *, precision=None, dtype=jnp.float32) -> jax.Array:
    """Flatten, then ``Dense_i`` with ReLU between."""
    p = params["params"]
    x = x.reshape(x.shape[0], -1).astype(dtype)
    n_layers = len(p)
    for i in range(n_layers):
        layer = p[f"Dense_{i}"]
        x = jnp.dot(x, layer["kernel"].astype(dtype), precision=precision)
        x = x + layer["bias"].astype(dtype)
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x.astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean over the batch of ``logsumexp(logits) - logits[label]``."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


# --------------------------------------------------------------------------
# the round: per-worker gradients -> attack -> aggregate -> update
# --------------------------------------------------------------------------


def flatten(tree: Any) -> jax.Array:
    return jnp.concatenate([jnp.ravel(leaf) for leaf in jax.tree_util.tree_leaves(tree)])


def flatten_host(tree: Any):
    """``flatten`` of a tree of host arrays, on the host."""
    import numpy as np

    return np.concatenate([np.ravel(np.asarray(leaf))
                           for leaf in jax.tree_util.tree_leaves(tree)])


def unflatten(flat: jax.Array, like: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, at = [], 0
    for leaf in leaves:
        out.append(flat[at : at + leaf.size].reshape(leaf.shape))
        at += leaf.size
    return jax.tree_util.tree_unflatten(treedef, out)


def follow_rounds(
    logits_fn: Callable[..., jax.Array],
    arch: Dict[str, Any],
    params0: Any,
    batches: Sequence[Tuple[jax.Array, jax.Array]],
    *,
    n_nodes: int,
    n_byzantine: int,
    aggregate: Callable[[jax.Array], jax.Array],
    attack: Callable[[jax.Array, int], jax.Array],
    lr: float,
    momentum: float,
    precision=None,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    """Robust parameter-server rounds from ``params0``, one per entry of
    ``batches`` (``xs: (n, B, ...)``, ``ys: (n, B)``): honest workers'
    loss and gradient one worker at a time, byzantine rows from
    ``attack``, ``aggregate`` over the (n, d) matrix, SGD with momentum.

    Returns the honest-mean loss of each round, the first round's
    aggregate (what the optimizer got) and the parameters after the
    last, as trees shaped like ``params0``.
    """
    h = n_nodes - n_byzantine

    def loss(params, x, y):
        return cross_entropy(
            logits_fn(params, x, arch, precision=precision, dtype=dtype), y
        )

    worker = jax.jit(jax.value_and_grad(loss))
    # everything around the workers is one program each (flatten, unflatten,
    # the round's tail), so that a run loads a handful of programs, not one
    # for every leaf and operation
    flat_of = jax.jit(flatten)
    tree_of = jax.jit(lambda vector: unflatten(vector, params0))

    @jax.jit
    def tail(flat, trace, rows):
        honest = jnp.stack(rows).astype(jnp.float32)
        matrix = honest
        if n_byzantine:
            matrix = jnp.concatenate([honest, attack(honest, n_byzantine)], axis=0)
        agg = aggregate(matrix)
        flat, trace = sgd_momentum(flat, trace, agg, lr=lr, momentum=momentum)
        return flat, trace, agg

    flat = flat_of(params0).astype(jnp.float32)
    trace = jnp.zeros_like(flat)
    losses: List[float] = []
    first_grad = None
    for xs, ys in batches:
        params = tree_of(flat)
        rows, round_losses = [], []
        for i in range(h):
            value, grads = worker(params, xs[i], ys[i])
            round_losses.append(float(value))
            rows.append(flat_of(grads))
        flat, trace, agg = tail(flat, trace, rows)
        if first_grad is None:
            first_grad = agg
        losses.append(sum(round_losses) / len(round_losses))
    return {
        "losses": losses,
        "first_grad": tree_of(first_grad),
        "params": tree_of(flat),
    }


# --------------------------------------------------------------------------
# the numbers compared
# --------------------------------------------------------------------------


def leaf_norms(tree: Any) -> List[float]:
    """The norm of every leaf, on the host (the trees compared are there)."""
    import numpy as np

    return [float(np.sqrt(np.sum(np.square(np.asarray(leaf, np.float64)))))
            for leaf in jax.tree_util.tree_leaves(tree)]


def worst_leaf_norm_gap(got: Sequence[float], want: Sequence[float]) -> float:
    """Largest over leaves of ``|got - want|`` (a gap between norms, not
    the norm of a difference) over the larger of the reference's norm of
    that leaf and of its median leaf: some gradients are all but zero."""
    ordered = sorted(want)
    median = ordered[len(ordered) // 2]
    return max(abs(g - w) / max(w, median) for g, w in zip(got, want))


def short_mantissa_share(values, *, low_bits: int = 8) -> float:
    """Share of the non-zero float32 values whose ``low_bits`` lowest
    mantissa bits are all zero. Float32 arithmetic on float32 rows leaves
    about ``2**-low_bits`` of them so. A vector emitted in bfloat16 has
    them all (its low 16 bits are zero), and so has nearly all of a mean
    of a few bfloat16-valued rows taken in float32: the sum of four
    8-bit mantissas is a dozen bits long."""
    import numpy as np

    bits = np.ascontiguousarray(np.asarray(values, np.float32)).view(np.uint32)
    nonzero = bits & np.uint32(0x7FFFFFFF) != 0
    if not nonzero.any():
        return 1.0
    short = ((bits & np.uint32((1 << low_bits) - 1)) == 0) & nonzero
    return float(np.count_nonzero(short) / np.count_nonzero(nonzero))
