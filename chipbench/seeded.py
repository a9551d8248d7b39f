"""Everything a run draws from ``--seed``: weights, data, keys.

Made on the device in one jitted call each, in the type they are used
in. The program and the reference are handed the same arrays; neither
makes its own.
"""

from __future__ import annotations

import math
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31): low 31 bits seed it, the rest are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make_params(shapes: Any, seed: int, *, sharding: Any = None) -> Any:
    """Seeded weights for a tree of ``ShapeDtypeStruct``: ``kernel``
    leaves are normal with variance 1/fan_in (fan_in = every axis but the
    last), ``scale`` leaves one, everything else (``bias``) zero."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in paths_leaves]
    leaves = [leaf for _, leaf in paths_leaves]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, leaf in zip(keys, names, leaves):
            if name == "kernel":
                fan_in = math.prod(leaf.shape[:-1])
                out.append(jax.random.normal(k, leaf.shape, leaf.dtype)
                           * jnp.asarray(1.0 / math.sqrt(fan_in), leaf.dtype))
            elif name == "scale":
                out.append(jnp.ones(leaf.shape, leaf.dtype))
            else:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(root_key(seed), 1)
    return jax.jit(build, out_shardings=sharding)(key)


def make_batches(
    seed: int, *, pool: int, n_nodes: int, batch: int, input_shape: Sequence[int],
    num_classes: int, sharding: Any = None,
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """``pool`` batches of ``(n_nodes, batch, *input_shape)`` float32
    images and ``(n_nodes, batch)`` int32 labels: class-conditional
    Gaussian blobs (one centre per class, noise 0.5), every image
    different. Returned as lists of device arrays, one entry per batch."""
    shape = (n_nodes, batch, *input_shape)
    flat = math.prod(input_shape)

    def build(key):
        k_centres, k_batches = jax.random.split(key)
        centres = jax.random.normal(k_centres, (num_classes, flat), jnp.float32)
        xs, ys = [], []
        for k in jax.random.split(k_batches, pool):
            k_y, k_x = jax.random.split(k)
            y = jax.random.randint(k_y, (n_nodes, batch), 0, num_classes, jnp.int32)
            x = centres[y] + 0.5 * jax.random.normal(k_x, (n_nodes, batch, flat), jnp.float32)
            xs.append(x.reshape(shape))
            ys.append(y)
        return xs, ys

    key = jax.random.fold_in(root_key(seed), 2)
    out_shardings = None if sharding is None else ([sharding] * pool, [sharding] * pool)
    return jax.jit(build, out_shardings=out_shardings)(key)


def make_matrix(seed: int, n: int, d: int, *, sharding: Any = None) -> jax.Array:
    """A seeded (n, d) float32 standard-normal matrix on the device."""
    key = jax.random.fold_in(root_key(seed), 3)
    fn = jax.jit(lambda k: jax.random.normal(k, (n, d), jnp.float32), out_shardings=sharding)
    return fn(key)


def step_keys(seed: int, count: int) -> List[jax.Array]:
    key = jax.random.fold_in(root_key(seed), 4)
    return list(jax.random.split(key, count))
