"""What a run of a Nemotron-H cell draws from ``--seed``: weights and
token batches. The program and the reference are handed the same arrays.

Weights, by the name of the leaf: matrices normal with variance
1 / fan_in (the embedding's input is one-hot: fan_in 1), norm scales and
``d_skip`` 1, and the Mamba-2 vectors in their published ranges (``A`` in
[1, 16], ``dt`` log-uniform between the configuration's ``time_step_min``
and ``time_step_max`` with its floor, through the inverse softplus;
convolution weights and bias uniform in +-1/sqrt(kernel)). Every leaf has
a key of its own, so one segment can be made again alone
(``make_segment``): the comparison after the window needs the starting
weights a segment at a time, never a second whole copy.

Data: a first-order Markov chain over the vocabulary held (each token has
``fanout`` successors with fixed odds, and is the successor of ``fanout``
tokens: the stationary distribution is uniform), so that a model can
learn it and the loss can fall; one packed sequence a worker, ``x`` its first ``T``
tokens and ``y`` the tokens that follow them.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench.seeded import root_key

_ONES = ("norm_scale", "gate_norm_scale", "d_skip")
SUCCESSOR_ODDS = (0.4, 0.3, 0.2, 0.1)


def _leaf(name: str, shape: Sequence[int], dtype: Any, key: jax.Array, arch: Dict[str, Any]):
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(int(arch["conv_kernel"]))
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(float(arch["time_step_min"])),
            math.log(float(arch["time_step_max"]))))
        dt = jnp.maximum(dt, float(arch["time_step_floor"]))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
    fan_in = 1 if name == "embedding" else shape[-2]
    return jax.random.normal(key, shape, dtype) * jnp.asarray(1.0 / math.sqrt(fan_in), dtype)


_BUILDERS: Dict[Any, Any] = {}


def _segment_builder(shapes: Dict[str, Dict[str, Any]], segment: str, arch: Dict[str, Any]):
    """The jitted maker of one segment, made once for a tree of shapes: a
    run makes every segment four times (the program's weights, the change
    after the rounds followed, and both again for the reference)."""
    names = sorted(shapes[segment])
    at = sorted(shapes).index(segment)
    leaves = tuple((name, tuple(shapes[segment][name].shape), str(shapes[segment][name].dtype))
                   for name in names)
    ranges = tuple(float(arch[k]) for k in (
        "conv_kernel", "time_step_min", "time_step_max", "time_step_floor"))
    known = (at, leaves, ranges)
    if known not in _BUILDERS:
        def build(key):
            key = jax.random.fold_in(key, at)
            return {name: _leaf(name, shape, jnp.dtype(dtype), jax.random.fold_in(key, k), arch)
                    for k, (name, shape, dtype) in enumerate(leaves)}

        _BUILDERS[known] = jax.jit(build)
    return _BUILDERS[known]


def make_segment(shapes: Dict[str, Dict[str, Any]], seed: int, segment: str,
                 arch: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The seeded weights of one segment (``shapes[segment]``: leaf name ->
    ``ShapeDtypeStruct``), the same values ``make_params`` gives it."""
    key = jax.random.fold_in(root_key(seed), 1)
    return _segment_builder(shapes, segment, arch)(key)


def make_params(shapes: Dict[str, Dict[str, Any]], seed: int, arch: Dict[str, Any]
                ) -> Dict[str, Dict[str, jax.Array]]:
    """Seeded weights for the whole tree, a segment a program."""
    return {segment: make_segment(shapes, seed, segment, arch) for segment in sorted(shapes)}


def make_token_batches(seed: int, *, pool: int, n_nodes: int, seq_len: int, vocab: int
                       ) -> Tuple[List[jax.Array], List[jax.Array]]:
    """``pool`` batches of ``x, y: (n_nodes, 1, seq_len)`` int32: one
    packed sequence a worker from one Markov chain (successor table and
    odds fixed by the seed; every sequence its own walk)."""
    fanout = len(SUCCESSOR_ODDS)
    walks = pool * n_nodes

    def build(key):
        k_table, k_start, k_steps = jax.random.split(key, 3)
        # each column a permutation of the vocabulary: every token is the
        # successor of exactly `fanout` tokens, so the chain's stationary
        # distribution is uniform and no expert's share of a sequence hangs
        # on which tokens the chain happens to favour
        successors = jnp.stack(
            [jax.random.permutation(k, vocab) for k in jax.random.split(k_table, fanout)],
            axis=1).astype(jnp.int32)
        start = jax.random.randint(k_start, (walks,), 0, vocab, jnp.int32)
        picks = jax.random.choice(k_steps, fanout, (seq_len, walks),
                                  p=jnp.asarray(SUCCESSOR_ODDS, jnp.float32))

        def step(token, pick):
            following = successors[token, pick]
            return following, following

        _, rest = jax.lax.scan(step, start, picks)
        tokens = jnp.concatenate([start[None], rest], axis=0).T  # (walks, seq_len + 1)
        tokens = tokens.reshape(pool, n_nodes, 1, seq_len + 1)
        return ([tokens[i, ..., :-1] for i in range(pool)],
                [tokens[i, ..., 1:] for i in range(pool)])

    return jax.jit(build)(jax.random.fold_in(root_key(seed), 2))
