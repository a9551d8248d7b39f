"""What a run of a Xing4.0 cell draws from ``--seed``: weights. The token
batches are ``chipbench.seeded_nemotron_h``'s Markov-chain walks (the same
generator, the configuration's own vocabulary slice). The program and the
reference are handed the same arrays.

Weights, by the name of the leaf: matrices normal with variance
1 / fan_in (the embedding's input is one-hot: fan_in 1; a stack of
experts' matrices: each expert's own fan_in; a hyper-connection's ``Phi``:
all ``n * hidden`` values of a position's streams), every norm's scale 1.
A hyper-connection's ``alpha`` (pre, post, residual) uniform in [0.5, 1]
and its ``b`` uniform in [-1, 1] with 2 added on the diagonal of the
residual block: AWAY from where a training run starts them (``alpha`` 0.01,
``H_res`` the identity), where the dynamic part ``alpha (x^ Phi)`` would
move no mapping by more than a rounding and a wrong ``Phi`` could not be
told from a right one. Here a position's ``H_res`` has 0.4-0.9 on its
diagonal, 0.69 in the mean (neither the identity nor uniform), ``H_pre`` lies in 0.1-0.9 and
``H_post`` in 0.2-1.8, and the projected part moves each logit by about its
own ``alpha`` from position to position. Every leaf has a key of its own, so
one segment can be made again alone (``make_segment``): the comparison after
the window needs the starting weights a segment at a time, never a second
whole copy.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from chipbench.seeded import root_key
from chipbench.seeded_nemotron_h import make_token_batches  # noqa: F401  (the driver's)

ALPHA_RANGE = (0.5, 1.0)
B_RANGE = (-1.0, 1.0)
B_RES_DIAGONAL = 2.0


def _leaf(name: str, shape: Sequence[int], dtype: Any, key: jax.Array):
    if name.endswith("norm_scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("_hc_alpha"):
        return jax.random.uniform(key, shape, dtype, *ALPHA_RANGE)
    if name.endswith("_hc_b"):
        n = math.isqrt(shape[0] + 1) - 1  # n + n + n * n values: pre | post | residual
        diagonal = jnp.concatenate(
            [jnp.zeros((2 * n,), dtype), jnp.eye(n, dtype=dtype).reshape(-1)])
        return jax.random.uniform(key, shape, dtype, *B_RANGE) + B_RES_DIAGONAL * diagonal
    fan_in = 1 if name == "embedding" else shape[-2]
    return jax.random.normal(key, shape, dtype) * jnp.asarray(1.0 / math.sqrt(fan_in), dtype)


_BUILDERS: Dict[Any, Any] = {}


def _segment_builder(shapes: Dict[str, Dict[str, Any]], segment: str):
    """The jitted maker of one segment, made once for a tree of shapes: a
    run makes every segment four times (the program's weights, the change
    after the rounds followed, and both again for the reference)."""
    at = sorted(shapes).index(segment)
    leaves = tuple((name, tuple(shapes[segment][name].shape), str(shapes[segment][name].dtype))
                   for name in sorted(shapes[segment]))
    known = (at, leaves)
    if known not in _BUILDERS:
        def build(key):
            key = jax.random.fold_in(key, at)
            return {name: _leaf(name, shape, jnp.dtype(dtype), jax.random.fold_in(key, k))
                    for k, (name, shape, dtype) in enumerate(leaves)}

        _BUILDERS[known] = jax.jit(build)
    return _BUILDERS[known]


def make_segment(shapes: Dict[str, Dict[str, Any]], seed: int, segment: str,
                 arch: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The seeded weights of one segment (``shapes[segment]``: leaf name ->
    ``ShapeDtypeStruct``), the same values ``make_params`` gives it.
    ``arch`` is the driver's to hand over; nothing here is drawn from it."""
    del arch
    return _segment_builder(shapes, segment)(jax.random.fold_in(root_key(seed), 1))


def make_params(shapes: Dict[str, Dict[str, Any]], seed: int, arch: Dict[str, Any]
                ) -> Dict[str, Dict[str, jax.Array]]:
    """Seeded weights for the whole tree, a segment a program."""
    return {segment: make_segment(shapes, seed, segment, arch) for segment in sorted(shapes)}
