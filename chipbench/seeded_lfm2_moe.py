"""What a run of an LFM2 cell draws from ``--seed``: weights. The token
batches are ``chipbench.seeded_nemotron_h``'s Markov-chain walks (the same
generator, the configuration's own vocabulary slice). The program and the
reference are handed the same arrays.

Weights, by the name of the leaf: matrices normal with variance
1 / fan_in (a stack of experts' matrices: each expert's own fan_in; the
convolution's taps ``conv_w (K, hidden)``: K). The TIED table
(``embedding``) is the head's matrix too, and its fan_in is the head's:
``hidden`` (a standard deviation of 0.0221 at 2048, the family's
``initializer_range`` 0.02 to a tenth), so that the logits start at unit
variance; with the one-hot fan_in 1 of an untied embedding they would start
at a deviation of 45 and the loss at a hundred. Every norm's scale
(``*norm_scale``: the two of a block, the head norms of 64, the final
norm) uniform in [0.75, 1.25]: AWAY from the 1 they would start a training
run at, where a dropped norm weight could not be told from a kept one.
Every leaf has a key of its own, so one segment can be made again alone
(``make_segment``): the comparison after the window needs the starting
weights a segment at a time, never a second whole copy.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from chipbench.seeded import root_key
from chipbench.seeded_nemotron_h import make_token_batches  # noqa: F401  (the driver's)

NORM_SCALE_RANGE = (0.75, 1.25)


def _leaf(name: str, shape: Sequence[int], dtype: Any, key: jax.Array):
    if name.endswith("norm_scale"):
        return jax.random.uniform(key, shape, dtype, *NORM_SCALE_RANGE)
    fan_in = shape[-1] if name == "embedding" else shape[-2]
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
