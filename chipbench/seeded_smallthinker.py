"""What a run of the SmallThinker cell draws from ``--seed``: weights. The
token batches are ``chipbench.seeded_nemotron_h``'s Markov-chain walks (the
same generator, the configuration's own vocabulary slice). The program and
the reference are handed the same arrays.

Weights, by the name of the leaf: matrices normal with variance 1 / fan_in
(a stack of experts' matrices: each expert's own fan_in; the embedding's
input is one-hot: fan_in 1; the untied head ``w_head (hidden, vocab)``:
``hidden``, so the logits start at unit variance), EXCEPT the two matrices
that write a block's branches into the stream (``w_o``, ``experts_down``):
variance 1 / (fan_in x 2 x the blocks of the chain), the depth scaling that
pre-training code gives a residual branch's output projection. With unit
gain there, what the tokens of a sequence SHARE doubles every block
(attention averages thousands of keys: a token's own part averages away,
the shared part goes through ``W_v W_o`` whole), and by the eighth block the
softmax router sends one held expert most of a worker's tokens: a routing
no trained, balanced router has, and a rate that hangs on the seed (PERF.md
section 6, PR 49, has the reading block by block). Every norm's scale
(``*norm_scale``: the two of a block and the final norm) uniform in [0.75,
1.25]: AWAY from the 1 they would start a training run at, where a dropped
norm weight could not be told from a kept one (and a router fed the stream
instead of its normed form could not be told by the norm's weight). Every
leaf has a key of its own, so one segment can be made again alone
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
# the two matrices that write a block's branches into the stream
BRANCH_OUTPUTS = ("w_o", "experts_down")


def _leaf(name: str, shape: Sequence[int], dtype: Any, key: jax.Array, branches: int):
    if name.endswith("norm_scale"):
        return jax.random.uniform(key, shape, dtype, *NORM_SCALE_RANGE)
    fan_in = 1 if name == "embedding" else shape[-2]
    if name in BRANCH_OUTPUTS:
        fan_in *= branches
    return jax.random.normal(key, shape, dtype) * jnp.asarray(1.0 / math.sqrt(fan_in), dtype)


_BUILDERS: Dict[Any, Any] = {}


def _segment_builder(shapes: Dict[str, Dict[str, Any]], segment: str):
    """The jitted maker of one segment, made once for a tree of shapes: a
    run makes every segment four times (the program's weights, the change
    after the rounds followed, and both again for the reference)."""
    at = sorted(shapes).index(segment)
    leaves = tuple((name, tuple(shapes[segment][name].shape), str(shapes[segment][name].dtype))
                   for name in sorted(shapes[segment]))
    # two branches a block of the chain at hand
    branches = 2 * sum("w_o" in shapes[name] for name in shapes)
    known = (at, leaves, branches)
    if known not in _BUILDERS:
        def build(key):
            key = jax.random.fold_in(key, at)
            return {name: _leaf(name, shape, jnp.dtype(dtype), jax.random.fold_in(key, k), branches)
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
