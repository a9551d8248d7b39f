"""byzlint fixture: METRIC-CONTRACT false-positive guards for the in-jit
names.

Catalogued scope labels and kernel names, and the shapes the rule must
resolve to nothing: a computed label that starts with the literal of a
catalogued family (``SCOPE_PREFIXES``), computed kernel names (silent by
design).
"""

import jax
from jax.experimental import pallas as pl


def step(x, stage):
    with jax.named_scope("round.aggregate"):
        x = x * 2
    with jax.named_scope("serving.opt_update"):
        x = x + 1
    with jax.named_scope("segment." + stage):  # computed, under a catalogued family
        x = x - 1
    with jax.named_scope(f"segment.{stage}"):  # the same family, as an f-string
        x = x * 3
    with jax.named_scope("segment.s0_in"):  # a literal of the family
        return x


def _sorted_reduce_stream_call(x, kernel, shape):
    return pl.pallas_call(kernel, out_shape=shape, name="sorted_reduce_stream")(x)


def _family_call(x, kernel, shape, family):
    return pl.pallas_call(kernel, out_shape=shape, name=family + "_stream")(x)
