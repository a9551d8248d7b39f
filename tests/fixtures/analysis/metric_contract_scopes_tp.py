"""byzlint fixture: METRIC-CONTRACT true positives for the in-jit names
(never imported).

A ``named_scope`` label the scope catalog has never heard of, two computed
labels under no catalogued family, a ``pallas_call`` that leaves its kernel
to a compiler-made name, and a kernel name missing from the kernel catalog.
"""

import jax
from jax.experimental import pallas as pl


def step(x):
    # finding: not in catalog.SCOPES
    with jax.named_scope("round.bogus_stage"):
        return x * 2


def computed(x, stage):
    # finding: a computed label whose literal head names no catalogued family
    with jax.named_scope(f"round.{stage}"):
        x = x + 1
    # finding: a computed label with no literal head at all
    with jax.named_scope(stage):
        return x


def _unnamed_call(x, kernel, shape):
    # finding: no name= — the custom call gets a name the compiler made
    return pl.pallas_call(kernel, out_shape=shape)(x)


def _uncatalogued_call(x, kernel, shape):
    # finding: not in catalog.KERNELS
    return pl.pallas_call(kernel, out_shape=shape, name="bogus_kernel")(x)
