"""byzlint fixture: TRACE-DISPATCH true positives (never imported)."""

import os
from functools import partial

import jax


@jax.jit
def env_read_under_jit(x):
    flag = os.environ.get("BYZPY_TPU_FAKE_FLAG")  # finding: env read in trace
    return -x if flag else x


@partial(jax.jit, static_argnames=("n",))
def getenv_under_jit(x, n):
    return x * int(os.getenv("BYZPY_TPU_FAKE_TILE", "128"))  # finding


def make_kernel(x):
    def traced(y):
        if pallas_serves(y):  # finding: the dispatch gate asked mid-trace
            return y * 2
        return y

    return jax.jit(traced)(x)


def pallas_serves(x):
    return False
