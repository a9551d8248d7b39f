"""The toy configuration's model factory: the program's own MLP with the
``dtype`` switch its ``mnist_mlp`` factory does not pass on, so that the
``model_bf16`` control can be rehearsed at toy size."""

from __future__ import annotations

import jax.numpy as jnp


def mnist_mlp(seed: int = 0, hidden: int = 32, dtype=jnp.float32):
    from byzpy_tpu.models.nets import MLP, make_bundle

    return make_bundle(MLP(features=(hidden, 10), dtype=dtype), (1, 28, 28, 1), seed=seed)
