"""Model zoo: flax.linen networks used by examples, benchmarks and tests.

The reference trains a torch ``SmallCNN`` on MNIST in its PS/P2P examples
(ref: ``examples/ps/nodes.py:46-61``) and names ResNet-18/CIFAR-10 and
ResNet-50/ImageNet in larger benchmark configs. These are the JAX
equivalents, designed for TPU:

* **NHWC layout** — flax's native conv layout, which XLA maps directly onto
  the MXU without transposes;
* **bfloat16-friendly** — every module takes a ``dtype`` so activations can
  run in bf16 while parameters stay f32 (the standard TPU mixed-precision
  recipe);
* static shapes everywhere, so one trace covers the whole run.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .bundle import ModelBundle

Dtype = Any


class MLP(nn.Module):
    """Plain MLP classifier (flattens its input)."""

    features: Sequence[int] = (128, 10)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for i, feat in enumerate(self.features):
            x = nn.Dense(feat, dtype=self.dtype)(x)
            if i < len(self.features) - 1:
                x = nn.relu(x)
        return x.astype(jnp.float32)


class SmallCNN(nn.Module):
    """MNIST CNN with the reference architecture: conv32-pool-conv64-pool-
    fc128-fc10 (ref: ``examples/ps/nodes.py:46-61``). Input NHWC (B,28,28,1).
    """

    num_classes: int = 10
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        x = nn.relu(nn.Conv(32, (3, 3), padding="SAME", dtype=self.dtype)(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(nn.Conv(64, (3, 3), padding="SAME", dtype=self.dtype)(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128, dtype=self.dtype)(x))
        return nn.Dense(self.num_classes, dtype=self.dtype)(x).astype(jnp.float32)


class ResNetBlock(nn.Module):
    """Basic residual block (two 3x3 convs)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.float32
    norm: Callable = nn.GroupNorm

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = nn.Conv(self.filters, (3, 3), self.strides, padding="SAME",
                    use_bias=False, dtype=self.dtype)(x)
        y = self.norm(dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding="SAME",
                    use_bias=False, dtype=self.dtype)(y)
        y = self.norm(dtype=self.dtype)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = self.norm(dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """Bottleneck residual block (1x1 -> 3x3 -> 1x1, 4x expansion)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Dtype = jnp.float32
    norm: Callable = nn.GroupNorm

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = self.norm(dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, padding="SAME",
                    use_bias=False, dtype=self.dtype)(y)
        y = self.norm(dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters * 4, (1, 1), use_bias=False, dtype=self.dtype)(y)
        y = self.norm(dtype=self.dtype)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters * 4, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = self.norm(dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """ResNet for CIFAR (3x3 stem) or ImageNet (7x7 stem) style inputs.

    GroupNorm instead of BatchNorm: robust-aggregation training averages
    *gradients* across nodes, and BatchNorm's running statistics are state
    that the PS round has no channel for — GroupNorm keeps the model a pure
    function of (params, x), which is also what jit/shard_map want.
    """

    stage_sizes: Sequence[int]
    block_cls: Callable = ResNetBlock
    num_classes: int = 10
    num_filters: int = 64
    small_input: bool = True  # CIFAR-style stem
    dtype: Dtype = jnp.float32
    norm: Callable = nn.GroupNorm

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        if self.small_input:
            x = nn.Conv(self.num_filters, (3, 3), padding="SAME",
                        use_bias=False, dtype=self.dtype)(x)
        else:
            x = nn.Conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = self.norm(dtype=self.dtype)(x)
        x = nn.relu(x)
        for i, size in enumerate(self.stage_sizes):
            for j in range(size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i, strides=strides,
                                   dtype=self.dtype, norm=self.norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=self.dtype)(x).astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=(2, 2, 2, 2), block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BottleneckBlock)


def make_bundle(
    model: nn.Module,
    input_shape: Sequence[int],
    *,
    seed: int = 0,
    loss_fn: Callable | None = None,
) -> ModelBundle:
    """Initialize ``model`` and wrap it as a :class:`ModelBundle`. With the
    default loss (the mean cross-entropy over the batch) and a model of this
    file that keeps a batch's examples apart, the bundle declares
    ``example_mean_loss``; a caller's own ``loss_fn`` or module does not."""
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng, jnp.zeros(tuple(input_shape), jnp.float32))
    return ModelBundle(apply_fn=model.apply, params=params, loss_fn=loss_fn,
                       example_mean_loss=loss_fn is None and _keeps_examples_apart(model))


def _keeps_examples_apart(model: nn.Module) -> bool:
    """Whether no layer of ``model`` mixes the examples of a batch, as far
    as this file can know: true of its own modules as written (``Dense``,
    ``Conv``, pooling within an image, ``GroupNorm``: the statistics of ONE
    example's channel groups), not known of a caller's module, and not of a
    ResNet handed another ``norm``."""
    if isinstance(model, ResNet):
        return model.norm is nn.GroupNorm
    return isinstance(model, (MLP, SmallCNN))


def mnist_mlp(seed: int = 0, hidden: int = 128) -> ModelBundle:
    """MLP(hidden, 10) bundle for 28x28x1 inputs (MNIST-shaped)."""
    return make_bundle(MLP(features=(hidden, 10)), (1, 28, 28, 1), seed=seed)


def mnist_cnn(seed: int = 0, dtype: Dtype = jnp.float32) -> ModelBundle:
    """SmallCNN bundle with the reference's MNIST architecture."""
    return make_bundle(SmallCNN(dtype=dtype), (1, 28, 28, 1), seed=seed)


def digits_mlp(seed: int = 0, hidden: int = 64) -> ModelBundle:
    """MLP for the real 8x8 digits dataset (``data.load_digits_dataset``)."""
    return make_bundle(MLP(features=(hidden, 10)), (1, 8, 8, 1), seed=seed)


def cifar_resnet18(seed: int = 0, dtype: Dtype = jnp.float32) -> ModelBundle:
    """ResNet-18 bundle for 32x32x3 (CIFAR-10-shaped) inputs."""
    return make_bundle(ResNet18(num_classes=10, dtype=dtype), (1, 32, 32, 3), seed=seed)


def imagenet_resnet50(seed: int = 0, dtype: Dtype = jnp.bfloat16) -> ModelBundle:
    """ResNet-50 bundle for 224x224x3 inputs, bf16 activations by default."""
    return make_bundle(
        ResNet50(num_classes=1000, small_input=False, dtype=dtype),
        (1, 224, 224, 3),
        seed=seed,
    )


__all__ = [
    "MLP",
    "SmallCNN",
    "ResNetBlock",
    "BottleneckBlock",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "make_bundle",
    "mnist_mlp",
    "mnist_cnn",
    "digits_mlp",
    "cifar_resnet18",
    "imagenet_resnet50",
]
