"""ModelBundle: the JAX-native stand-in for a torch ``nn.Module`` handle.

Where the reference passes a mutable torch module into attacks and nodes
(ref: ``byzpy/attacks/base.py:62``), the JAX equivalent is a pure
``apply_fn`` plus an explicit parameter pytree and a loss. Everything that
needs "the model" takes one of these.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax


def softmax_cross_entropy_loss(apply_fn: Callable) -> Callable:
    """Default classification loss for integer labels."""

    def loss_fn(params: Any, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        logits = apply_fn(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    return loss_fn


@dataclass(frozen=True)
class Segment:
    """One link of a model that is a chain: ``params[key]`` is its
    parameter subtree and ``apply`` its function. Every segment but the
    last maps ``(subtree, h) -> h`` (the first is handed the worker's
    batch ``x``); the last is the loss head, ``(subtree, h, y) -> loss``.
    ``h``, the boundary between two links, is an array or a TREE of
    arrays: a link that needs what an earlier one made (a second loss
    term that reads the embedded tokens again) is handed it along the
    chain, and a link returns untouched, as the same array, whatever it
    only hands on (a round keeps such an array once). Parameters stay
    with one link each: a table read on two paths gets both paths'
    gradient because both cotangents reach its link.
    A link that reads ANOTHER link's parameters (a head that multiplies by
    the embedding's own table) names the links that own them in ``reads``,
    each EARLIER in the chain, and is handed their subtrees as one more,
    last, argument, ``{key: subtree}``: ``(subtree, h, read) -> h``, the
    head ``(subtree, h, y, read) -> loss``. The parameter still has one
    owner, one place in one row, one aggregate and one update a step: a
    worker's gradient through the reader is added to the owner's row
    before the owner's aggregate (a round that streams starts the owner's
    rows at the reader and keeps them until the owner's turn; nothing of
    the parameter's size is kept besides).
    With ``aux`` a segment returns ``(h, aux)`` (the head: ``(loss,
    aux)``), ``aux`` a tree of small arrays the round reports per honest
    worker (an expert layer's token counts, a loss's terms) and takes no
    gradient through."""

    key: str
    apply: Callable
    aux: bool = False
    reads: Tuple[str, ...] = ()

    def read_of(self, params: Any) -> Tuple[Any, ...]:
        """What ``apply`` is handed after its other arguments: nothing, or
        the subtrees of the links in ``reads``."""
        return ({key: params[key] for key in self.reads},) if self.reads else ()


def chain_loss(segments: Sequence[Segment]) -> Callable:
    """``loss_fn(params, x, y)`` of the whole chain."""

    def loss_fn(params: Any, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        h = x
        for seg in segments[:-1]:
            h = seg.apply(params[seg.key], h, *seg.read_of(params))
            if seg.aux:
                h = h[0]
        head = segments[-1]
        loss = head.apply(params[head.key], h, y, *head.read_of(params))
        return loss[0] if head.aux else loss

    return loss_fn


@dataclass
class ModelBundle:
    """``segments``, where a bundle declares them, say that the model is
    a chain whose links own disjoint subtrees of ``params`` (a dict keyed
    by ``Segment.key``) and that ``loss_fn`` is
    :func:`chain_loss` of them. A round may then take gradients, aggregate
    and update one segment at a time
    (:func:`~byzpy_tpu.parallel.ps.build_ps_train_step`); everything else
    uses ``loss_fn`` and never looks.

    ``example_mean_loss``, where a bundle declares it, says that
    ``loss_fn(params, x, y)`` is the MEAN over the examples of the batch of
    a term that depends on that example alone: no layer mixes the examples
    of a batch (no BatchNorm in training mode, no statistic, contrast or
    routing capacity taken over the batch) and the loss weighs them equally.
    Then, and only then, the loss and gradient of a batch are the means of
    the losses and gradients of equal parts of it, and an ``(n, d)`` round
    may take a worker's batch in passes that fit the chip's fast memory
    (``parallel/ps.py: _worker_passes``; ``docs/performance.md``, "A batch in
    passes"). A fact about model and loss together, so whoever writes both
    declares it: :func:`~byzpy_tpu.models.nets.make_bundle` does for its own
    loss over its own modules. Not declared (the default, a caller's own
    ``loss_fn``, every language bundle: a packed sequence is one example) a
    batch is never split."""

    apply_fn: Callable[[Any, jnp.ndarray], jnp.ndarray]
    params: Any
    loss_fn: Optional[Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None
    segments: Optional[Tuple[Segment, ...]] = None
    example_mean_loss: bool = False

    def __post_init__(self) -> None:
        if self.segments is not None:
            self.segments = tuple(self.segments)
            keys = [seg.key for seg in self.segments]
            if len(keys) < 2 or len(set(keys)) != len(keys) or set(self.params) != set(keys):
                raise ValueError(
                    "a segmented bundle's params are a dict keyed by its segments' "
                    f"keys (segments {keys}, params {list(self.params)})"
                )
            for at, seg in enumerate(self.segments):
                if not set(seg.reads) <= set(keys[:at]):
                    raise ValueError(
                        f"segment {seg.key!r} reads {list(seg.reads)}: a link reads the "
                        f"parameters of links before it in the chain ({keys[:at]})")
            if self.loss_fn is None:
                self.loss_fn = chain_loss(self.segments)
        if self.loss_fn is None:
            self.loss_fn = softmax_cross_entropy_loss(self.apply_fn)

    def grad(self, x: jnp.ndarray, y: jnp.ndarray) -> Any:
        return jax.grad(self.loss_fn)(self.params, x, y)

    def loss(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        return self.loss_fn(self.params, x, y)

    def with_params(self, params: Any) -> "ModelBundle":
        return replace(self, params=params)


__all__ = ["ModelBundle", "Segment", "chain_loss", "softmax_cross_entropy_loss"]
