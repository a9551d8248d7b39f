"""What works coordinate by coordinate: the ONE declared table.

A round that aggregates segment by segment
(:func:`~byzpy_tpu.parallel.ps.build_ps_train_step` with a bundle that
declares segments) never holds the ``(n, d)`` gradient matrix: it hands
the aggregate, and the attack before it, the ``(n, d_segment)`` columns
of one segment at a time. That is exact — the segments' results
concatenated ARE the result on ``(n, d)`` — for a function that treats
every column alone, and wrong for anything that reads a whole row (a
norm, an inner product, a Gram block, a selection of rows by score). So
the round asks this table, and nothing else, which is which. It is a
declaration and not a probe: a function that is not listed is refused,
however it behaves. A Gram-type aggregate over a streamed round needs a
second pass (its ``(n, n)`` statistics summed over the segments first;
``ROADMAP.md``).

The optimizer meets the same question: the update of one segment's
leaves may read nothing of another's. Nothing here looks inside an
optimizer to find out: the round's own default (SGD with momentum) is
leaf by leaf, and a caller who passes another says so with
:func:`leafwise`; one that is not so marked is refused (a global-norm
clip reads every leaf, and must not be marked).

Whether an update is more than leaf by leaf, ELEMENT by element, is asked
of the update itself (:func:`is_elementwise`, its jaxpr): that decides
nothing about what may stream, only how the round hands a segment's
leaves to it (on their tiles in the row's own order, or whole).

A second set, :data:`KERNEL_FORMED_ATTACKS` beside
:data:`KERNEL_ATTACKED`, declares what the sort kernel can do INSIDE its
body: form the byzantine rows of a block from the honest rows of that
block, so that the round writes no attack row and allocates none
(``docs/performance.md``, "How a call reaches a kernel"). A member is a
deterministic function of the honest rows (no key), it lowers in Mosaic
on ``(h, r, 128)`` blocks, and it keeps a column of zeros at zero (a
row's pad columns are not masked there). The streamed round asks
:func:`attacked_in_kernel`, once, before it traces; what the sets do not
name keeps the round that writes the rows. It is a declaration like the
rest: nothing tries a lowering to find out.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, FrozenSet, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import attack_ops, robust

Array = jnp.ndarray


def mean(x: Array) -> Array:
    """The plain average of the rows: the aggregate of a round that
    trusts every worker."""
    return jnp.mean(x, axis=0)


#: aggregates ``(n, d) -> (d,)`` whose column j reads column j alone
AGGREGATES: FrozenSet[Callable] = frozenset(
    {mean, robust.trimmed_mean, robust.coordinate_median, robust.mean_of_medians}
)

#: attacks whose column j reads column j of the honest rows alone (a
#: reduction over WORKERS is fine: it is the same for every column)
ATTACKS: FrozenSet[Callable] = frozenset(
    {attack_ops.sign_flip, attack_ops.empire, attack_ops.little, attack_ops.mimic}
)


#: attacks of :data:`ATTACKS` that the sort kernel forms in its body from
#: the block of honest rows it holds: no key read, lowers in Mosaic, a
#: column of zeros stays zero. (``little`` is not one: its ``ndtri`` of a
#: Python float is traced under its ``jit`` to a polynomial with array
#: constants, which Mosaic's lowering of a nested ``jit`` refuses.)
KERNEL_FORMED_ATTACKS: FrozenSet[Callable] = frozenset(
    {attack_ops.sign_flip, attack_ops.empire, attack_ops.mimic}
)

#: aggregates whose kernel takes the honest rows alone and an attack to
#: form the others from: the aggregate -> that form of it (same keywords,
#: and ``attack=``, ``b=``)
KERNEL_ATTACKED: Mapping[Callable, Callable] = {
    robust.trimmed_mean: robust.trimmed_mean_attacked,
    robust.coordinate_median: robust.coordinate_median_attacked,
}


class Leafwise(NamedTuple):
    """An optimizer (``init``, ``update`` as optax's) whose caller declares
    that its update of a leaf reads that leaf's gradient, state and
    parameter alone: SGD, momentum, Adam, AdamW are; a global-norm clip,
    LAMB or anything else that reduces over the whole tree is not."""

    init: Callable
    update: Callable


def leafwise(optimizer: Any) -> Leafwise:
    """Mark ``optimizer`` as one that updates leaf by leaf. The caller's
    word: nothing checks it."""
    return Leafwise(optimizer.init, optimizer.update)


@dataclass(frozen=True)
class RoundAttack:
    """A round's attack ``(honest (h, width), key) -> rows`` made of one
    of the table's functions: ``fn`` is handed the honest rows, or with
    ``of="honest_mean"`` their mean over the workers (what an omniscient
    sign flip negates). The table sees through it to ``fn``."""

    fn: Callable
    of: str = "honest"
    kwargs: Mapping[str, Any] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.of not in ("honest", "honest_mean"):
            raise ValueError(f"of must be 'honest' or 'honest_mean', got {self.of!r}")

    def __hash__(self) -> int:  # a static argument of the kernel's jitted call
        return hash((self.fn, self.of, tuple(sorted(self.kwargs.items()))))

    def __call__(self, honest: Array, key: jax.Array) -> Array:
        given = jnp.mean(honest, axis=0) if self.of == "honest_mean" else honest
        return self.fn(given, **dict(self.kwargs))


#: primitives whose result at a place reads its operands at that place alone
_PLACEWISE = frozenset({
    "abs", "add", "and", "clamp", "convert_element_type", "copy", "div", "eq", "exp", "exp2",
    "expm1", "ge", "gt", "integer_pow", "is_finite", "le", "log", "log1p", "logistic", "lt",
    "max", "min", "mul", "ne", "neg", "not", "or", "pow", "rsqrt", "select_n", "sign", "sqrt",
    "square", "stop_gradient", "sub", "tanh", "xor",
})


def _placewise(jaxpr: Any) -> bool:
    """Whether every equation of ``jaxpr`` is arithmetic on scalars, a
    scalar spread over an array, or a :data:`_PLACEWISE` primitive on
    arrays of one shape (scalars beside them)."""
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values() if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        if inner:  # jit, custom_jvp_call, ...: as good as what it wraps
            if not all(_placewise(getattr(v, "jaxpr", v)) for v in inner):
                return False
            continue
        shapes = {tuple(v.aval.shape) for v in [*eqn.invars, *eqn.outvars]} - {()}
        if not shapes:
            continue
        if eqn.primitive.name == "broadcast_in_dim":
            if eqn.invars[0].aval.shape != ():
                return False
        elif eqn.primitive.name not in _PLACEWISE or len(shapes) > 1:
            return False
    return True


def is_elementwise(optimizer: Any, params: Any, state: Any) -> bool:
    """Whether ``optimizer.update`` on ``params`` and ``state`` (trees of
    arrays or of their shapes) computes every element of its results from
    the elements at the same place of its arguments (and scalars) alone:
    SGD, momentum, Adam and AdamW do; a per-leaf norm, a trust ratio or a
    factored second moment does not. Read off the update's own jaxpr,
    equation by equation (:func:`_placewise`), so it holds for this
    optimizer on these shapes and says nothing by name; what the reading
    does not know counts as not elementwise. Such an update gives the
    same elements whatever order a leaf's elements are handed to it in,
    which is what lets the streamed round run it on a leaf's tiles in the
    row's own order."""
    return _placewise(jax.make_jaxpr(optimizer.update)(params, state, params).jaxpr)


def _listed(fn: Callable) -> Callable:
    """The function under ``functools.partial`` and :class:`RoundAttack`."""
    while True:
        if isinstance(fn, partial):
            fn = fn.func
        elif isinstance(fn, RoundAttack):
            fn = fn.fn
        else:
            return fn


def is_coordinatewise_aggregate(fn: Callable) -> bool:
    return _listed(fn) in AGGREGATES


def is_coordinatewise_attack(fn: Callable) -> bool:
    return _listed(fn) in ATTACKS


def attacked_in_kernel(aggregate: Callable, attack: Any) -> Optional[Callable]:
    """The aggregate of the honest rows and of the rows ``attack`` makes of
    them as ONE call ``(honest (h, width), b=) -> (width,)`` whose kernel
    forms the attack's rows in its body, or ``None`` where the table does
    not declare both: the aggregate one of :data:`KERNEL_ATTACKED` (seen
    through ``partial``, its arguments kept), the attack a
    :class:`RoundAttack` itself (which ignores the round's key; a subclass
    may not) of one of :data:`KERNEL_FORMED_ATTACKS`. Whether a kernel
    serves the rows at hand is the gate's to say
    (``robust.attacked_serves``), not the table's."""
    if type(attack) is not RoundAttack or attack.fn not in KERNEL_FORMED_ATTACKS:
        return None
    form = KERNEL_ATTACKED.get(_listed(aggregate))
    if form is None:
        return None
    bound = aggregate if isinstance(aggregate, partial) else partial(aggregate)
    return partial(form, *bound.args, **bound.keywords, attack=attack)


def refusal(aggregate: Callable, attack: Any, optimizer: Any) -> Dict[str, str]:
    """What of a segmented round's three functions this table does not
    list, by role: empty where the round may stream."""
    out: Dict[str, str] = {}
    if not is_coordinatewise_aggregate(aggregate):
        out["aggregate"] = repr(_listed(aggregate))
    if attack is not None and not is_coordinatewise_attack(attack):
        out["attack"] = repr(_listed(attack))
    if optimizer is not None and not isinstance(optimizer, Leafwise):
        out["optimizer"] = "not marked with coordinatewise.leafwise(...)"
    return out


__all__ = [
    "AGGREGATES",
    "ATTACKS",
    "KERNEL_ATTACKED",
    "KERNEL_FORMED_ATTACKS",
    "Leafwise",
    "RoundAttack",
    "attacked_in_kernel",
    "is_coordinatewise_aggregate",
    "is_coordinatewise_attack",
    "is_elementwise",
    "leafwise",
    "mean",
    "refusal",
]
