"""Device milliseconds of one step inside `round.update`: the norm of
the aggregate, unravel, the optimizer and `apply_updates`, and, nested in
it on a mesh, `round.param_gather`. Source: device_trace, through
`chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    return scope_join.scope_ms(ctx, "round.update", "round.param_gather")
