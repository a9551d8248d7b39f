"""Device milliseconds of one step inside `round.fwdbwd`: the vmapped
per-worker forward and backward of the model, ravel and gradient cast
included (`parallel/ps.py`, `models/nets.py`). Each op of the traced
step is placed by the `op_name` its instruction carries in the compiled
text (`chipbench/scope_join.py`). Source: device_trace."""

from chipbench import scope_join


def read(ctx):
    return scope_join.scope_ms(ctx, "round.fwdbwd")
