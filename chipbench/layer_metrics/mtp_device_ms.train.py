"""Device milliseconds of one step inside `model.mtp`: the
multi-token-prediction module (its two norms, the projection of the
joined stream and embedded tokens, its expert block, its norm) and the
head's second term, in all three passes. Placed by the label an op's
`op_name` holds (`chipbench/scope_paths.py`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.mtp")
