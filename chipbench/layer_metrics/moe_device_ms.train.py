"""Device milliseconds of one step inside `model.moe_route` and
`model.moe_experts`: the expert layers' router, gather, held experts,
shared expert and combine (`parallel/moe.py:held_experts_ffn`), in all
three passes. Placed by the label an op's `op_name` holds
(`chipbench/scope_paths.py`); `None` for a model with no such layer.
Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.moe_route", "model.moe_experts")
