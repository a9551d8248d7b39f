"""Device milliseconds of one step in part `model.mlp`: the dense block's gated
MLP (`byzpy_tpu/models/glm4_moe_lite.py:_gated_mlp`), in all three passes. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.mlp")
