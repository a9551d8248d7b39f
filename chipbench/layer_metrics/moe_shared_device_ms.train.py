"""Device milliseconds of one step in part `model.moe_shared`: the expert
layers' shared expert, a dense MLP over every token
(`byzpy_tpu/parallel/moe.py:held_experts_ffn`), in all three passes;
`moe_device_ms.train` holds it too. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.moe_shared")
