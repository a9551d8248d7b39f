"""Device milliseconds of one step in part `model.attention_proj`:
attention's products with `w_q`, `w_k`, `w_v` and `w_o` (Qwen3-Next's
`w_q_gate` too; `byzpy_tpu/models/layers.py:attention_proj`, which every
model's attention calls, latent attention for `w_o` alone: its own down- and
up-projections stay `model.mla_latent`), the weights' casts and the
products' transposes, in all three passes. Placed by the LAST `model.*` /
`stream.*` label of an op's `op_name` (`chipbench/scope_parts.py`,
`chipbench/PARTS.md`); `None` for a program that never enters the scope (the
parent of the PR that added it). Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.attention_proj")
