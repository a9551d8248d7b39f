"""Device milliseconds of one step in part `model.attention_core`: the
attention kernels and what their call puts around them
(`byzpy_tpu/ops/pallas_attention.py`: `causal_attention`'s pads to whole
blocks, its three calls and the slices back to `t`; the backward rule's
`delta` row-sum, its transpose and pad and the two backward calls; where the
kernels do not serve, `models/layers.py:blocked_causal_attention`'s `lax.map`
over query blocks), in all three passes. Placed by the LAST `model.*` /
`stream.*` label of an op's `op_name` (`chipbench/scope_parts.py`,
`chipbench/PARTS.md`); `None` for a program that never enters the scope (the
parent of the PR that added it). Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.attention_core")
