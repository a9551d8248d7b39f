"""Device milliseconds of one step in part `model.ssm_gate`: what of the Mamba-2
mixer is neither a projection nor the scan nor a norm: the causal depthwise
convolution, `silu`, `softplus`, the skip, the gate, the group norm's scale
(`byzpy_tpu/models/nemotron_h.py:mamba2_mixer`), in all three passes. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.ssm_gate")
