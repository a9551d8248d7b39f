"""Device milliseconds of one step in part `model.norm`: every RMSNorm
(`byzpy_tpu/models/layers.py:rms_norm`: the blocks' pre-norms, the head's,
latent attention's two, Mamba-2's gated group norm, the MTP module's three),
in all three passes. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.norm")
