"""Device milliseconds of one step in part `model.ssm_proj`: the Mamba-2 mixers'
products with `w_z`, `w_xbc`, `w_dt` and `w_out`
(`byzpy_tpu/models/nemotron_h.py:mamba2_mixer`), in all three passes. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.ssm_proj")
