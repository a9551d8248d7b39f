"""Device milliseconds of one step inside `model.mla_latent`: latent
attention's down- and up-projections of queries and keys/values, the norm
on each latent and the rotary turn (`byzpy_tpu/models/glm4_moe_lite.py:
mla_attention`), in all three passes; the attention core and the output
projection stand outside it, in `model.attention`. Placed by the label an
op's `op_name` holds (`chipbench/scope_paths.py`); `None` for a program
that never enters the scope. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.mla_latent")
