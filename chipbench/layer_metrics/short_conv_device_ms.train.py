"""Device milliseconds of one step inside the gated short convolution: ops
whose `op_name` holds `model.short_conv` (the gates `B * X` and `C * (.)`
and the 3-tap causal depthwise convolution between them, of
`byzpy_tpu/models/layers.py:gated_short_conv` and its backward rule) and
not `model.short_conv_proj` (the products with `w_in` and `w_out`, whose
label begins with the same letters), in the forward pass, the segments'
second forward and the backward pass together. Placed by the label an op's
`op_name` holds (`chipbench/scope_paths.py`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.short_conv", without=("model.short_conv_proj",))
