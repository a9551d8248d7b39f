"""Device milliseconds of one step inside the hyper-connections: ops whose
`op_name` holds `model.hc_maps` (the norm of a position's flattened streams,
the projection, the sigmoids, the Sinkhorn iterations) or `model.hc_mix`
(the pre-mix `H_pre X`, the write-back `H_res X + H_post^T y`, the streams'
entry copy and exit sum) of `byzpy_tpu/models/xing4.py`, in the forward
pass, the segments' second forward and the backward pass together. Placed
by the label an op's `op_name` holds (`chipbench/scope_paths.py`); `None`
for a program that never enters the scopes. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.hc_maps", "model.hc_mix")
