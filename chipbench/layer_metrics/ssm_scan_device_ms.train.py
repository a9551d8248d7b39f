"""Device milliseconds of one step inside `model.ssm_scan`: the Mamba-2
chunked state-space scan (`models/nemotron_h.py:ssd_chunked`), in the
forward pass, the segments' second forward and the backward pass together.
Ops are placed by the label their `op_name` holds in the compiled text
(`chipbench/scope_paths.py`); `None` for a model with no such layer.
Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.ssm_scan")
