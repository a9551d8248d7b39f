"""Device milliseconds of one step inside `model.delta_rule`: the gated delta
rule of the Gated DeltaNet mixers in its chunked form
(`byzpy_tpu/models/qwen3_next.py:gated_delta_rule_chunked`: the chunk's
triangular system, the products inside a chunk, the scan over chunks), in the
forward pass, the segments' second forward and the backward pass together.
Ops are placed by the label their `op_name` holds in the compiled text
(`chipbench/scope_paths.py`); `None` for a model with no such layer.
Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.delta_rule")
