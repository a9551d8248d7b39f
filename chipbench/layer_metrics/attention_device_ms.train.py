"""Device milliseconds of one step inside `model.attention`: grouped-query
causal attention, projections included (`models/nemotron_h.py:
gqa_attention`), in all three passes. Placed by the label an op's
`op_name` holds (`chipbench/scope_paths.py`); `None` for a model with no
such layer. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "model.attention")
