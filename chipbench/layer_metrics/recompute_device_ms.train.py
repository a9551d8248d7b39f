"""Device milliseconds of one step inside `round.segment_recompute`: the
segments' second forward, from the boundary kept, that the streamed round
pays so that no segment's activations outlive its turn (`parallel/ps.py`).
The backward ops proper carry `round.segment_bwd` and are not counted.
`None` for a round that does not stream. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    return scope_paths.path_ms(ctx, "round.segment_recompute", without=("round.segment_bwd",))
