"""Device milliseconds of one step in the costliest segment's whole turn: its
first forward, second forward, backward, `round.build_matrix`,
`round.aggregate` and `round.update` together, by the `segment.<key>` label
`_streamed_train_step` enters (`chipbench/scope_parts.py:segments`; the run's
`segment_ms` line has every segment). `None` for a round that does not stream
or enters no such label. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    found = scope_parts.segments(ctx)
    turns = [sum(row.values()) for key, row in (found or {}).items()
             if key != scope_parts.NO_SEGMENT]
    return max(turns) if turns else None
