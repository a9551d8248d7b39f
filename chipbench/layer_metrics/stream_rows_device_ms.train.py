"""Device milliseconds of one step in parts `stream.rows` and `stream.boundary`:
the streamed round's own work inside `round.fwdbwd`
(`byzpy_tpu/parallel/ps.py:_streamed_train_step`): a worker's gradient placed
in the segment's folded stack, its batch and boundary read from the stacks
kept, kept arrays, cotangents, losses and aux written back. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "stream.rows", "stream.boundary")
