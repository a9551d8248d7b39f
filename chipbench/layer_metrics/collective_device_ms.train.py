"""Device milliseconds of one step in collective ops: `all-to-all`,
`all-reduce`, `all-gather` and `reduce-scatter` (their `-start` and
`-done` halves included), found by opcode among the ops of the step's
executions (`chipbench/scope_paths.py`). The gradient transpose and the
parameter gather of the mesh round (`parallel/ps.py`). Nothing to read on
one chip. Source: device_trace."""

from chipbench import scope_paths


def read(ctx):
    if int(ctx.cell.get("chips", 1)) < 2:
        return None
    return scope_paths.opcode_ms(ctx, "all-to-all", "all-reduce", "all-gather", "reduce-scatter")
