"""Share of one step's device-busy time that no scope owns: fusions whose
instructions lie in two scopes (`mixed`) and ops without an `op_name`
(`unscoped`). 100 where the program declares scopes and its executable
carries none. The run's `heaviest_unattributed` line names the ops.
Source: device_trace, through `chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    joined = scope_join.of(ctx)
    return None if joined is None else joined["unattributed_pct"]
