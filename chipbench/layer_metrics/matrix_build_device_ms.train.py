"""Device milliseconds of one step inside `round.build_matrix`: the
attack on the honest rows and the concatenate into the (n, d) matrix
(`parallel/ps.py:build_matrix`). A concatenate that the compiler fused
into its producers shows under `scope_unattributed_pct.train` instead.
Source: device_trace, through `chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    return scope_join.scope_ms(ctx, "round.build_matrix")
