"""Device milliseconds of one step inside `round.aggregate`: whatever
`ops/robust.py` routed the aggregate to, with the padded copy before a
kernel, the kernel and the slice after it (`agg_kernel_device_ms.train`
is the kernel alone; the difference is what the route costs around it).
Source: device_trace, through `chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    return scope_join.scope_ms(ctx, "round.aggregate")
