"""Device milliseconds of one step inside its Pallas kernels: the custom
calls of the compiled step whose line holds a name of the program's
`observability.catalog.KERNELS` (the `name=` of the `pallas_call`), not
the instruction name the compiler made. None where the step holds no
such call (the XLA route, the CPU rehearsal). Source: device_trace,
through `chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    joined = scope_join.of(ctx)
    if joined is None or not joined["kernel_ms"]:
        return None
    return sum(joined["kernel_ms"].values())
