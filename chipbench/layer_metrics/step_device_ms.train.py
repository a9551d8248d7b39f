"""Device-busy milliseconds of one step: the union of the op intervals
inside each execution of the step's program during the traced window,
median over executions and mean over chips. Source: device_trace."""

import statistics

from chipbench import trace_reduce as tr


def read(ctx):
    window = tr.span(ctx.reduced, "window")
    name = ctx.outcome["measured"].get("step_module")
    if window is None or name is None:
        return None
    per_device = []
    for dev in ctx.reduced.devices:
        runs = tr.module_runs(dev, name, window.start, window.end)
        if runs:
            per_device.append(statistics.median(tr.busy_in_runs(dev, runs)))
    if not per_device:
        return None
    return 1e3 * statistics.fmean(per_device)
