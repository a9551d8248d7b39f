"""The cell's aggregator alone, jitted, on a device-resident (n, d)
float32 matrix: the least time the chip could take over its median device
time, in percent. Memory-bound for every aggregator here, so the least
time is bytes over the HBM peak, where bytes count the matrix read once
and the result written once (``chipbench.opcount.aggregate_bytes``), per
chip. A kernel that sweeps the matrix twice cannot pass 50. Source:
device_trace."""

import statistics

from chipbench import trace_reduce as tr


def read(ctx):
    m = ctx.outcome["measured"]
    where = tr.span(ctx.reduced, "agg_alone")
    if where is None or "agg_module" not in m:
        return None
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    if entry is None:  # the CPU rehearsal: no peak, so no share (a TPU
        return None  # kind that is not in the table stops the run earlier)
    peak = entry["hbm_bytes_per_s"]
    least_s = m["agg_matrix_bytes_per_device"] / peak
    per_device = []
    for dev in ctx.reduced.devices:
        runs = tr.module_runs(dev, m["agg_module"], where.start, where.end)
        if runs:
            per_device.append(statistics.median(tr.busy_in_runs(dev, runs)))
    if not per_device:
        return None
    return 100.0 * least_s / statistics.fmean(per_device)
