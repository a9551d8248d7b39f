"""The hyper-connections' share of their HBM roofline inside one step: the
least bytes they must move (`chipbench/opcount_hyper_connections.py`: six
times a worker's float32 streams a sublayer, over the configuration's
sublayers and the honest workers) at `peaks.json`'s `hbm_bytes_per_s`, over
the device time of `model.hc_maps` and `model.hc_mix`
(`hc_device_ms.train`). The count is a floor (the `(tokens, hidden)` arrays
and every second read are left out), so the share cannot pass 100; bound by
bytes, not operations (a sublayer's mappings are 24 numbers a position).
`None` where the compiled step never enters the labels, the configuration
has no `hc_mult`, or the device's kind has no peak (the CPU rehearsal).
Source: device_trace."""

from chipbench import opcount_hyper_connections, scope_paths


def read(ctx):
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    if entry is None or "hc_mult" not in ctx.config:
        return None
    ms = scope_paths.path_ms(ctx, "model.hc_maps", "model.hc_mix")
    if not ms:
        return None
    least_s = opcount_hyper_connections.least_bytes_per_step(
        ctx.config, ctx.mix) / entry["hbm_bytes_per_s"]
    return 100.0 * least_s / (1e-3 * ms)
