"""The gated short convolutions' share of their HBM roofline inside one
step: the least bytes they must move (`chipbench/opcount_short_conv.py`:
fifteen times a worker's `(tokens, hidden_size)` float32 array a block,
over the configuration's short-convolution blocks and the honest workers)
at `peaks.json`'s `hbm_bytes_per_s`, over the device time of
`model.short_conv` (`short_conv_device_ms.train`). The count is a floor
(the gated product, the convolution's own result and every second read are
left out), so the share cannot pass 100; bound by bytes, not operations
(two products and three multiply-adds a channel a position). `None` where
the compiled step never enters the label, the configuration names no
`layers_held`, or the device's kind has no peak (the CPU rehearsal).
Source: device_trace."""

from chipbench import opcount_short_conv, scope_paths


def read(ctx):
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    if entry is None or "layers_held" not in ctx.config:
        return None
    ms = scope_paths.path_ms(ctx, "model.short_conv", without=("model.short_conv_proj",))
    if not ms:
        return None
    least_s = opcount_short_conv.least_bytes_per_step(
        ctx.config, ctx.mix) / entry["hbm_bytes_per_s"]
    return 100.0 * least_s / (1e-3 * ms)
