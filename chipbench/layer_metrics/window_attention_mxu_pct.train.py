"""The sliding-window attention kernels' share of the MXU's bf16 peak inside
one step: the operations inside the window that the step's
`window_attention_*` calls must do (`chipbench/opcount_window_attention.py`,
from the configuration's heads, head size and window and the mix's tokens a
worker) over the device time of those calls (`scope_join`'s `kernel_ms`), as
a share of `peaks.json`'s `bf16_flops_per_s`.

A kernel's calls a step: its `tpu_custom_call` instructions in the compiled
step, each of which stands in a loop over the honest workers (the streamed
round's three passes), so instructions x (n - f) calls of one sequence each.
`None` where the step holds no such kernel, the configuration's reference
names no `sliding_window_size`, or the device's kind has no peak.
Source: device_trace."""

from chipbench import opcount_window_attention, scope_join


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    arch = ctx.config.get("reference", {}).get("arch", {})
    joined = scope_join.of(ctx) if text else None
    if joined is None or entry is None or "sliding_window_size" not in arch:
        return None
    honest = int(ctx.config["n_nodes"]) - int(ctx.config["n_byzantine"])
    kinds = list(opcount_window_attention.KINDS)
    named = list(scope_join.read_labels(text, kinds).kernel.values())
    flops = ms = 0.0
    for kind in kinds:
        flops += named.count(kind) * honest * opcount_window_attention.kernel_flops(
            kind, int(arch["num_attention_heads"]), int(arch["head_dim"]),
            int(ctx.mix["tokens_per_worker"]), int(arch["sliding_window_size"]))
        ms += joined["kernel_ms"].get(kind, 0.0)
    if not ms:
        return None
    return 100.0 * flops / (1e-3 * ms) / entry["bf16_flops_per_s"]
