"""The block-causal attention kernels' share of the MXU's bf16 peak inside
one step: the operations of the causal half that the step's
`causal_attention_*` calls must do (`chipbench/opcount_attention.py`, from
the configuration's heads and head size, the mix's tokens a worker and the
calls' kinds) over the device time of those calls (`scope_join`'s
`kernel_ms`), as a share of `peaks.json`'s `bf16_flops_per_s`.

A kernel's calls a step: its `tpu_custom_call` instructions in the
compiled step, each of which stands in a loop over the honest workers
(the streamed round's three passes), so instructions x (n - f) calls of
one sequence each. `None` where the step holds no such kernel (the
`lax.map` route, the CPU rehearsal) or the device's kind has no peak.
Source: device_trace."""

from chipbench import opcount_attention, scope_join


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    joined = scope_join.of(ctx)
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    arch = ctx.config.get("reference", {}).get("arch", {})
    if not text or joined is None or entry is None or "num_attention_heads" not in arch:
        return None
    head_dim = arch.get("v_head_dim", arch.get("head_dim"))
    honest = int(ctx.config["n_nodes"]) - int(ctx.config["n_byzantine"])
    named = list(scope_join.read_labels(text, list(opcount_attention.PRODUCTS)).kernel.values())
    flops = ms = 0.0
    for kind in opcount_attention.PRODUCTS:
        instructions = named.count(kind)
        flops += instructions * honest * opcount_attention.kernel_flops(
            kind, int(arch["num_attention_heads"]), int(head_dim),
            int(ctx.mix["tokens_per_worker"]))
        ms += joined["kernel_ms"].get(kind, 0.0)
    if not ms:
        return None
    return 100.0 * flops / (1e-3 * ms) / entry["bf16_flops_per_s"]
