"""The block-causal attention kernels' share of the MXU's bf16 peak inside
one step where queries / keys and values differ in width: as
`attention_kernel_mxu_pct.train`, with every product counted at its own
published width (`chipbench/opcount_attention_qk_v.py`: `qk_nope_head_dim +
qk_rope_head_dim` for a product with queries or keys, `v_head_dim` for one
with values or the output's cotangent; padded columns not counted) over the
device time of the step's `causal_attention_*` calls (`scope_join`'s
`kernel_ms`), as a share of `peaks.json`'s `bf16_flops_per_s`.

A kernel's calls a step: its `tpu_custom_call` instructions in the compiled
step, each of which stands in a loop over the honest workers. `None` where
the step holds no such kernel (the `lax.map` route, the CPU rehearsal), the
configuration's reference gives no `qk_nope_head_dim`, or the device's kind
has no peak. Source: device_trace."""

from chipbench import opcount_attention_qk_v, scope_join


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    joined = scope_join.of(ctx)
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    arch = ctx.config.get("reference", {}).get("arch", {})
    if not text or joined is None or entry is None or "qk_nope_head_dim" not in arch:
        return None
    honest = int(ctx.config["n_nodes"]) - int(ctx.config["n_byzantine"])
    kinds = list(opcount_attention_qk_v.PRODUCTS)
    named = list(scope_join.read_labels(text, kinds).kernel.values())
    flops = ms = 0.0
    for kind in kinds:
        flops += named.count(kind) * honest * opcount_attention_qk_v.kernel_flops(
            kind, int(arch["num_attention_heads"]),
            int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"]),
            int(arch["v_head_dim"]), int(ctx.mix["tokens_per_worker"]))
        ms += joined["kernel_ms"].get(kind, 0.0)
    if not ms:
        return None
    return 100.0 * flops / (1e-3 * ms) / entry["bf16_flops_per_s"]
