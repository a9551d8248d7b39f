"""The gated delta rule's share of its roofline inside one step: the least
time the rule AS DEFINED could take on this chip (`chipbench/
opcount_delta_rule.py`: a position's three state-sized products a value head
against `peaks.json`'s bf16 peak, its q, k, v, g, beta read once and o
written once against the HBM peak, the larger of the two; times the mix's
tokens a worker, the configuration's Gated DeltaNet blocks, the honest
workers and the step's passes) over the device time of `model.delta_rule`
(`delta_rule_device_ms.train`). Whatever form computes the rule is held to
the rule's own floor, and a chunked form does more, so this cannot pass 100.
`None` where the compiled step never enters the label, or the device's kind
has no peak (the CPU rehearsal). Source: device_trace."""

from chipbench import opcount_delta_rule, scope_paths


def read(ctx):
    entry = ctx.peaks["devices"].get(ctx.devices[0].device_kind)
    arch = ctx.config.get("reference", {}).get("arch", {})
    if entry is None or "linear_num_value_heads" not in arch:
        return None
    ms = scope_paths.path_ms(ctx, "model.delta_rule")
    if not ms:
        return None
    least_s = opcount_delta_rule.least_seconds_per_step(
        ctx.config, ctx.mix, flops_per_s=entry["bf16_flops_per_s"],
        bytes_per_s=entry["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
