"""Device milliseconds of one step in part `model.attention_core` OUTSIDE
the kernels: the part (`attention_core_device_ms.train`) less `scope_join`'s
`kernel_ms` of the `causal_attention_*` and `window_attention_*` kinds
(`opcount_attention.PRODUCTS`, `opcount_window_attention.KINDS`). What is
left is what the call puts at the kernels' door: the pads to whole blocks,
the slices back to `t`, the backward's `delta`, relayouts of q / k / v.
`None` where the compiled step never enters the scope (the parent of the PR
that added it) or holds no such kernel (the `lax.map` route, the CPU
rehearsal: the whole part is the core there). Source: device_trace."""

from chipbench import opcount_attention, opcount_window_attention, scope_join, scope_parts


def read(ctx):
    core = scope_parts.part_ms(ctx, "model.attention_core")
    joined = scope_join.of(ctx) if core is not None else None
    if joined is None:
        return None
    kernels = sum(joined["kernel_ms"].get(kind, 0.0)
                  for kind in (*opcount_attention.PRODUCTS, *opcount_window_attention.KINDS))
    return core - kernels if kernels else None
