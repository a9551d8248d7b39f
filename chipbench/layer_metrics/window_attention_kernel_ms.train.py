"""Device milliseconds of one step inside the sliding-window attention
kernels: `scope_join`'s `kernel_ms` of `window_attention_fwd`,
`window_attention_dq` and `window_attention_dkv`
(`byzpy_tpu/ops/pallas_attention.py` with a `window`), summed. The global
blocks' `causal_attention_*` calls are not in it. `None` where the compiled
step holds no such kernel (a model with no windowed block, the `lax.map`
route, a program that has no such kernels: the parent of the PR that added
them). Source: device_trace."""

from chipbench import opcount_window_attention, scope_join


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    joined = scope_join.of(ctx) if text else None
    if joined is None:
        return None
    ms = sum(joined["kernel_ms"].get(kind, 0.0) for kind in opcount_window_attention.KINDS)
    return ms or None
