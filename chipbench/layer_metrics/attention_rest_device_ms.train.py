"""Device milliseconds of one step in part `model.attention` itself: ops
whose LAST `model.*` / `stream.*` label is `model.attention`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`), so nothing that a label
nested in it names (`model.attention_proj`, `model.rotary`,
`model.attention_core`, `model.mla_latent`, `model.norm`). On a program
without the three nested labels (the parent of the PR that added them) that
is projections, turns, kernels and all; with them, what nobody named: the
reshapes to heads and back, the concatenations around a partial turn,
Qwen3-Next's gate multiply, Xing4.0's envelope around the `vmap`; 0 where
the nested labels name all there is. `None` for a step none of whose parts
is attention's. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    found = scope_parts.parts(ctx)
    if not found or not any(part.startswith("model.attention") for part in found):
        return None
    return found.get("model.attention", 0.0)
