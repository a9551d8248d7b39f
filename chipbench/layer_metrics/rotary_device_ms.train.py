"""Device milliseconds of one step in part `model.rotary`: the turn by
position (`byzpy_tpu/models/layers.py:rotary`: the angles, `cos` / `sin`,
the 2 x 2 rotation of every pair, its transpose), in all three passes.
Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`): in latent attention the
turn stands inside `model.mla_latent`, whose part it leaves
(`mla_latent_device_ms.train` asks what a path HOLDS and keeps it). `None`
for a program that never enters the scope (the parent of the PR that added
it, a model that turns nothing: Nemotron-H). Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.rotary")
