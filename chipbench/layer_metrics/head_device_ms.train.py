"""Device milliseconds of one step in part `model.head`: the product with
`w_head` and the cross-entropy (`_head` of both language models; in GLM both
terms), second forward and backward: the head has no first forward. Its norm
is `model.norm`'s. Placed by the LAST `model.*` / `stream.*` label of an op's `op_name`
(`chipbench/scope_parts.py`, `chipbench/PARTS.md`); `None` for a program that
never enters the scope. Source: device_trace."""

from chipbench import scope_parts


def read(ctx):
    return scope_parts.part_ms(ctx, "model.head")
