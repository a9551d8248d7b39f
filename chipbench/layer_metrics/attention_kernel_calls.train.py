"""How many Mosaic kernels the compiled step runs inside `model.attention`:
the `tpu_custom_call` instructions whose `op_name` holds the scope. 0 for
a step whose attention is XLA's (`lax.map` over query blocks: the score
matrix goes through HBM); one for each pass's kernel where the
block-causal kernels serve (`byzpy_tpu/ops/pallas_attention.py`: forward,
the segment's second forward, dq, dk/dv: 4). `None` for a model with no
attention layer. Read from the compiled program's text; a count, repeats
exactly. Source: program_counter."""

import re

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or "model.attention" not in text:
        return None
    calls = 0
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            path = _OP_NAME.search(line)
            calls += bool(path and "model.attention" in path.group(1))
    return calls
