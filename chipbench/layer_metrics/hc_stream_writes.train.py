"""How often a worker's residual streams are written whole by the
hyper-connections of one compiled step: instructions whose `op_name` (their
own, or that of an instruction fused into them) holds `model.hc_maps` or
`model.hc_mix` and whose result (one of them, of a fusion with several) is
float32 of exactly `tokens x hc_mult x hidden_size` elements, each counted
once, in whatever shape it is laid. Instructions inside a fused computation
write nothing of their own and are not counted; nor are `bitcast`,
`get-tuple-element`, `tuple` and `parameter`. Each such instruction stands
in a loop over the honest workers, and a sublayer's least is one in the
first forward and one in the backward. Read from the compiled program's
text (`chipbench/scope_paths.py:read_text`); a count, repeats exactly.
`None` where the configuration has no `hc_mult` or no instruction holds the
labels. Source: program_counter."""

import math
import re

from chipbench import scope_paths

_FLOAT32 = re.compile(r"f32\[([\d,]*)\]")
_WRITES_NOTHING = {"bitcast", "get-tuple-element", "tuple", "parameter", ""}
_LABELS = ("model.hc_maps", "model.hc_mix")


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or "hc_mult" not in ctx.config:
        return None
    elements = (int(ctx.mix["tokens_per_worker"]) * int(ctx.config["hc_mult"])
                * int(ctx.config["hidden_size"]))
    instructions = scope_paths.read_text(text)
    fused = set()  # computations a fusion calls: their instructions write nothing
    computation, inside = None, {}
    for line in text.splitlines():
        if computation is None:
            head = scope_paths._COMPUTATION.match(line)
            computation = head.group(1) if head else None
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = scope_paths._INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        inside[name] = (computation, rest.partition(instructions[name]["opcode"] + "(")[0])
        if instructions[name]["opcode"] == "fusion":
            calls = scope_paths._CALLS.search(rest)
            if calls:
                fused.add(calls.group(1))
    labelled = writes = 0
    for name, (computation, result) in inside.items():
        ins = instructions[name]
        if computation in fused or ins["opcode"] in _WRITES_NOTHING:
            continue
        if not any(label in path for path in ins["paths"] for label in _LABELS):
            continue
        labelled += 1
        writes += any(math.prod(int(x) for x in dims.split(",") if x) == elements
                      for dims in _FLOAT32.findall(result))
    return writes if labelled else None
