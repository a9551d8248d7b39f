"""What tying the embedding table costs the round in memory at the table's
second use: megabytes of float32 arrays of the table's `vocab_size x
hidden_size` elements, alone or under a leading axis of 1, of the h honest
or of the n workers, that the compiled step MAKES in the turn of the
segment that READS the table (the configuration's `tied_table`: `owner`,
`reader`; `byzpy_tpu/models/bundle.py:Segment.reads`), the owner's stack of
rows left out. Counted: the result of every instruction outside fused
computations whose `op_name` (its own, or that of an instruction fused into
it) holds `segment.<reader>` and whose float32 result has that many
elements, in whatever shape it is laid, but `parameter`, `tuple`,
`get-tuple-element`, `bitcast`, `while`, `conditional`, `call` and an
in-place `dynamic-update-slice` (or a fusion whose root is one), which make
nothing. A reader that owns nothing of that size (the head: a norm's
weight) makes such arrays for the tie alone: a worker's gradient of the
table through the head before it lies in the owner's row, a copy of it or
of the table in another layout, a stack a worker of cotangents that would
ride back along the chain. The owner's rows, which both paths' gradients
meet in, are the one array of the tie the round has to keep; that they live
from the reader's turn to the owner's is `peak_hbm_gb.train`'s to show. An
instruction is counted once however often it runs: what the program asks
for, not the allocator's answer. `None` where the configuration names no
`tied_table` or the compiled step holds no `stream.shared_rows`. Read from
the compiled program's text; repeats exactly. Source: program_counter."""

import math
import re

from chipbench import scope_paths

_FLOAT32 = re.compile(r"f32\[([\d,]*)\]")
_MAKES_NOTHING = {"parameter", "tuple", "get-tuple-element", "bitcast", "while", "conditional",
                  "call", "dynamic-update-slice", "optimization-barrier", ""}


def made_elements(text: str, label: str, sizes) -> dict:
    """`{size: elements}`: the float32 results of each of `sizes` elements
    made by the instructions of `text` whose paths hold `label`."""
    instructions = scope_paths.read_text(text)
    fused, roots, lines = set(), {}, {}
    computation = None
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
        lines[name] = (computation, rest)
        if line.lstrip().startswith("ROOT"):
            roots[computation] = instructions[name]["opcode"]
        if instructions[name]["opcode"] == "fusion":
            calls = scope_paths._CALLS.search(rest)
            if calls:
                fused.add(calls.group(1))
    made = dict.fromkeys(sizes, 0)
    for name, (computation, rest) in lines.items():
        opcode, paths = instructions[name]["opcode"], instructions[name]["paths"]
        if computation in fused or opcode in _MAKES_NOTHING:
            continue
        if not any(label in path for path in paths):
            continue
        if opcode == "fusion":
            calls = scope_paths._CALLS.search(rest)
            if calls and roots.get(calls.group(1)) == "dynamic-update-slice":
                continue
        for dims in _FLOAT32.findall(rest.partition(opcode + "(")[0]):
            size = math.prod(int(x) for x in dims.split(",") if x)
            if size in made:
                made[size] += size
    return made


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    tied = ctx.config.get("tied_table")
    if not text or not tied or "stream.shared_rows" not in text:
        return None
    n = int(ctx.config["n_nodes"])
    h = n - int(ctx.config["n_byzantine"])
    elements = int(ctx.config["vocab_size"]) * int(ctx.config["hidden_size"])
    made = made_elements(text, "segment.%s/" % tied["reader"], [k * elements for k in (1, h, n)])
    # the rows are made once, h or n of them, where the reader's turn starts:
    # one such stack is the owner's own and is left out
    rows = next((k for k in (h, n) if made[k * elements]), 0)
    return 4.0 * (sum(made.values()) - rows * elements) / 1e6
