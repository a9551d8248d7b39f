"""Instructions of the compiled step, outside the forward/backward
loop's body, that write the whole gradient matrix with the workers as
sublanes: in the entry computation (the loop's body and every fused
computation are computations of their own), an instruction whose result,
leading 1-dimensions dropped, is a 2-D array of at least h rows (the
honest workers) and at least d columns. A row of such an array is one
sublane of every (8, 128) tile, so writing it costs a pass over the whole
matrix however few rows change; a row of the folded (n, d / 128, 128)
stack is whole tiles and is not counted. `bitcast`, `get-tuple-element`,
`tuple` and `parameter` write nothing and are not counted. 2 where the
stack is relaid for the kernel and the byzantine rows are selected in; 0
where the kernel reads the loop's folded stack; 1 where an aggregate that
wants workers in sublanes (a Gram) makes the compiler relay it. Read from
the compiled program's text; a count, repeats exactly. Source:
program_counter."""

import re

_RESULT = re.compile(r"^\s*(?:ROOT )?%?\S+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
_WRITES_NOTHING = {"bitcast", "get-tuple-element", "tuple", "parameter"}


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    entry = text.partition("\nENTRY ")[2].partition("\n}")[0]
    if not entry:
        return None
    h = int(ctx.config["n_nodes"]) - int(ctx.config.get("n_byzantine", 0))
    d = int(ctx.config.get("n_parameters", 0))
    writes = 0
    for line in entry.splitlines():
        found = _RESULT.match(line)
        if not found or found.group(2) in _WRITES_NOTHING:
            continue
        dims = [int(x) for x in found.group(1).split(",") if x]
        while dims[:1] == [1]:
            dims.pop(0)
        writes += len(dims) == 2 and dims[0] >= h and dims[1] >= d
    return writes
