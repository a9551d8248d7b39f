"""Instructions of the compiled step that rewrite the whole (n, .)
gradient matrix outside forward/backward: a `pad` in scope
`round.aggregate` (the route's zero-padded copy to the kernel's tile),
and a `maximum` or `concatenate` in scope `round.build_matrix` whose
result has n rows and at least d columns (the honest and byzantine rows
put side by side, lowered on the TPU as a `maximum` of two pads). 0 where
the round writes its matrix once. Read from the compiled program's text; a
count, repeats exactly. Source: program_counter."""

import re

_REWRITE = re.compile(r" = \w+\[([\d,]*)\]\S* (pad|maximum|concatenate)\(")


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    n, d = int(ctx.config["n_nodes"]), int(ctx.config.get("n_parameters", 0))
    copies = 0
    for line in text.splitlines():
        found = _REWRITE.search(line)
        if not found:
            continue
        op_name = line.partition('op_name="')[2].partition('"')[0]
        if found.group(2) == "pad":
            copies += "round.aggregate" in op_name
        elif "round.build_matrix" in op_name:
            dims = [int(x) for x in found.group(1).split(",") if x]
            copies += dims[-2:-1] == [n] and dims[-1] >= d
    return copies
