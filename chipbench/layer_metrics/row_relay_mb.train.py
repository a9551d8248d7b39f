"""Megabytes of leaf-sized gradient moved without arithmetic inside the
forward/backward loop, per iteration (one honest worker): in the
computation that the entry computation's `while` names as `body=` (the
first `while` whose carry holds the f32[n, ., 128] stack of folded rows),
the bytes of the results of top-level `copy`, `concatenate`, `transpose`
and `reshape` instructions whose result is f32 of at least 2^17 elements
(a `reshape` the TPU compiler leaves standing changes a layout: one that
does not is a `bitcast` in this text).
Fused computations are computations of their own and count in none: a
relayout fused into the write that places a leaf in the stack is that
write. 86.57 where every weight gradient whose minor dimension is 256 or
512 is relaid row-major (41.81) and `ravel` concatenates the row (44.76)
before the loop writes it; 0 where each leaf's gradient goes into the
stack as it lies. `None` where no loop carries such a stack (a mesh).
Read from the compiled program's text; repeats exactly. Source:
program_counter."""

import re

_RESULT = re.compile(r"^\s*(?:ROOT )?%?\S+ = f32\[([\d,]*)\]\S* (copy|concatenate|transpose|reshape)\(")
_BODY = re.compile(r"body=(%?[\w.\-]+)")
_LEAF_SIZED = 1 << 17


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    entry = text.partition("\nENTRY ")[2].partition("\n}")[0]
    stack = re.compile(r"f32\[%d,\d+,128\]" % int(ctx.config["n_nodes"]))
    body = None
    for line in entry.splitlines():
        carry, is_while, rest = line.partition(" while(")
        if is_while and stack.search(carry) and _BODY.search(rest):
            body = _BODY.search(rest).group(1)
            break
    if body is None:
        return None
    computation = text.partition("\n" + body + " (")[2].partition("\n}")[0]
    if not computation:
        return None
    moved = 0
    for line in computation.splitlines():
        found = _RESULT.match(line)
        if not found:
            continue
        elements = 1
        for dim in found.group(1).split(","):
            elements *= int(dim) if dim else 1
        if elements >= _LEAF_SIZED:
            moved += 4 * elements
    return moved / 1e6
