"""How many of its own workers a chip of the mesh round runs ONE AFTER
ANOTHER: the trip count of the `while` under `round.fwdbwd` whose body holds
the model's contractions (`convolution` / `dot` instructions that carry
`round.fwdbwd` in their `op_name`, in the body or in a computation it
calls), read off the compiled step's text (`parallel/ps.py:
_mesh_train_step`; a chip's n / k workers, 2 in the four-chip cell). 0 where
the partitioned step has no such loop: the workers a chip holds are computed
side by side under `vmap` (the program before PR 47, or an n the node axis
does not divide). `None` where the step was not partitioned over chips (no
`num_partitions` above 1 in the module's header: a one-chip cell), where
there is no compiled text, and where a loop is there and its count cannot be
read. The count is the backend's `known_trip_count` where the line has one,
else the constant the loop's condition compares its counter with
(`direction=LT`; the round's loops count from 0). Read from the compiled
program's text; a count, repeats exactly. Source: program_counter."""

import re

SCOPE = "round.fwdbwd"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALLED = re.compile(r"\b(?:calls|body|to_apply)=%?([\w.\-]+)")
_CONTRACTION = re.compile(r"\s(?:convolution|dot)\(")
_KNOWN = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CONSTANT = re.compile(r"^\s*%?([\w.\-]+) = [su]\d+\[\]\S* constant\((\d+)\)")
_COMPARE = re.compile(r"ROOT\s+%?[\w.\-]+ = pred\[\]\S* compare\(%?[\w.\-]+, %?([\w.\-]+)\), direction=LT")


def _computations(text):
    found, name = {}, None
    for line in text.splitlines():
        if name is None:
            head = _COMPUTATION.match(line)
            if head:
                name = head.group(1)
                found[name] = []
        elif line.startswith("}"):
            name = None
        else:
            found[name].append(line)
    return found


def _holds_contraction(found, name, seen):
    if name in seen or name not in found:
        return False
    seen.add(name)
    for line in found[name]:
        if _CONTRACTION.search(line) and SCOPE in line:
            return True
        if any(_holds_contraction(found, called, seen) for called in _CALLED.findall(line)):
            return True
    return False


def _trips(found, line):
    known = _KNOWN.search(line)
    if known:
        return int(known.group(1))
    condition = re.search(r"\bcondition=%?([\w.\-]+)", line)
    lines = found.get(condition.group(1), ()) if condition else ()
    constants = dict(m.groups() for m in map(_CONSTANT.match, lines) if m)
    for root in lines:
        compared = _COMPARE.search(root)
        if compared and compared.group(1) in constants:
            return int(constants[compared.group(1)])
    return None


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    partitions = re.search(r"\bnum_partitions=(\d+)", text.split("\n", 1)[0])
    if not partitions or int(partitions.group(1)) < 2:
        return None
    found = _computations(text)
    counts = []
    for lines in found.values():
        for line in lines:
            if " while(" not in line or SCOPE not in line:
                continue
            body = re.search(r"\bbody=%?([\w.\-]+)", line)
            if body and _holds_contraction(found, body.group(1), set()):
                counts.append(_trips(found, line))
    if None in counts:
        return None
    return max(counts, default=0)
