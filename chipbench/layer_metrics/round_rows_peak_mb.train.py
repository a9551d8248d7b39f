"""Megabytes of the largest float32 array with a leading axis of h (the
honest workers) or n (all of them) that the streamed round makes under
its labels `stream.rows` (a segment's gradient rows, the stack the
aggregate reads) or `stream.boundary` (the boundaries kept for every
worker): what the round holds beside the model's own arrays. For a round
that streams it is h rows of the LARGEST SEGMENT (2,403 for the Nemotron
configuration's expert block, six rows of 100.1M in whole tiles, since
the sort kernel forms the byzantine rows itself, PR 43; n rows where a
route writes them); were the (n, d) stack back it would be n rows of d
(21,343 there). The label is what finds it: an array with a leading 8
elsewhere in the step is the held experts' matrices or a round of theirs,
not the round path's. `None` where the compiled step has neither label
(the (n, d) rounds of the accepted cells have `matrix_copies.train` for
their stack). Read from the compiled program's text; repeats exactly.
Source: program_counter."""

import math
import re

_F32 = re.compile(r"= f32\[(\d+(?:,\d+)+)\]")


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text:
        return None
    n = int(ctx.config["n_nodes"])
    leading = {n, n - int(ctx.config["n_byzantine"])}
    largest = 0
    for line in text.splitlines():
        if "stream.rows" not in line and "stream.boundary" not in line:
            continue
        shape = _F32.search(line)
        if shape:
            dims = [int(dim) for dim in shape[1].split(",")]
            if dims[0] in leading:
                largest = max(largest, math.prod(dims))
    return 4 * largest / 1e6 if largest else None
