"""Megabytes of the largest float32 array with a leading axis of n (the
workers) anywhere in the compiled step: the round's gradient rows. For a
round that streams it is n rows of the LARGEST SEGMENT (3,204 for the
Nemotron configuration's expert block); were the (n, d) stack back it
would be n rows of d (21,343 there). Reported only where the model
declares segments (the compiled step holds `round.segment_bwd`): the
(n, d) rounds of the accepted cells have `matrix_copies.train` for their
stack. Read from the compiled program's text; repeats exactly. Source:
program_counter."""

import re


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or "round.segment_bwd" not in text:
        return None
    n = int(ctx.config["n_nodes"])
    largest = 0
    for dims in re.findall(r"f32\[((?:1,)?%d,[\d,]+)\]" % n, text):
        elements = 1
        for dim in dims.split(","):
            elements *= int(dim)
        largest = max(largest, elements)
    return 4 * largest / 1e6 if largest else None
