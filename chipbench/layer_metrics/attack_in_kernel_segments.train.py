"""How many segments of a streamed round hand the sort kernel their honest
rows alone and have it form the byzantine rows in its body: the
`tpu_custom_call` instructions of the compiled step whose line holds the
kernel's name, `sorted_reduce_stream_attacked` (`byzpy_tpu/ops/
pallas_kernels.py`; one call a segment). The cell's number of segments where
every segment is served so (embedding, blocks, head); 0 where the round
writes the attack's rows into an (n, width) stack and aggregates that (the
program before PR 43, an attack or an aggregate the program's table does not
declare, no byzantine worker). `None` where the model declares no segments
(the compiled step holds no `round.segment_bwd`): the (n, d) round never
takes this route. Read from the compiled program's text; a count, repeats
exactly. Source: program_counter."""

KERNEL = "sorted_reduce_stream_attacked"


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or "round.segment_bwd" not in text:
        return None
    return sum('custom_call_target="tpu_custom_call"' in line and KERNEL in line
               for line in text.splitlines())
