"""In how many passes a worker's batch goes through the model in an (n, d)
round: the trip count of the `while` under `round.fwdbwd` that carries the
label `stream.passes` in its `op_name` and whose body holds the model's
contractions, read off the compiled step's text (`parallel/ps.py:
_worker_loss_and_grad`; 4 in the four-chip cell, whose workers hold 512
images each). 1 where the step has `round.fwdbwd` and no such loop: the batch
goes through whole (128 images a worker, a bundle that does not declare
`example_mean_loss`, a device that is no TPU, the program before PR 48).
`None` where there is no compiled text, where it has no `round.fwdbwd`, and
where a loop is there and its count cannot be read. Loops, bodies and counts
are found as `mesh_loop_workers.train` finds them (its file's own functions:
`known_trip_count` where the line has one, else the constant the loop's
condition compares its counter with). Read from the compiled program's text;
a count, repeats exactly. Source: program_counter."""

import os
import re

from chipbench import harness

LABEL = "stream.passes"
_loops = harness.load_by_path(
    os.path.join(harness.HERE, "layer_metrics", "mesh_loop_workers.train.py"),
    "mesh_loop_workers.train")


def read(ctx):
    text = ctx.outcome.get("compiled_text")
    if not text or _loops.SCOPE not in text:
        return None
    found = _loops._computations(text)
    counts = []
    for lines in found.values():
        for line in lines:
            if " while(" not in line or LABEL not in line:
                continue
            body = re.search(r"\bbody=%?([\w.\-]+)", line)
            if body and _loops._holds_contraction(found, body.group(1), set()):
                counts.append(_loops._trips(found, line))
    if None in counts:
        return None
    return max(counts, default=1)
