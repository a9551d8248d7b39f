"""Megabytes each chip puts on the interconnect per step: every
collective of the compiled step's entry computation, by the benchmark's
own copy of the HLO parser. A count; repeats exactly. Nothing to read on
one chip. Source: program_counter."""

from chipbench import hlo_collectives


def read(ctx):
    chips = int(ctx.cell["chips"])
    text = ctx.outcome.get("compiled_text")
    if chips < 2 or not text:
        return None
    per = hlo_collectives.wire_bytes_per_device(text, default_group=chips)
    ctx.say(collective_bytes_per_device=per)
    return sum(per.values()) / 1e6
