"""Peak bytes in use on the fullest chip, after the window and before the
reference runs (``device.memory_stats()``), in GB. Source:
program_counter."""


def read(ctx):
    return ctx.outcome["memory_peak_bytes"] / 1e9
