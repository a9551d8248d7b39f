"""Share of the robust step that a plain data-parallel step would not
pay: ``100 * (1 - t_plain / t_robust)``. Both are blocks of the same
number of steps of ``jit_ps_train_step`` in the traced run, by the host
clock, each ending in ``block_until_ready``; the plain one averages the
rows with no byzantine worker and no attack. Source: program_span."""


def read(ctx):
    m = ctx.outcome["measured"]
    if "t_robust_block_s" not in m:
        return None
    return 100.0 * (1.0 - m["t_plain_block_s"] / m["t_robust_block_s"])
