"""Share of the traced steady window in which no op ran on the device,
mean over chips (the same busy and window seconds the result line's
``device`` object carries). Source: device_trace."""


def read(ctx):
    window = ctx.outcome["measured"].get("device_window")
    if window is None:
        return None
    return 100.0 * (1.0 - window["busy_s"] / window["window_s"])
