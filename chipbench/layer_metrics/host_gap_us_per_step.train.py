"""Microseconds the device waits between two executions of the step in
the traced window, mean over the step boundaries: the end of one
`XLA Modules` event to the start of the next, on the device's clock
alone (no host span is involved, so the two clocks' skew is not either).
Source: device_trace, through `chipbench/scope_join.py`."""

from chipbench import scope_join


def read(ctx):
    joined = scope_join.of(ctx)
    return None if joined is None else joined["host_gap_us_per_step"]
