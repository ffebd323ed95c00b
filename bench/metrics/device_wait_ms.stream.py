"""Median time (ms) a group's completion waited for its result's device
buffers in the traced window: the raven.device_wait span, opened once the
program is dispatched and closed before the first copy back."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.median_ms(ctx.events, "raven.device_wait")
