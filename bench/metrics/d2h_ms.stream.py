"""Median duration (ms) of a group's copy back to the host in the traced
window: the raven.d2h span, one copy per result column and the split into
per-request answers."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.median_ms(ctx.events, "raven.d2h")
