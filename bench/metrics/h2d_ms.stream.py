"""Median duration (ms) of a group's copy to the device in the traced
window: the raven.h2d span, padding and one device copy per input column."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.median_ms(ctx.events, "raven.h2d")
