"""Share of the device's idle time in the traced window that a raven.h2d
span (a group's copy to the device) covers."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.idle_covered_share(ctx.events, ("raven.h2d",))
