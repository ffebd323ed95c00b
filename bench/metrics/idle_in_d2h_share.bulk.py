"""Share of the device's idle time in the traced window that a raven.d2h
span (a group's copy back to the host and its split) covers."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    return spans.idle_covered_share(ctx.events, ("raven.d2h",))
