"""Share of the device's idle time in the traced window that no raven.*
span of the program covers: time the host spent outside every named step
of the serving path."""
from bench import spans


def read(ctx):
    if ctx.events is None:
        return None
    share = spans.idle_covered_share(ctx.events, spans.PREFIX)
    return None if share is None else 1.0 - share
