"""Device-busy milliseconds of the traced window per request that returned
in it."""
from bench import trace


def read(ctx):
    done = len(ctx.completed())
    if ctx.events is None or not done or not trace.device_ops(ctx.events):
        return None
    return 1e3 * trace.busy_s(ctx.events) / done
