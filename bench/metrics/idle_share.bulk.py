"""Share of the traced window in which no operation ran on the device."""
from bench.readers import busy_share


def read(ctx):
    busy = busy_share(ctx)
    return None if busy is None else 1.0 - busy
