"""Rows returned per second: every row of every request that returned
inside the window, over the window's whole length (host clock)."""
from bench.readers import rows_served


def read(ctx):
    return rows_served(ctx) / ctx.seconds
