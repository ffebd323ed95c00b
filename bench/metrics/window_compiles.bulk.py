"""Compiles inside the window: the plan cache's trace count and XLA
backend compiles seen by a jax.monitoring listener. Should read 0."""
from bench.readers import compiles


def read(ctx):
    return compiles(ctx)
