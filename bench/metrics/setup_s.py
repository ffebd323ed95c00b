"""Set-up seconds: process start to the end of warm-up (data, model,
session, prepare, serve, and every bucket's program compiled or loaded)."""


def read(ctx):
    return ctx.setup_s
