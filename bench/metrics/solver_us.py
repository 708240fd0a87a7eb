"""Device time per solver step outside collective operations, in us."""


def read(ctx):
    return ctx.trace.compute_s / ctx.window.work * 1e6
