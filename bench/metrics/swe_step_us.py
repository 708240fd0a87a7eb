"""Window wall time over the solver steps completed in it, in us."""


def read(ctx):
    w = ctx.window
    return w.seconds / w.work * 1e6
