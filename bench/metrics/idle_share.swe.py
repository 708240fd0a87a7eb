"""Share of the traced window in which no operation ran on the chip, while
the host drove solver segments, in %."""


def read(ctx):
    return ctx.trace.idle_share * 100.0
