"""Collective time under no other operation, per solver step, in us; nothing
where the trace holds no collective operation."""


def read(ctx):
    t = ctx.trace
    if not t.collective_s:
        return None
    return t.exposed_collective_s / ctx.window.work * 1e6
