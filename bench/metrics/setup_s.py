"""Process start to the first timed unit: JAX start-up, the inputs, mesh
and partition, compile or cache load, one warm unit."""


def read(ctx):
    return ctx.setup.get("total_s")
