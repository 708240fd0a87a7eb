"""Host seconds of ``build_simulation``: mesh generation, partition,
config resolution, state placement."""


def read(ctx):
    return ctx.setup.get("mesh_s")
