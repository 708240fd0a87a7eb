"""Host seconds of the first call beyond a warm one: trace, lower, and
compile or load from the compilation cache."""


def read(ctx):
    return ctx.setup.get("compile_s")
