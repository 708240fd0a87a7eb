"""Host seconds of the program's ``swe.build.mesh_gen`` span: the bight
mesh's generation and the step it keeps stable."""
from bench import program_trace


def read(ctx):
    return program_trace.last_seconds("swe.build.mesh_gen")
