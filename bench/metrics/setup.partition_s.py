"""Host seconds of the program's ``swe.build.partition`` span: the RCB
partition of the mesh, its exchange rounds and the partitioned state."""
from bench import program_trace


def read(ctx):
    return program_trace.last_seconds("swe.build.partition")
