"""Mean host time of the program's ``swe.segment`` span (the scalar ``t`` to
the device, then the launch of one segment's program) over the segments of
the window, in us.  The window's segments are the last ones the program
ran: set-up runs two before it, nothing runs one after it."""
from bench import program_trace


def read(ctx):
    n = ctx.window.units
    segments = program_trace.spans("swe.segment")[-n:]
    if len(segments) < n:
        return None
    return sum(e["dur"] for e in segments) / n
