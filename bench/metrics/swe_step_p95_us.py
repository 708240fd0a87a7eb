"""95th percentile, over all segments of the window, of each segment's
wall time over its steps, in us."""
from bench.harness import percentile


def read(ctx):
    w = ctx.window
    return percentile([s / w.work_per_unit * 1e6 for s in w.unit_seconds], 95)
