"""The whole step's share of the chips' bf16 peak, in %: the operations
``bench/flops.py`` counts per step, times steps per second of the traced
window, over the chips' peak.  The solver's f32 vector work has no published
peak, so the share is small by construction; it bounds the step once
kernels take over parts of it."""
from bench import flops, peaks


def read(ctx):
    w = ctx.window
    rate = flops.swe_step_flops(ctx.config["n_elements"]) * w.work / w.seconds
    return rate / (ctx.chips * peaks.peak(ctx.device_kind).bf16_flops) * 100.0
