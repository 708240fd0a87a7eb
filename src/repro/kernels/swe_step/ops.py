"""jit wrapper for the SWE element-update kernel.

The kernel compiles for the TPU; ``interpret=True`` runs it in Pallas
interpret mode on any backend (the CPU tests).
"""
import functools

import jax

from repro.kernels.swe_step.swe_step import swe_step_pallas


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def swe_step(u, u_n, nx, ny, edge_type, area, valid, h_sea, *, dt,
             interpret: bool = False):
    return swe_step_pallas(u, u_n, nx, ny, edge_type, area, valid, h_sea,
                           dt=dt, interpret=interpret)
