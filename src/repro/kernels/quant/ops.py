"""jit wrappers for the quantization kernels.

The kernels compile for the TPU; ``interpret=True`` runs them in Pallas
interpret mode on any backend (the CPU tests).
"""
import functools

import jax

from repro.kernels.quant.quant import quantize_pallas, dequantize_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x, interpret: bool = False):
    return quantize_pallas(x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def dequantize(q, s, shape, dtype, interpret: bool = False):
    return dequantize_pallas(q, s, shape, dtype, interpret=interpret)
