"""Pallas TPU kernels for the compression plugin's int8 wire format.

Per-block symmetric quantization (block = quant rows of 128 lanes): the
gradient all-reduce's quantize/dequantize hot loop.  VPU-bound elementwise
work with an in-block max reduction; tile = (block_rows, 128) in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 8      # one quant block = 8 x 128 = 1024 elements


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]
                  ).astype(x_ref.dtype)


def quantize_pallas(x, interpret: bool = False):
    """x: any shape -> (q int8 (nblocks, BLOCK_ROWS, LANES), scales (nblocks,1))."""
    flat = x.reshape(-1)
    blk = BLOCK_ROWS * LANES
    pad = (-flat.shape[0]) % blk
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nb = flat.shape[0] // blk
    tiles = flat.reshape(nb, BLOCK_ROWS, LANES)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, BLOCK_ROWS, LANES), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tiles)
    return q, s[:, 0]


def dequantize_pallas(q, s, shape, dtype, interpret: bool = False):
    nb = q.shape[0]
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_ROWS, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK_ROWS, LANES), dtype),
        interpret=interpret,
    )(q, s[:, :, None])
    n = 1
    for d in shape:
        n *= d
    return x.reshape(-1)[:n].reshape(shape)
