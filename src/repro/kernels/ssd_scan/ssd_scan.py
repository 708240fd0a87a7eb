"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU-native adaptation of the SSD duality: the intra-chunk term is an
attention-like masked matmul (MXU), the inter-chunk recurrence carries a
(state × head_dim) tile in VMEM scratch across the sequential chunk grid
dimension — the same carry pattern as flash attention's (m, l, acc), and the
on-chip analogue of the paper's chunk-state "halo" hand-off.

Grid: (batch·heads, chunks) with chunks sequential ("arbitrary").
Block shapes: chunk length L (=128, MXU-aligned) × head_dim P × state N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, hstate, *,
            chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        hstate[...] = jnp.zeros_like(hstate)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0].astype(jnp.float32)        # (L, 1)
    bmat = b_ref[0].astype(jnp.float32)       # (L, N)
    cmat = c_ref[0].astype(jnp.float32)       # (L, N)

    da = dt * a_ref[0]                         # (L, 1); A (1, 1) negative
    # Inclusive prefix sum cum_i = sum_{j<=i} da_j as a lower-triangular
    # matmul (Mosaic lowers no cumsum), once as a column, once as a row.
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = mask.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tril, da, (((1,), (0,)), ((), ())),
                              precision=hi,
                              preferred_element_type=jnp.float32)  # (L, 1)
    cum_row = jax.lax.dot_general(da, tril, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1, L)
    cum_end = jnp.sum(da, axis=0, keepdims=True)                   # (1, 1)

    # Intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i·B_j) dt_j x_j
    lmat = jnp.exp(jnp.where(mask, cum - cum_row, -jnp.inf))
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    w = cb * lmat                              # (L, L)
    dx = x * dt                                # (L, P)
    y = jax.lax.dot_general(w, dx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # Inter-chunk: y_i += C_i exp(cum_i) h_prev     h_prev: (N, P)
    y = y + jax.lax.dot_general(cmat * jnp.exp(cum), hstate[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # Chunk state update: h = exp(cum_L) h_prev + sum_j exp(cum_L - cum_j)
    #                          dt_j B_j x_j^T
    decay_end = jnp.exp(cum_end - cum)         # (L, 1)
    s_c = jax.lax.dot_general(bmat * (decay_end * dt), x,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    hstate[...] = hstate[...] * jnp.exp(cum_end) + s_c

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0] = hstate[...]


def ssd_scan_pallas(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S); a: (BH,); b/c: (BH, S, N) -> (BH, S, P).

    The ops wrapper maps model layout (B, S, H, P) onto the flat BH dim and
    broadcasts the shared B/C groups.
    """
    bh, s_len, p_dim = x.shape
    n_dim = b.shape[-1]
    assert s_len % chunk == 0, (s_len, chunk)
    nc = s_len // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, chunk, n_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n_dim), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n_dim, p_dim), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, p_dim), x.dtype),
            jax.ShapeDtypeStruct((bh, n_dim, p_dim), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_dim, p_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt[..., None], a[:, None, None], b, c)
