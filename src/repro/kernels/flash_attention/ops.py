"""jit'd public wrapper: model-layout (B,S,H,hd) GQA attention -> kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False):
    """q: (B,S,H,hd); k/v: (B,T,KV,hd) -> (B,S,H,hd_v).

    GQA: q heads are grouped onto kv heads (H % KV == 0).  The kernel
    compiles for the TPU; ``interpret=True`` runs it in Pallas interpret
    mode on any backend (the CPU tests).  Production model code selects
    this path only when rt.use_pallas is set.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV

    # exact GQA lowering: repeat kv per q-head group, flatten heads to batch
    q2 = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    k2 = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1
                    ).reshape(B * H, T, hd)
    v2 = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1
                    ).reshape(B * H, T, v.shape[-1])
    out = flash_attention_pallas(q2, k2, v2, causal=causal, window=window,
                                 softcap=softcap, interpret=interpret)
    return out.reshape(B, H, S, -1).transpose(0, 2, 1, 3)


def flash_attention_reference(q, k, v, *, causal=True, window=None,
                              softcap=None):
    """Same layout as flash_attention, via the oracle (for tests)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    q2 = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    k2 = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, T, hd)
    v2 = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1
                    ).reshape(B * H, T, v.shape[-1])
    out = attention_ref(q2, k2, v2, causal=causal, window=window,
                        softcap=softcap)
    return out.reshape(B, H, S, -1).transpose(0, 2, 1, 3)
