"""jit'd public wrapper mapping the model layout onto the SSD kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """Model layout: x (B,S,H,P); dt (B,S,H); a (H,); b/c (B,S,G,N), G=1.

    Returns (y (B,S,H,P) fp32, h_final (B,H,N,P) fp32) — matching
    repro.models.ssm.ssd_chunked_ref.  The final state is recomputed from
    the last chunk boundary cheaply via the reference recurrence (the kernel
    streams y; serving prefill uses the state).  The kernel compiles for
    the TPU; ``interpret=True`` runs it in Pallas interpret mode on any
    backend (the CPU tests).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.broadcast_to(a[None], (B, H)).reshape(B * H)
    bf = jnp.broadcast_to(b[:, :, 0:1, :], (B, S, H, N)
                          ).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    cf = jnp.broadcast_to(c[:, :, 0:1, :], (B, S, H, N)
                          ).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y, h_final = ssd_scan_pallas(xf, dtf, af, bf, cf, chunk, interpret=interpret)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3).astype(jnp.float32)
    h_final = h_final.reshape(B, H, N, P)
    return y, h_final
