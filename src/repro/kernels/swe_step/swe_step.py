"""Pallas TPU kernel for the shallow-water element update (the paper's
compute pipeline).

The neighbor gather stays in XLA (dynamic indexing); the kernel is the
arithmetic hot loop: 3 Rusanov edge fluxes + the element update, VPU-bound,
tiled (TILE_E elements × 8 sublanes-aligned) in VMEM.  This is the
algorithm-hardware codesign analogue of the paper's HLS element kernel: one
element per clock on the FPGA ⇒ one (8, 128)-vector lane bundle per VPU op
here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_E = 512
G = 9.81


def _flux_kernel(u_ref, un_ref, nx_ref, ny_ref, et_ref, area_ref, valid_ref,
                 hsea_ref, out_ref, *, dt: float):
    """One tile of elements; edge axis unrolled (3 edges).

    u: (T, 3vars); un: (T, 3edges, 3vars); n: (T, 3edges); et: (T, 3edges);
    out: updated state (T, 3vars).
    """
    u = u_ref[...].astype(jnp.float32)            # (T,3)
    div = jnp.zeros_like(u)
    hsea = hsea_ref[0, 0]
    for j in range(3):
        nx = nx_ref[:, j].astype(jnp.float32)
        ny = ny_ref[:, j].astype(jnp.float32)
        et = et_ref[:, j]
        u_n = un_ref[:, j, :].astype(jnp.float32)

        nlen = jnp.maximum(jnp.sqrt(nx * nx + ny * ny), 1e-12)
        nhx, nhy = nx / nlen, ny / nlen

        h_l = jnp.maximum(u[:, 0], 1e-8)
        qn_l = u[:, 1] * nhx + u[:, 2] * nhy
        # ghost states
        u_land0 = u[:, 0]
        u_land1 = u[:, 1] - 2 * qn_l * nhx
        u_land2 = u[:, 2] - 2 * qn_l * nhy
        u_r0 = jnp.where(et == 1, u_land0,
                         jnp.where(et == 2, hsea, u_n[:, 0]))
        u_r1 = jnp.where(et == 1, u_land1,
                         jnp.where(et == 2, u[:, 1], u_n[:, 1]))
        u_r2 = jnp.where(et == 1, u_land2,
                         jnp.where(et == 2, u[:, 2], u_n[:, 2]))

        h_r = jnp.maximum(u_r0, 1e-8)
        un_l = qn_l / h_l
        un_r = (u_r1 * nhx + u_r2 * nhy) / h_r
        lam = jnp.maximum(jnp.abs(un_l) + jnp.sqrt(G * h_l),
                          jnp.abs(un_r) + jnp.sqrt(G * h_r))

        def phys(h, hu, hv):
            un_s = (hu * nx + hv * ny) / jnp.maximum(h, 1e-8)
            f0 = h * un_s
            f1 = hu * un_s + 0.5 * G * h * h * nx
            f2 = hv * un_s + 0.5 * G * h * h * ny
            return f0, f1, f2

        fl = phys(h_l, u[:, 1], u[:, 2])
        fr = phys(h_r, u_r1, u_r2)
        f0 = 0.5 * (fl[0] + fr[0] - lam * nlen * (u_r0 - u[:, 0]))
        f1 = 0.5 * (fl[1] + fr[1] - lam * nlen * (u_r1 - u[:, 1]))
        f2 = 0.5 * (fl[2] + fr[2] - lam * nlen * (u_r2 - u[:, 2]))
        div = div + jnp.stack([f0, f1, f2], axis=-1)

    area = area_ref[...].astype(jnp.float32)      # (T,1)
    valid = valid_ref[...].astype(jnp.float32)    # (T,1)
    new = (u - dt / jnp.maximum(area, 1e-12) * div) * valid
    # depth floor on column 0 (h); a select, since Mosaic has no scatter
    col = jax.lax.broadcasted_iota(jnp.int32, new.shape, 1)
    new = jnp.where(col == 0, jnp.maximum(new, 1e-6) * valid, new)
    out_ref[...] = new.astype(out_ref.dtype)


def swe_step_pallas(u, u_n, nx, ny, edge_type, area, valid, h_sea, *,
                    dt: float, interpret: bool = False):
    """u: (E,3); u_n: (E,3,3); nx/ny/edge_type: (E,3); area/valid: (E,)."""
    E = u.shape[0]
    pad = (-E) % TILE_E
    if pad:
        padf = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        u, u_n, nx, ny, area, valid = map(padf, (u, u_n, nx, ny, area, valid))
        edge_type = jnp.pad(edge_type, ((0, pad), (0, 0)),
                            constant_values=1)
    ne = u.shape[0] // TILE_E
    kernel = functools.partial(_flux_kernel, dt=dt)
    out = pl.pallas_call(
        kernel,
        grid=(ne,),
        in_specs=[
            pl.BlockSpec((TILE_E, 3), lambda i: (i, 0)),
            pl.BlockSpec((TILE_E, 3, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((TILE_E, 3), lambda i: (i, 0)),
            pl.BlockSpec((TILE_E, 3), lambda i: (i, 0)),
            pl.BlockSpec((TILE_E, 3), lambda i: (i, 0)),
            pl.BlockSpec((TILE_E, 1), lambda i: (i, 0)),
            pl.BlockSpec((TILE_E, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_E, 3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u.shape[0], 3), u.dtype),
        interpret=interpret,
    )(u, u_n, nx, ny, edge_type, area[:, None], valid[:, None],
      jnp.asarray(h_sea, jnp.float32)[None, None])
    return out[:E]
