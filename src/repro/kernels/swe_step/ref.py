"""Pure-jnp oracle for the SWE element-update kernel — delegates to the
production solver math (single source of truth for the physics)."""
import jax.numpy as jnp

from repro.swe.dg_solver import reflect, rusanov


def swe_step_ref(u, u_n, nx, ny, edge_type, area, valid, h_sea, *, dt: float):
    """Row-major in and out, as the kernel: ``u`` (E, 3), ``u_n`` (E, 3edges,
    3), ``nx``/``ny``/``edge_type`` (E, 3edges); the physics runs on the
    solver's component-major layout."""
    n = jnp.stack([nx.T, ny.T])                             # (2,3,E)
    un = jnp.transpose(u_n, (2, 1, 0))                      # (3,3,E)
    et = edge_type.T
    ub = jnp.broadcast_to(u.T[:, None], un.shape)
    u_land = reflect(ub, n)
    u_sea = jnp.stack([jnp.broadcast_to(h_sea, ub[0].shape), ub[1], ub[2]])
    u_r = jnp.where(et == 1, u_land, jnp.where(et == 2, u_sea, un))
    f = rusanov(ub, u_r, n)
    div = jnp.sum(f, axis=1).T                              # (E,3)
    new = (u - dt / jnp.maximum(area[:, None], 1e-12) * div) * valid[:, None]
    new = new.at[:, 0].set(jnp.maximum(new[:, 0], 1e-6) * valid)
    return new
