"""Plain reference of the shallow-water step, in numpy.

Cell-centred finite volumes on the triangles: per element and edge a
Rusanov (local Lax-Friedrichs) flux against the neighbour, a mirrored ghost
on land edges, or still water of depth ``h_sea`` on sea edges; then an
explicit Euler update, with the depth kept at least 1e-6.  The arithmetic
runs in the dtype it is given: float64 is the reference, a lower precision
is the control that the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

from bench.ref.mesh import RefMesh

G = 9.81


def stable_dt(mesh: RefMesh, dt_max: float, h_sea: float, cfl: float) -> float:
    """The longest step at most ``dt_max`` with ``dt * c * P / A <= cfl`` on
    every element, c the gravity-wave speed at twice the sea depth."""
    perimeter = np.linalg.norm(mesh.normals, axis=-1).sum(-1)
    c = np.sqrt(G * 2.0 * h_sea)
    return float(min(dt_max, cfl * np.min(mesh.area / perimeter) / c))


def hump(mesh: RefMesh, seed: int, params: dict) -> np.ndarray:
    """Still water of depth 1 plus a Gaussian hump whose centre, height and
    width are drawn from ``seed`` within the ranges in ``params``."""
    rng = np.random.default_rng(seed)
    cx = rng.uniform(*params["centre_x"])
    cy = rng.uniform(*params["centre_y"])
    amp = rng.uniform(*params["amplitude"])
    width = rng.uniform(*params["width"])
    c = mesh.centroids
    state = np.zeros((mesh.n_elements, 3))
    state[:, 0] = 1.0 + amp * np.exp(
        -width * ((c[:, 0] - cx) ** 2 + (c[:, 1] - cy) ** 2))
    return state


class Stepper:
    """``run(state, steps)`` advances the global ``(E, 3)`` state."""

    def __init__(self, mesh: RefMesh, dt: float, h_sea: float, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        cast = lambda a: np.asarray(a, self.dtype)
        self.dt = cast(dt)
        self.h_sea = cast(h_sea)
        self.area = cast(mesh.area)
        n = cast(mesh.normals)                                # (E, 3, 2)
        self.n = n
        self.nlen = np.maximum(np.sqrt(n[..., 0] * n[..., 0]
                                       + n[..., 1] * n[..., 1]),
                               cast(1e-12))                   # (E, 3)
        self.nhat = n / self.nlen[..., None]
        nb = mesh.neighbors
        self.land = nb == -1
        self.sea = nb == -2
        self.nb = np.where(nb >= 0, nb, 0)

    def _flux(self, u, n):
        h = np.maximum(u[..., 0], self.dtype.type(1e-8))
        hu, hv = u[..., 1], u[..., 2]
        un = (hu * n[..., 0] + hv * n[..., 1]) / h
        p = self.dtype.type(0.5 * G) * h * h
        return np.stack([h * un, hu * un + p * n[..., 0],
                         hv * un + p * n[..., 1]], axis=-1)

    def _rows(self, u: np.ndarray, rows: slice) -> np.ndarray:
        """The next state of elements ``rows`` given the whole state."""
        t = self.dtype.type
        own = u[rows]
        ul = np.broadcast_to(own[:, None, :], (len(own), 3, 3))
        ur = u[self.nb[rows]]                                 # (B, 3, 3)
        nh, n, nlen = self.nhat[rows], self.n[rows], self.nlen[rows]
        land, sea = self.land[rows], self.sea[rows]
        qn = ul[..., 1] * nh[..., 0] + ul[..., 2] * nh[..., 1]
        mirror = np.stack([ul[..., 0], ul[..., 1] - t(2) * qn * nh[..., 0],
                           ul[..., 2] - t(2) * qn * nh[..., 1]], axis=-1)
        still = np.stack([np.broadcast_to(self.h_sea, ul[..., 0].shape),
                          ul[..., 1], ul[..., 2]], axis=-1)
        ur = np.where(land[..., None], mirror,
                      np.where(sea[..., None], still, ur))
        h_l = np.maximum(ul[..., 0], t(1e-8))
        h_r = np.maximum(ur[..., 0], t(1e-8))
        un_l = (ul[..., 1] * nh[..., 0] + ul[..., 2] * nh[..., 1]) / h_l
        un_r = (ur[..., 1] * nh[..., 0] + ur[..., 2] * nh[..., 1]) / h_r
        lam = np.maximum(np.abs(un_l) + np.sqrt(t(G) * h_l),
                         np.abs(un_r) + np.sqrt(t(G) * h_r))
        f = t(0.5) * (self._flux(ul, n) + self._flux(ur, n)
                      - (lam * nlen)[..., None] * (ur - ul))
        new = own - (self.dt / self.area[rows])[:, None] * f.sum(axis=1)
        new[:, 0] = np.maximum(new[:, 0], t(1e-6))
        return new

    def run(self, state: np.ndarray, steps: int, block: int = 32768,
            threads: int = 8) -> np.ndarray:
        """``steps`` steps from ``state``, in blocks of elements on a few
        threads (numpy releases the interpreter lock inside each array
        operation)."""
        from concurrent.futures import ThreadPoolExecutor
        u = np.asarray(state, self.dtype)
        blocks = [slice(a, min(a + block, len(u)))
                  for a in range(0, len(u), block)]
        with ThreadPoolExecutor(min(threads, len(blocks))) as pool:
            for _ in range(steps):
                new = np.empty_like(u)

                def one(rows, u=u, new=new):
                    new[rows] = self._rows(u, rows)

                list(pool.map(one, blocks))
                u = new
        return u
