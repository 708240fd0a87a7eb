"""The bight mesh and its partition layout, rebuilt without the program.

A plain copy of the published construction (jittered grid in the bight
polygon, Delaunay triangles, sliver filter, land/sea boundary edges) and of
the recursive coordinate bisection that places elements on partitions.  The
reference solver reads only what this module builds, so a fault in the program's mesh, geometry or partitioning shows as a
difference instead of being shared by both sides.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.spatial import Delaunay


@dataclasses.dataclass
class RefMesh:
    nodes: np.ndarray       # (N, 2)
    elements: np.ndarray    # (E, 3) node ids
    neighbors: np.ndarray   # (E, 3): element id, -1 land, -2 sea
    area: np.ndarray        # (E,)
    normals: np.ndarray     # (E, 3, 2) outward normal times edge length
    centroids: np.ndarray   # (E, 2)

    @property
    def n_elements(self) -> int:
        return len(self.elements)


def _water(pts: np.ndarray) -> np.ndarray:
    """Inside the bight: east of a cosine coastline; x = 1 is open sea."""
    coast = 0.25 * (1 - np.cos(2 * np.pi * pts[:, 1])) * 0.5
    return pts[:, 0] > coast


def _areas(nodes, elements):
    p = nodes[elements]
    return 0.5 * np.abs(
        (p[:, 1, 0] - p[:, 0, 0]) * (p[:, 2, 1] - p[:, 0, 1])
        - (p[:, 2, 0] - p[:, 0, 0]) * (p[:, 1, 1] - p[:, 0, 1]))


def _normals(nodes, elements):
    p = nodes[elements]
    cent = p.mean(1)
    out = np.zeros((len(elements), 3, 2))
    for j in range(3):
        a, b = p[:, j], p[:, (j + 1) % 3]
        t = b - a
        n = np.stack([t[:, 1], -t[:, 0]], 1)
        flip = np.einsum("ij,ij->i", n, 0.5 * (a + b) - cent) < 0
        n[flip] *= -1
        out[:, j] = n
    return out


def _neighbors(nodes, elements):
    """Edge j joins vertices j and j+1; an edge of two triangles links
    them, an edge of one is sea when both ends lie on the eastern edge."""
    e_count = len(elements)
    a = elements
    b = np.roll(elements, -1, axis=1)
    lo = np.minimum(a, b).astype(np.int64).ravel()
    hi = np.maximum(a, b).astype(np.int64).ravel()
    key = lo * (len(nodes) + 1) + hi
    order = np.argsort(key, kind="stable")
    k = key[order]
    neigh = np.full(e_count * 3, -1, np.int64)
    pair = np.nonzero(k[1:] == k[:-1])[0]
    first, second = order[pair], order[pair + 1]
    neigh[first] = second // 3
    neigh[second] = first // 3
    single = neigh < 0
    xmax = nodes[:, 0].max()
    sea = (nodes[lo, 0] > xmax - 1e-6) & (nodes[hi, 0] > xmax - 1e-6)
    neigh[single & sea] = -2
    return neigh.reshape(e_count, 3).astype(np.int32)


def bight_mesh(target_elements: int, seed: int = 0) -> RefMesh:
    n_pts = max(16, int(target_elements / 2))
    nx = int(np.sqrt(n_pts))
    ny = max(2, n_pts // max(nx, 1))
    rng = np.random.RandomState(seed)
    gx, gy = np.meshgrid(np.linspace(0, 1, nx), np.linspace(0, 1, ny))
    pts = np.stack([gx.ravel(), gy.ravel()], 1)
    jitter = 0.35 / max(nx, ny)
    inner = ((pts[:, 0] > 0) & (pts[:, 0] < 1)
             & (pts[:, 1] > 0) & (pts[:, 1] < 1))
    pts[inner] += rng.uniform(-jitter, jitter, pts[inner].shape)
    pts = pts[_water(pts)]
    elements = Delaunay(pts).simplices.astype(np.int32)
    keep = _water(pts[elements].mean(1))
    a = _areas(pts, elements)
    keep &= a > 0.05 * np.median(a[a > 1e-12])
    elements = elements[keep]
    return RefMesh(nodes=pts, elements=elements,
                   neighbors=_neighbors(pts, elements),
                   area=_areas(pts, elements),
                   normals=_normals(pts, elements),
                   centroids=pts[elements].mean(1))


def bisect(centroids: np.ndarray, n_parts: int) -> np.ndarray:
    """Recursive coordinate bisection: a part id per element."""
    part = np.zeros(len(centroids), np.int32)

    def split(idx, parts_left, base):
        if parts_left == 1:
            part[idx] = base
            return
        half = parts_left // 2
        c = centroids[idx]
        axis = int(np.argmax(c.max(0) - c.min(0)))
        order = np.argsort(c[:, axis], kind="stable")
        cut = int(round(len(idx) * half / parts_left))
        split(idx[order[:cut]], half, base)
        split(idx[order[cut:]], parts_left - half, base + half)

    split(np.arange(len(centroids)), n_parts, 0)
    return part


def layout(mesh: RefMesh, n_parts: int) -> np.ndarray:
    """(P, E_max) global element id at each partition row, -1 for padding.

    Partition p holds its elements in increasing global id.
    """
    part = bisect(mesh.centroids, n_parts)
    ids = [np.nonzero(part == p)[0] for p in range(n_parts)]
    e_max = max(len(i) for i in ids)
    table = np.full((n_parts, e_max), -1, np.int64)
    for p, i in enumerate(ids):
        table[p, :len(i)] = i
    return table
