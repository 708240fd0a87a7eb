"""Operations of one shallow-water step, counted from the mesh's sizes.

Per element and edge, the Rusanov flux of ``bench/ref/swe.py`` against the
neighbour's state; per element, the update.  Ghost states of boundary edges,
the padding rows of a partition and the halo exchange are not counted, and
a square root counts as one operation, so the count is what the algorithm
needs and no more.
"""
from __future__ import annotations

# Per edge.
NORMAL = 4 + 1 + 2          # |n| (2 mul, add, sqrt), max with 1e-12, n / |n|
DEPTHS = 2                  # max(h, 1e-8) on both sides
NORMAL_VELOCITY = 2 * 4     # (hu nx + hv ny) / h on both sides
WAVE_SPEED = 2 * 4 + 1      # |un| + sqrt(g h) on both sides, their max
PHYSICAL_FLUX = 2 * (1 + 4 + 1 + 2 + 2 * 3)   # per side: max, un, h un,
                            # (g/2) h h, two momentum fluxes
DISSIPATION = 1 + 3 + 3     # lam |n|, u_r - u_l, their product
COMBINE = 3 + 3 + 3         # sum of fluxes, minus dissipation, times 1/2
PER_EDGE = (NORMAL + DEPTHS + NORMAL_VELOCITY + WAVE_SPEED + PHYSICAL_FLUX
            + DISSIPATION + COMBINE)
# Per element: sum of three edge fluxes, dt / A, scale and subtract, and the
# positive depth.
UPDATE = 2 * 3 + 1 + 3 + 3 + 1
PER_ELEMENT = 3 * PER_EDGE + UPDATE


def swe_step_flops(n_elements: int) -> float:
    """Operations of one step over ``n_elements`` real elements."""
    return float(PER_ELEMENT * n_elements)
