"""Mesh construction: every device mesh in the repo is built here.

``jax.make_mesh`` gives Explicit axes by default, under which ``shard_map``
bodies that mix per-device arrays with mesh-wide shardings are refused.
The code is written for Auto axes (GSPMD propagation outside ``shard_map``,
manual collectives inside), so every mesh goes through :func:`make_mesh`.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto.  ``devices`` defaults to
    the default backend's devices in JAX's topology-aware order."""
    shape = tuple(axis_shapes)
    return jax.make_mesh(shape, tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small host-device mesh for CPU multi-device tests."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def exit_unless_host_cpu(tool: str) -> None:
    """Exit at once when JAX's default backend is not the host CPU.

    For tools that force host CPU devices or start child processes that
    need devices of their own: on an accelerator this process already holds
    the chip, so such a child would fail or hang waiting for it.
    """
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"{tool} runs on forced host CPU devices and is not a chip "
            f"path; JAX is on {backend!r} here. Run it with "
            f"JAX_PLATFORMS=cpu, or use chip_smoke.py on the chip.")
