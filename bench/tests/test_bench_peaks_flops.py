"""The peaks table and the step's operation count."""
from __future__ import annotations

import sys

import numpy as np
import pytest

from bench_subprocess import ROOT

sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from bench import flops, peaks  # noqa: E402


def test_peaks_of_a_v5e():
    p = peaks.peak("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_peaks_refuse_an_unknown_device(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak(kind)


def test_step_count_does_not_exceed_xla_count():
    """The benchmark's count of one step is at most what XLA counts for
    the program's own step compiled for the CPU, so ``swe_step_mfu`` cannot
    be inflated by the count."""
    import jax
    from repro.core.config import CommConfig
    from repro.launch.mesh import make_mesh
    from repro.swe import dg_solver, driver

    sim = driver.build_simulation(3000, make_mesh((1,), ("data",)),
                                  CommConfig())
    pm = sim.pm
    step = dg_solver.make_step_fn(pm, sim.comm_cfg, "data", sim.swe)
    args = [pm.state0[0].astype(np.float32), pm.area[0].astype(np.float32),
            pm.normals[0].astype(np.float32), pm.neigh_idx[0],
            pm.edge_type[0], pm.valid[0], pm.send_idx[0], pm.send_mask[0],
            pm.recv_slot[0], pm.boundary_idx[0]]
    f = jax.jit(lambda s, *a: step(s, 0.0, *a))
    cost = f.lower(*args).compile().cost_analysis()
    xla = cost["flops"] + cost.get("transcendentals", 0.0)
    ours = flops.swe_step_flops(sim.mesh.n_elements)
    assert 0 < ours <= xla
    # and it is no token count: at least half of what XLA counts
    assert ours >= 0.5 * cost["flops"]
