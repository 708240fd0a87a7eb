"""Virtual-torus hop-scaling rows: measured vs predicted per-edge latency.

The paper's per-edge result in benchmark form: the same sendrecv pattern is
measured at several hop distances on a virtual 2x4 torus (each extra hop is
one physically executed permute — ``repro.core.topology``'s store-and-forward
lowering), next to the hop-aware Eq. 1 prediction:

- ``topo_hops_sendrecv_h<d>_<size>B`` — measured µs/op at hop distance d
  (derived column: the calibrated-model prediction at the same distance);
- ``topo_hop_ratio_sendrecv_<size>B`` — measured t(max_hop)/t(1) ratio
  (non-latency row: a *smaller* ratio means better hop hiding, not a
  regression).

New rows ride this PR report-only (``benchmarks.diff --report-only-prefixes
topo_``) until a second committed baseline lands.
"""
from __future__ import annotations

HOPS = (1, 2, 3)
SIZES = (1 << 16, 1 << 20)


def run():
    import jax
    if jax.device_count() < 8:
        return [("topo_hops", 0.0, "skipped_lt8devices")]
    from repro.launch.mesh import make_mesh
    from repro.core import latmodel
    from repro.core.config import OPTIMIZED_CONFIG, V5E
    from repro.core.topology import TorusSpec
    from repro.tune import sweep as tune_sweep
    from repro.tune.space import config_to_dict

    mesh = make_mesh((8,), ("x",))
    spec = TorusSpec((2, 4))
    from repro.core.communicator import Communicator
    comm = Communicator.from_mesh(mesh, "x", topo=spec)
    cfg = OPTIMIZED_CONFIG
    hw = spec.hardware(V5E)
    rows = []
    measured: dict[tuple[int, int], float] = {}
    for size in SIZES:
        for d in HOPS:
            op = tune_sweep._build_op("sendrecv", comm, cfg, hop_distance=d)
            sec = tune_sweep._time_program(
                op, mesh, size, cfg, reps=3, inner=4,
                cache_key=("bench_topo", spec.name, d,
                           tune_sweep._mesh_key(mesh), "sendrecv",
                           tuple(sorted(config_to_dict(cfg).items())), size))
            measured[(size, d)] = sec
            pred = latmodel.pingping_latency(size, cfg, hw, hops=d)
            rows.append((f"topo_hops_sendrecv_h{d}_{size}B", sec * 1e6,
                         f"pred{pred * 1e6:.1f}us"))
        ratio = measured[(size, HOPS[-1])] / max(measured[(size, 1)], 1e-12)
        pred_ratio = (latmodel.pingping_latency(size, cfg, hw, HOPS[-1])
                      / latmodel.pingping_latency(size, cfg, hw, 1))
        rows.append((f"topo_hop_ratio_sendrecv_{size}B", ratio,
                     f"h{HOPS[-1]}/h1_pred{pred_ratio:.2f}x"))
    return rows
