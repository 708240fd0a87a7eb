"""Back-to-back segments of the shallow-water solver.

The window drives ``driver.make_sim_runner`` over ``driver.build_simulation``:
one dispatch advances ``n_inner`` steps, and the host blocks on each segment,
as a simulation that reads its state per segment does.  The seeded initial
state (a Gaussian hump) goes in through ``build_simulation(initial_state=)``;
the mesh is fixed by the configuration, so every seed runs the same shapes.

The comparison takes the first segment (from the seeded state), the last
segment of the window and a sample of the others drawn from the seed, and
runs each from its input through the float64 reference.  The number compared
is the largest difference from the reference over the largest change that
the reference makes in that segment.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import harness
from bench.ref import mesh as ref_mesh
from bench.ref import swe as ref_swe


@dataclasses.dataclass
class Prepared:
    ctx: object
    devices: list
    seed: int
    mesh: ref_mesh.RefMesh
    dt: float
    run: object                # the program's segment runner
    state: object              # device state after the warm segment
    t: float
    samples: list              # [(input, output)] device or host arrays
    reservoir: harness.Reservoir = None
    last: tuple = None


def prepare(ctx, devices, seed: int) -> Prepared:
    import jax
    from repro.launch.mesh import make_mesh
    from repro.swe import driver
    from repro.swe.dg_solver import SWEConfig

    cfg, tr = ctx.config, ctx.traffic
    # The reference's own mesh: the reference's time, not set-up's.  The
    # inputs are the seeded hump over its elements.
    t0 = time.perf_counter()
    mesh = ref_mesh.bight_mesh(cfg["n_elements_requested"], cfg["mesh_seed"])
    ctx.reference_s += time.perf_counter() - t0
    t0 = time.perf_counter()
    state0 = ref_swe.hump(mesh, seed, cfg["initial_state"])
    ctx.setup["inputs_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    device_mesh = make_mesh((len(devices),), ("data",), devices=devices)
    swe = SWEConfig(dt=cfg["dt_max"], h_sea=cfg["h_sea"],
                    tidal_amplitude=cfg["tidal_amplitude"])
    sim = driver.build_simulation(cfg["n_elements_requested"], device_mesh,
                                  cfg["comm"], swe=swe, seed=cfg["mesh_seed"],
                                  initial_state=state0)
    ctx.setup["mesh_s"] = time.perf_counter() - t0
    check_shape(ctx, sim.mesh.n_elements, mesh)
    harness.log(f"[bench] {sim.mesh.n_elements} elements on "
                f"{sim.pm.n_parts} partitions, {sim.pm.n_rounds} exchange "
                f"rounds, s_max {sim.pm.s_max}, dt {sim.swe.dt!r}; comm "
                f"{cfg['comm']!r} resolved to {sim.comm_cfg}")

    run = driver.make_sim_runner(sim, n_inner=tr["n_inner"])
    seg_dt = tr["n_inner"] * sim.swe.dt
    t0 = time.perf_counter()
    first = jax.block_until_ready(run(sim.state, 0.0))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = jax.block_until_ready(run(first, seg_dt))
    warm = time.perf_counter() - t0
    ctx.setup["compile_s"] = t_first - warm
    ctx.setup["warm_s"] = warm
    return Prepared(ctx=ctx, devices=devices, seed=seed, mesh=mesh,
                    dt=sim.swe.dt, run=run, state=state,
                    t=2 * seg_dt, samples=[(state0, first)])


def check_shape(ctx, n_elements: int, mesh) -> None:
    want = ctx.config["n_elements"]
    if n_elements != want or mesh.n_elements != want:
        raise RuntimeError(f"the mesh has {n_elements} elements (reference "
                           f"{mesh.n_elements}), the configuration states "
                           f"{want}")


def measure(p: Prepared, seconds: float, traced: bool) -> harness.Window:
    tr = p.ctx.traffic
    seg_dt = tr["n_inner"] * p.dt
    p.reservoir = harness.Reservoir(tr["samples"], p.seed)
    clock = {"t": p.t}

    def dispatch(state):
        out = p.run(state, clock["t"])
        clock["t"] += seg_dt
        return out

    def keep(before, after):
        p.reservoir.offer((before, after))
        p.last = (before, after)

    p.state, window = harness.drive(dispatch, p.state, seconds,
                                    tr["n_inner"], keep, traced)
    return window


def flatten(table: np.ndarray, state) -> np.ndarray:
    """Partitioned ``(P, E_max, 3)`` -> global ``(E, 3)`` by the layout."""
    s = np.asarray(state, np.float64)
    if s.shape[:2] != table.shape:
        raise RuntimeError(f"state of shape {s.shape} for a layout of "
                           f"{table.shape}")
    ok = table >= 0
    out = np.full((int(ok.sum()), 3), np.nan)
    out[table[ok]] = s[ok]
    return out


def compare(p: Prepared, substitute=None) -> list:
    """Largest difference from the reference over the reference's change,
    over the sampled segments."""
    pairs = list(p.samples) + list(p.reservoir.items)
    if p.last is not None:
        pairs.append(p.last)
    table = ref_mesh.layout(p.mesh, len(p.devices))
    host = []
    for before, after in pairs:
        b = before if isinstance(before, np.ndarray) else flatten(table, before)
        host.append((b, flatten(table, after)))
    # free the program's state before the reference runs
    p.samples, p.reservoir.items, p.last, p.state = [], [], None, None
    n_inner = p.ctx.traffic["n_inner"]
    cfg = p.ctx.config
    # the reference's own step from the configuration, not the program's
    dt = ref_swe.stable_dt(p.mesh, cfg["dt_max"], cfg["h_sea"], cfg["cfl"])
    ref = ref_swe.Stepper(p.mesh, dt, cfg["h_sea"])
    low = None
    if substitute == "control":
        import ml_dtypes
        low = ref_swe.Stepper(p.mesh, dt, cfg["h_sea"], ml_dtypes.bfloat16)
    worst = 0.0
    for before, after in host:
        want = ref.run(before, n_inner)
        if low is not None:
            after = np.asarray(low.run(before, n_inner), np.float64)
        change = float(np.abs(want - before).max())
        err = float(np.abs(after - want).max()) / change
        if not err == err:
            err = float("inf")
        worst = max(worst, err)
    return [("seg_err", worst)]
