"""Shallow-water reproduction correctness (the paper's application)."""
import json

import numpy as np
import pytest

from helpers import run_multidevice


def test_mesh_generation_properties():
    from repro.swe.mesh_gen import generate_bight_mesh
    mesh = generate_bight_mesh(800, seed=1)
    assert mesh.n_elements > 300
    assert (mesh.neighbors == -2).sum() > 0          # has open-sea edges
    assert (mesh.neighbors == -1).sum() > 0          # has land edges
    assert (mesh.area > 0).all()
    # outward normals: each element's normals sum to ~0 (closed polygon)
    assert np.abs(mesh.normals.sum(axis=1)).max() < 1e-9
    # adjacency is symmetric
    for e in range(0, mesh.n_elements, 7):
        for j in range(3):
            n = mesh.neighbors[e, j]
            if n >= 0:
                assert e in mesh.neighbors[n], (e, n)


def test_partition_schedule_valid():
    from repro.swe.mesh_gen import generate_bight_mesh
    from repro.swe.partition import partition_mesh
    from repro.swe.dg_solver import initial_state
    mesh = generate_bight_mesh(800, seed=1)
    pm = partition_mesh(mesh, 8, initial_state(mesh))
    # every round is a valid ppermute (each rank sends/receives <= once)
    for perm in pm.rounds:
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
    assert pm.n_max >= 1
    assert pm.n_rounds >= pm.n_max   # rounds cover the neighbor count
    # element conservation
    assert int(pm.valid.sum()) == mesh.n_elements


def test_hypothesis_partition_balance():
    from helpers import require_hypothesis
    require_hypothesis()
    from hypothesis import given, settings, strategies as st
    from repro.swe.partition import _rcb

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 16), st.integers(50, 400))
    def check(parts, n):
        rng = np.random.RandomState(n)
        cent = rng.rand(n, 2)
        pid = _rcb(cent, parts)
        counts = np.bincount(pid, minlength=parts)
        assert counts.max() - counts.min() <= max(2, n // parts // 4 + 1)
        assert counts.sum() == n

    check()


def test_partitioned_equals_single_and_modes():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, CommMode, BASELINE_CONFIG
from repro.swe import driver
from repro.swe.partition import _rcb

def flatten(sim, s):
    part = _rcb(sim.mesh.centroids, sim.pm.n_parts)
    counts = np.zeros(sim.pm.n_parts, int)
    vals = np.zeros((sim.mesh.n_elements, 3))
    for e in range(sim.mesh.n_elements):
        p = part[e]
        vals[e] = s[p, counts[p]]
        counts[p] += 1
    return vals

mesh1 = make_mesh((1,), ("data",))
sim1 = driver.build_simulation(500, mesh1, CommConfig())
v1 = flatten(sim1, np.asarray(driver.make_sim_runner(sim1, 20)(sim1.state, 0.0)))

mesh8 = make_mesh((8,), ("data",))
for cfg in (CommConfig(), CommConfig(mode=CommMode.BUFFERED)):
    sim8 = driver.build_simulation(500, mesh8, cfg)
    v8 = flatten(sim8, np.asarray(driver.make_sim_runner(sim8, 20)(sim8.state, 0.0)))
    assert np.abs(v1 - v8).max() < 1e-4, cfg.mode

# host-scheduled baseline
simh = driver.build_simulation(500, mesh8, BASELINE_CONFIG)
runner = driver.make_host_scheduled_runner(simh)
sh, _ = runner.run(simh.state, 0.0, 20)
assert np.abs(v1 - flatten(simh, np.asarray(sh))).max() < 1e-4
assert runner.dispatches == 40
print("SWE PARITY OK")
""")
    assert "SWE PARITY OK" in out


def test_mass_conservation_multidevice():
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig
from repro.swe import driver
mesh = make_mesh((8,), ("data",))
sim = driver.build_simulation(600, mesh, CommConfig())
m0 = float(np.sum(np.asarray(sim.state)[..., 0] * sim.pm.area * sim.pm.valid))
s = driver.make_sim_runner(sim, 50)(sim.state, 0.0)
m1 = float(np.sum(np.asarray(s)[..., 0] * sim.pm.area * sim.pm.valid))
assert abs(m1 - m0) / m0 < 5e-3, (m0, m1)
assert np.isfinite(np.asarray(s)).all()
print("MASS OK", m0, m1)
""")
    assert "MASS OK" in out


def test_eq2_eq3_model_properties():
    """The latency model reproduces the paper's qualitative claims."""
    from repro.core import latmodel
    from repro.core.config import (BASELINE_CONFIG, CommConfig, CommMode,
                                   Scheduling, V5E)
    streaming = CommConfig()
    w = latmodel.SWEWorkload(
        e_total=6000 * 8, e_core=5600, e_send=270, e_recv=270, d_ext=0,
        l_pipe=100, n_max=4, flop_per_element=260.0, freq=256e6,
        msg_bytes=270 * 12 // 4)
    # 1) buffered+host (MPI baseline) latency >> streaming+fused
    l_base = latmodel.eq3_l_comm(w, BASELINE_CONFIG, V5E)
    l_accl = latmodel.eq3_l_comm(w, streaming, V5E)
    assert l_base > 3 * l_accl
    # 2) the baseline stalls the pipeline like the paper (75-80% there)
    assert latmodel.stall_fraction(w, BASELINE_CONFIG, V5E) > 0.4
    assert latmodel.stall_fraction(w, streaming, V5E) < 0.1
    # 3) throughput monotonically degrades with N_max (Fig. 10 steps)
    thr = []
    for nmax in (1, 2, 4, 8, 12):
        import dataclasses
        w2 = dataclasses.replace(w, n_max=nmax)
        thr.append(latmodel.eq2_throughput(w2, BASELINE_CONFIG, V5E))
    assert all(a >= b for a, b in zip(thr, thr[1:]))
    # 4) buffered mode caps below link bandwidth. NOTE the hardware
    # adaptation: on the FPGA the staging copy HALVED peak (6.6 vs 12.5 GB/s,
    # mem ~ link speed); on TPU HBM is 16x faster than ICI so the buffered
    # THROUGHPUT penalty is ~11% — the buffered LATENCY penalty (l_m + the
    # extra l_k) is what dominates instead (asserted in 1-2 above).
    assert latmodel.buffered_peak_bw(V5E) < V5E.ici_bw
    assert latmodel.buffered_peak_bw(V5E) > 0.8 * V5E.ici_bw


# The solver's phases as named scopes: every phase has instructions in the
# compiled runner (the halo exchange only where there is a neighbour).
_SCOPES_CODE = """
import glob, json, os, re, tempfile
dump = tempfile.mkdtemp()
os.environ["XLA_FLAGS"] += (" --xla_dump_to=" + dump + " --xla_dump_hlo_as_text"
                            " --xla_dump_hlo_module_re=jit_body")
import jax
from repro.launch.mesh import make_mesh
from repro.core.config import OPTIMIZED_CONFIG, CommConfig, Scheduling
from repro.swe import driver
cfg = {{"fused": OPTIMIZED_CONFIG,
        "overlapped": CommConfig(scheduling=Scheduling.OVERLAPPED)}}[{mode!r}]
sim = driver.build_simulation(500, make_mesh(({n},), ("data",)), cfg)
driver.make_sim_runner(sim, 5)(sim.state, 0.0)   # compiles, XLA dumps
text = open(glob.glob(dump + "/*jit_body*after_optimizations.txt")[0]).read()
found = {{}}
for name in re.findall(r'op_name="([^"]*)"', text):
    for part in name.split("/"):
        if part.startswith("swe."):
            found[part] = found.get(part, 0) + 1
print("SCOPES " + json.dumps(found))
"""


@pytest.mark.parametrize("mode,n", [("fused", 1), ("fused", 2),
                                    ("overlapped", 2)])
def test_runner_phases_are_scopes_in_compiled_hlo(mode, n):
    out = run_multidevice(_SCOPES_CODE.format(mode=mode, n=n), n_devices=n)
    line, = [l for l in out.splitlines() if l.startswith("SCOPES ")]
    found = json.loads(line[len("SCOPES "):])
    want = {"swe.args", "swe.gather", "swe.flux", "swe.update"}
    if n > 1:
        want.add("swe.exchange")
    if mode == "overlapped":
        want |= {"swe.interior", "swe.boundary"}
    assert want <= set(found), found


def test_runner_bitwise_with_tracing_on_and_off():
    """Tracing changes what is recorded, never the numbers: the same runner
    gives bitwise the same state with tracing off and on, and with it on
    each segment leaves its spans, children tagged with its number."""
    out = run_multidevice("""
import numpy as np
from repro.launch.mesh import make_mesh
from repro.core.config import OPTIMIZED_CONFIG
from repro.obs import trace
from repro.swe import driver
mesh = make_mesh((2,), ("data",))
states = []
for mode in ("0", "1"):
    trace.configure(mode)
    sim = driver.build_simulation(500, mesh, OPTIMIZED_CONFIG)
    run = driver.make_sim_runner(sim, 10)
    s = sim.state
    for k in range(3):
        s = run(s, k * 10 * sim.swe.dt)
    states.append(np.asarray(s))
assert np.array_equal(states[0], states[1])
evs = trace.events()
names = [e["name"] for e in evs if e["cat"] in ("driver", "setup")]
assert names[:4] == ["swe.build.mesh_gen", "swe.build.partition",
                     "swe.build.place", "swe.segment.put_t"], names
for n in range(3):
    seg = [e for e in evs if e["name"].startswith("swe.segment")
           and e["args"]["segment"] == n]
    assert sorted(e["name"] for e in seg) == [
        "swe.segment", "swe.segment.launch", "swe.segment.put_t"]
    outer, = [e for e in seg if e["name"] == "swe.segment"]
    for e in seg:
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
print("TRACING PARITY OK")
""", n_devices=2)
    assert "TRACING PARITY OK" in out


@pytest.mark.parametrize("n_parts", [1, 2, 4, 8])
def test_partition_indices_in_bounds(n_parts):
    """The step gathers with indices promised in bounds (no clamp, no
    negative wrap): every neighbour index lies in the local-plus-halo
    array, every sent row in the partition, every arriving row in the halo
    or masked, every boundary row in the partition."""
    from repro.swe.dg_solver import initial_state
    from repro.swe.mesh_gen import generate_bight_mesh
    from repro.swe.partition import partition_mesh
    mesh = generate_bight_mesh(800, seed=1)
    pm = partition_mesh(mesh, n_parts, initial_state(mesh))

    def within(a, lo, hi):
        return bool(((a >= lo) & (a < hi)).all())

    assert within(pm.neigh_idx, 0, pm.e_max + pm.h_max)
    assert within(pm.send_idx, 0, pm.e_max)
    assert within(pm.recv_slot, -1, pm.h_max)
    assert within(pm.boundary_idx, 0, pm.e_max)
    # the halo is reached only where the partition has neighbours
    assert (pm.neigh_idx >= pm.e_max).any() == (n_parts > 1)


# The row-major step of ``make_step_fn``, applied step by step inside
# shard_map on ``PartitionedMesh``'s own arrays, against the segment runner.
_WRAPPER_CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.config import OPTIMIZED_CONFIG
from repro.swe import dg_solver, driver
sim = driver.build_simulation(3000, make_mesh(({n},), ("data",)),
                              OPTIMIZED_CONFIG)
pm = sim.pm
step = dg_solver.make_step_fn(pm, sim.comm_cfg, "data", sim.swe)
rows = [jnp.asarray(a, jnp.int32 if a.dtype.kind == "i" else jnp.float32)
        for a in (pm.area, pm.normals, pm.neigh_idx, pm.edge_type, pm.valid,
                  pm.send_idx, pm.send_mask, pm.recv_slot, pm.boundary_idx)]

def body(state, t, *static):
    s, local = state[0], [a[0] for a in static]
    for _ in range(5):
        s = step(s, t, *local)
        t = t + sim.swe.dt
    return s[None]

fn = jax.jit(jax.shard_map(
    body, mesh=sim.device_mesh,
    in_specs=(P("data"), P()) + (P("data"),) * len(rows),
    out_specs=P("data"), check_vma=False))
got = np.asarray(fn(sim.state, jnp.float32(0.0), *rows))
want = np.asarray(driver.make_sim_runner(sim, 5)(sim.state, 0.0))
assert got.shape == want.shape == (pm.n_parts, pm.e_max, 3)
assert np.array_equal(got, want), np.abs(got - want).max()
assert not np.array_equal(want, np.asarray(sim.state))
print("WRAPPER OK")
"""


@pytest.mark.parametrize("n", [1, 4])
def test_row_major_step_equals_runner_bitwise(n):
    out = run_multidevice(_WRAPPER_CODE.format(n=n), n_devices=n)
    assert "WRAPPER OK" in out


def _numpy_step(mesh, u, dt, h_sea):
    """One float64 step of the global ``(E, 3)`` state: a Rusanov flux per
    edge against the neighbour, a mirrored ghost on land edges, still water
    of depth ``h_sea`` on sea edges, an explicit update, depth >= 1e-6."""
    nb, n = mesh.neighbors, mesh.normals                    # (E, 3), (E, 3, 2)
    nlen = np.maximum(np.hypot(n[..., 0], n[..., 1]), 1e-12)
    nx, ny = n[..., 0] / nlen, n[..., 1] / nlen
    ul = np.repeat(u[:, None, :], 3, axis=1)                # (E, 3edges, 3)
    qn = ul[..., 1] * nx + ul[..., 2] * ny
    land = np.stack([ul[..., 0], ul[..., 1] - 2 * qn * nx,
                     ul[..., 2] - 2 * qn * ny], axis=-1)
    sea = np.stack([np.full_like(ul[..., 0], h_sea), ul[..., 1], ul[..., 2]],
                   axis=-1)
    ur = np.where((nb == -1)[..., None], land,
                  np.where((nb == -2)[..., None], sea, u[np.maximum(nb, 0)]))

    def flux(v):
        h = np.maximum(v[..., 0], 1e-8)
        un = (v[..., 1] * n[..., 0] + v[..., 2] * n[..., 1]) / h
        p = 0.5 * 9.81 * h * h
        return np.stack([h * un, v[..., 1] * un + p * n[..., 0],
                         v[..., 2] * un + p * n[..., 1]], axis=-1)

    def speed(v):
        h = np.maximum(v[..., 0], 1e-8)
        return np.abs((v[..., 1] * nx + v[..., 2] * ny) / h) + np.sqrt(9.81 * h)

    lam = np.maximum(speed(ul), speed(ur))
    f = 0.5 * (flux(ul) + flux(ur) - (lam * nlen)[..., None] * (ur - ul))
    new = u - dt / mesh.area[:, None] * f.sum(axis=1)
    new[:, 0] = np.maximum(new[:, 0], 1e-6)
    return new


def test_runner_matches_float64_numpy_step():
    """20 fused steps in float32 stay within 1e-5 of the state's magnitude
    of a float64 numpy stepper, and far closer than the steps' change."""
    from repro.core.config import OPTIMIZED_CONFIG
    from repro.launch.mesh import make_mesh
    from repro.swe import dg_solver, driver
    sim = driver.build_simulation(3000, make_mesh((1,), ("data",)),
                                  OPTIMIZED_CONFIG)
    u0 = dg_solver.initial_state(sim.mesh)
    want = u0
    for _ in range(20):
        want = _numpy_step(sim.mesh, want, sim.swe.dt, sim.swe.h_sea)
    got = driver.flatten_state(
        sim, driver.make_sim_runner(sim, 20)(sim.state, 0.0))
    err = np.abs(got - want).max()
    assert err <= 1e-5 * np.abs(want).max(), err
    assert err <= 1e-3 * np.abs(want - u0).max(), err
