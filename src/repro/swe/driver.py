"""Multi-device shallow-water simulation driver.

Three execution modes, mirroring the paper's §3.1/§5 scheduling comparison:

- **fused** ("PL scheduling"): the whole time step — halo exchange + element
  update — is ONE compiled program; with ``lax.scan`` over steps, an entire
  simulation segment launches with a single host dispatch.
- **overlapped** (§5 scaling configuration): fused, plus the step is split
  into interior/boundary element passes around a double-buffered halo
  exchange, so interior compute carries no dependency on the in-flight
  permutes (``make_sim_runner`` serves this mode too — the split lives in
  ``dg_solver.make_step_core``).
- **host** ("MPI+PCIe baseline"): each phase is a separate dispatch — the
  exchange is staged through host-visible buffers between two compiled
  programs, paying 2·l_k per step exactly like the paper's baseline where the
  communication kernel is invoked by the host every simulation step.
"""
from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import CommConfig, Scheduling
from repro.core import latmodel
from repro.obs import trace as obs_trace
from repro.swe import dg_solver
from repro.swe.dg_solver import SWEConfig, make_step_core
from repro.swe.mesh_gen import Mesh as SweMesh, generate_bight_mesh
from repro.swe.partition import PartitionedMesh, partition_mesh


@dataclasses.dataclass
class Simulation:
    mesh: SweMesh
    pm: PartitionedMesh
    device_mesh: Mesh
    comm_cfg: CommConfig
    swe: SWEConfig
    state: jnp.ndarray        # (P, E_max, 3) sharded over 'data'
    t: float = 0.0
    # Virtual torus the partitions are placed on (multi-hop exchange edges
    # route through intermediate partitions) and the per-round hop-aware
    # config selection; None = flat mesh / uniform config.
    topology: object = None            # TorusSpec | None
    round_cfgs: Optional[list] = None  # per exchange round, serial paths only


def _select_round_configs(rounds, comm, halo_bytes: int, tune_db_path=None,
                          objective: str = "latency"):
    """Per-edge hop-aware selection: one autotuned config per exchange round.

    Each round's edges share one ppermute (and, on a torus, comparable hop
    distances), so the round is the per-edge selection granularity: the
    round's worst-case hop distance is looked up in the TuneDB (preferring
    measurements taken on the same virtual placement) and the hop-matched
    winner returned.  This replaces the single worst-case-hop config of the
    uniform path — a 1-hop round no longer pays the transport tuned for the
    3-hop round (the paper's per-edge result).
    """
    from repro.tune import select_config, topology_key
    from repro.tune.db import TuneDB
    topo = topology_key(n_devices=comm.size)
    torus = comm.topo.name if comm.topo is not None else ""
    db = TuneDB.load(tune_db_path)   # one read for all rounds
    cfgs = []
    for perm in rounds:
        hops = max(1, comm.max_hops(perm))
        cfgs.append(select_config("multi_neighbor", halo_bytes, topo=topo,
                                  db=db, hops=hops,
                                  objective=objective, torus=torus))
    return cfgs


def flatten_state(sim: "Simulation", state) -> np.ndarray:
    """Partitioned ``(P, E_max, 3)`` state -> global element order
    ``(E, 3)``.

    The RCB partition is a pure function of (mesh, n_parts), so the same
    mesh flattens identically from ANY partition count — which is what makes
    the global state the elastic runtime's portable checkpoint: a snapshot
    taken on 8 partitions restores bitwise onto 7 survivors
    (``build_simulation(..., initial_state=flatten_state(...))``), and final
    states digest-compare across fault/no-fault runs.
    """
    from repro.swe.partition import _rcb
    s = np.asarray(state)
    part = _rcb(sim.mesh.centroids, sim.pm.n_parts)
    counts = np.zeros(sim.pm.n_parts, int)
    vals = np.zeros((sim.mesh.n_elements, 3), s.dtype)
    for e in range(sim.mesh.n_elements):
        p = part[e]
        vals[e] = s[p, counts[p]]
        counts[p] += 1
    return vals


def state_digest(sim: "Simulation", state) -> str:
    """sha256 of the global-order state — the result-stream fingerprint the
    kill-and-resume smoke compares against its no-fault reference."""
    import hashlib
    return hashlib.sha256(
        np.ascontiguousarray(flatten_state(sim, state)).tobytes()).hexdigest()


def _resolve_comm(comm_cfg, pm: PartitionedMesh, n_parts: int,
                  device_mesh: Mesh, tune_db_path, objective: str, topology):
    """``comm_cfg="auto"`` -> (representative config, per-round configs or
    None); see :func:`build_simulation`."""
    from repro.core.collectives import resolve_config
    from repro.core.communicator import Communicator
    halo_bytes = int(pm.s_max) * 3 * 4   # (h, hu, hv) f32 per halo element
    # Worst-case torus hop distance of this partitioning's exchange
    # pattern — multi-hop edges prefer hop-matched measurements.
    comm = Communicator(("data",), (n_parts,), topo=topology)
    edges = [e for r in pm.rounds for e in r]
    hops = comm.max_hops(edges) if edges else None
    comm_cfg = resolve_config(comm_cfg, "multi_neighbor", halo_bytes,
                              mesh=device_mesh, db_path=tune_db_path,
                              hops=hops, objective=objective,
                              torus=topology.name if topology else "")
    # Per-edge selection is a torus feature: the flat mesh keeps its
    # single worst-case-hop config (no silent behavior change), and the
    # double-buffered overlapped engine pipelines all rounds under one
    # config — don't select what can't be applied.
    if (pm.rounds and topology is not None
            and comm_cfg.scheduling != Scheduling.OVERLAPPED):
        per_round = _select_round_configs(pm.rounds, comm, halo_bytes,
                                          tune_db_path, objective)
        # One scheduling discipline per step: unify each round's wire
        # config with the representative's scheduling.
        per_round = [dataclasses.replace(c, scheduling=comm_cfg.scheduling)
                     for c in per_round]
        if any(c != comm_cfg for c in per_round):
            return comm_cfg, per_round
    return comm_cfg, None


def build_simulation(n_elements: int, device_mesh: Mesh,
                     comm_cfg: CommConfig | str, swe: SWEConfig = SWEConfig(),
                     seed: int = 0, tune_db_path=None,
                     objective: str = "latency",
                     topology=None,
                     initial_state: Optional[np.ndarray] = None) -> Simulation:
    """Build the partitioned simulation.

    ``comm_cfg="auto"`` asks the autotuner for the fastest measured config
    for this partitioning's halo exchange (multi-neighbor pattern at the
    largest per-round message size), falling back to ``OPTIMIZED_CONFIG``
    when no sweep has been run on this topology.  ``objective="e2e"`` ranks
    by the measured halo-fold consumer loop instead of the bare exchange —
    the step has interior compute the overlapped schedule can hide, exactly
    the case where the microbench winner is not the end-to-end winner (§5).

    ``topology`` (a :class:`~repro.core.topology.TorusSpec`) places the
    partitions on a virtual multi-hop torus.  With ``comm_cfg="auto"`` the
    selection then happens **per edge**: every exchange round is tuned at
    its own hop distance (``Simulation.round_cfgs``) instead of one config
    at the pattern's worst-case hop.  The representative ``comm_cfg`` (step
    structure / scheduling) is the worst-hop round's winner; per-round wire
    configs apply on the serially scheduled paths, and their scheduling is
    unified with the representative so the step structure stays coherent.

    ``initial_state`` (global ``(E, 3)``, e.g. from :func:`flatten_state`)
    seeds the partitions with a mid-run snapshot instead of the t=0 hump —
    the elastic-recovery path restoring onto a different partition count.

    ``swe.dt`` is shortened to what the mesh keeps stable
    (:func:`dg_solver.stable_dt`).

    Each phase is a host span (``swe.build.mesh_gen``, ``.partition``,
    ``.resolve_config``, ``.place``), recorded when tracing is on.
    """
    with obs_trace.span("swe.build.mesh_gen", cat="setup",
                        n_elements=n_elements):
        mesh = generate_bight_mesh(n_elements, seed=seed)
        swe = dataclasses.replace(swe, dt=dg_solver.stable_dt(mesh, swe))
    n_parts = device_mesh.shape["data"]
    with obs_trace.span("swe.build.partition", cat="setup", parts=n_parts):
        if initial_state is None:
            initial_state = dg_solver.initial_state(mesh)
        pm = partition_mesh(mesh, n_parts, np.asarray(initial_state))
    round_cfgs = None
    if not isinstance(comm_cfg, CommConfig):
        with obs_trace.span("swe.build.resolve_config", cat="setup"):
            comm_cfg, round_cfgs = _resolve_comm(
                comm_cfg, pm, n_parts, device_mesh, tune_db_path, objective,
                topology)
    with obs_trace.span("swe.build.place", cat="setup"):
        sharding = NamedSharding(device_mesh, P("data"))
        state = jax.device_put(jnp.asarray(pm.state0, jnp.float32), sharding)
    return Simulation(mesh=mesh, pm=pm, device_mesh=device_mesh,
                      comm_cfg=comm_cfg, swe=swe, state=state,
                      topology=topology, round_cfgs=round_cfgs)


def _static_args(sim: Simulation):
    """The step's static arguments on the devices, each with its leading P
    dim, the per-element edge arrays laid out element axis last once here
    (:func:`dg_solver.edge_major`)."""
    pm = sim.pm
    sharding = NamedSharding(sim.device_mesh, P("data"))
    put = lambda a, dt=jnp.float32: jax.device_put(jnp.asarray(a, dt), sharding)
    normals, neigh_idx, edge_type = dg_solver.edge_major(
        pm.normals, pm.neigh_idx, pm.edge_type)
    return dict(
        area=put(pm.area),
        normals=put(normals),
        neigh_idx=put(neigh_idx, jnp.int32),
        edge_type=put(edge_type, jnp.int32),
        valid=put(pm.valid),
        send_idx=put(pm.send_idx, jnp.int32),
        send_mask=put(pm.send_mask),
        recv_slot=put(pm.recv_slot, jnp.int32),
        boundary_idx=put(pm.boundary_idx, jnp.int32),
    )


def _segment_program(sim: Simulation, n_steps: int):
    """One compiled dispatch of ``n_steps`` steps: ``fn(state, *static,
    t)`` with ``state`` ``(P, E_max, 3)`` in and out, and ``static``, the
    arguments of :func:`_static_args`, which it returns beside it.

    The steps run on the component-major layout of
    :func:`dg_solver.make_step_core`: the state is transposed to its three
    component rows once before the scan and back once after it, both under
    the ``swe.args`` scope with the per-partition squeezes.  The scan
    carries the three ``(E_max,)`` rows: a ``(3, E_max)`` carry would take
    the row-major layout of the segment's input, which on a TPU pads each
    element's three values to a 128-lane row and costs a relayout in and
    out of every step."""
    step = make_step_core(sim.pm, sim.comm_cfg, "data", sim.swe,
                          topology=sim.topology, round_cfgs=sim.round_cfgs)
    args = _static_args(sim)
    in_specs = (P("data"),) + (P("data"),) * len(args) + (P(),)

    def body(state, *rest):
        *static, t0 = rest
        # this partition's slice of each argument (leading P dim of 1)
        with obs_trace.scope("swe.args"):
            s = tuple(state[0].T)
            local = [a[0] for a in static]

        def inner(carry, _):
            s, t = carry
            # the stack is what the gather's table is built from
            with obs_trace.scope("swe.gather"):
                s = jnp.stack(s)
            return (tuple(step(s, t, *local)), t + sim.swe.dt), None
        (s, _), _ = jax.lax.scan(inner, (s, t0), length=n_steps)
        with obs_trace.scope("swe.args"):
            return jnp.stack(s, axis=-1)[None]

    sm = jax.shard_map(body, mesh=sim.device_mesh,
                       in_specs=in_specs, out_specs=P("data"),
                       check_vma=False)
    return jax.jit(sm), args


def make_sim_runner(sim: Simulation, n_inner: int = 10):
    """Fused/overlapped runner: `run(state, t)` advances n_inner steps in one
    dispatch (the interior/boundary split of overlapped scheduling lives
    inside the step function); ``state`` is ``(P, E_max, 3)`` in and out
    (:func:`_segment_program`)."""
    fn, args = _segment_program(sim, n_inner)
    arg_list = list(args.values())
    segments = itertools.count()
    scheduling = sim.comm_cfg.scheduling.value

    def run(state, t):
        # Host wall-clock spans of one fused dispatch of n_inner steps: the
        # scalar ``t`` to the device, then the launch.  The dispatch is
        # async, so the spans cover launch, not completion — callers that
        # need completion time block outside.
        n = next(segments)
        with obs_trace.span("swe.segment", cat="driver", segment=n,
                            steps=n_inner, scheduling=scheduling):
            with obs_trace.span("swe.segment.put_t", cat="driver",
                                segment=n):
                t = jnp.asarray(t, jnp.float32)
            with obs_trace.span("swe.segment.launch", cat="driver",
                                segment=n):
                return fn(state, *arg_list, t)

    return run


def make_host_scheduled_runner(sim: Simulation):
    """Paper-baseline: communication staged through a host-visible buffer
    between two separately dispatched programs (2 dispatches / step)."""
    swe = sim.swe
    # phase 2: full step (exchange + update) as its own dispatch
    step_sm, args = _segment_program(sim, 1)
    arg_list = list(args.values())

    # phase 1: gather the send payloads (what the paper's communication
    # kernel writes to global memory for the host)
    def gather(state, send_idx, send_mask):
        payloads = state[:, send_idx[0]] * send_mask[0][None, ..., None]
        return payloads   # (1, R, S, 3) on this device

    gather_sm = jax.jit(jax.shard_map(
        gather, mesh=sim.device_mesh,
        in_specs=(P("data"), P("data"), P("data")), out_specs=P("data"),
        check_vma=False))

    class Runner:
        dispatches = 0

        def run(self, state, t, n_steps: int):
            for i in range(n_steps):
                with obs_trace.span("swe.host_step", cat="driver", step=i,
                                    dispatches=2):
                    payload = gather_sm(state, args["send_idx"],
                                        args["send_mask"])
                    jax.block_until_ready(payload)  # host round-trip (l_k)
                    state = step_sm(state, *arg_list,
                                    jnp.asarray(t, jnp.float32))
                    jax.block_until_ready(state)
                self.dispatches += 2
                t += swe.dt
            return state, t

    return Runner()


def build_workload(sim: Simulation, freq: float = 256e6) -> latmodel.SWEWorkload:
    """Eq. 2/3 workload descriptor from the partition statistics."""
    pm = sim.pm
    # critical partition: largest sent/received element count
    per_part_send = pm.n_send
    crit = int(np.argmax(per_part_send + pm.n_neighbors * 1000))
    msg_bytes = int(pm.s_max * 3 * 4)
    return latmodel.SWEWorkload(
        e_total=sim.mesh.n_elements,
        e_core=int(pm.n_core[crit]),
        e_send=int(pm.n_send[crit]),
        e_recv=int(pm.n_send[crit]),
        d_ext=0,
        l_pipe=100,
        n_max=pm.n_max,
        flop_per_element=dg_solver.FLOP_PER_ELEMENT,
        freq=freq,
        msg_bytes=msg_bytes)
