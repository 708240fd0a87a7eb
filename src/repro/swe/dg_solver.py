"""Piecewise-constant discontinuous-Galerkin (cell-centered FV) shallow-water
solver with ACCL-X halo exchange.

Per time step (paper Fig. 7/8):
  1. fire the halo exchange for the boundary elements (streaming: chunked
     collective-permutes with no barrier — XLA overlaps them with step 2;
     buffered: whole-message permute behind an optimization barrier);
  2. compute fluxes on all LOCAL edges (interior/land/sea) — the "core
     element" work that hides the communication latency;
  3. consume the received halo for the remote edges and update.

Under ``Scheduling.OVERLAPPED`` the step is additionally split into an
interior/boundary element partition: interior elements (no remote edge) are
fluxed and updated with NO data dependency on the exchange, while the
double-buffered exchange (``streaming.double_buffered_exchange``) folds each
round's message into the halo as it lands; only the boundary elements are then
recomputed against the real halo and scattered over the interior result.  The
arithmetic per element is identical, so all schedules are bitwise-equal —
only the dependency structure (and therefore the achievable compute/comm
overlap) differs.

Rusanov (local Lax-Friedrichs) flux; reflective land boundaries; open-sea
boundary with optional tidal forcing (the bight-of-Abaco scenario).

Layout: the step works component-major, element axis last — the state is
(3, E), the per-edge arrays (3edges, E) (:func:`edge_major`) — so on a TPU
the elements lie along the 128-wide lane axis, where row-major (E, 3) rows
would pad each element's 3 values to a full lane row.  The neighbour gather
returns (3, 3edges, E), the layout the flux reads, with indices promised in
bounds (no negative-index wrap).  Halo messages stay (S_max, 3) rows.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import collectives, streaming
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, Scheduling
from repro.obs import trace as obs_trace
from repro.swe.partition import PartitionedMesh

G = 9.81
# FLOP count per element per step (3 edges × Rusanov ≈ 75 flops + update),
# used for the Eq. 2 throughput accounting like the paper's FLOP_sum.
FLOP_PER_ELEMENT = 260.0


def physical_flux(u, n):
    """u: (3, ...) = (h, hu, hv); n: (2, ...) scaled outward normal."""
    h = jnp.maximum(u[0], 1e-8)
    hu, hv = u[1], u[2]
    un = (hu * n[0] + hv * n[1]) / h                # normal velocity * |n|
    f0 = h * un
    f1 = hu * un + 0.5 * G * h * h * n[0]
    f2 = hv * un + 0.5 * G * h * h * n[1]
    return jnp.stack([f0, f1, f2])


def rusanov(u_l, u_r, n):
    """Rusanov numerical flux through an edge with scaled normal n."""
    nlen = jnp.maximum(jnp.linalg.norm(n, axis=0), 1e-12)
    nhat = n / nlen
    h_l = jnp.maximum(u_l[0], 1e-8)
    h_r = jnp.maximum(u_r[0], 1e-8)
    un_l = (u_l[1] * nhat[0] + u_l[2] * nhat[1]) / h_l
    un_r = (u_r[1] * nhat[0] + u_r[2] * nhat[1]) / h_r
    lam = jnp.maximum(jnp.abs(un_l) + jnp.sqrt(G * h_l),
                      jnp.abs(un_r) + jnp.sqrt(G * h_r))
    return 0.5 * (physical_flux(u_l, n) + physical_flux(u_r, n)
                  - lam * nlen * (u_r - u_l))


def reflect(u, n):
    """Reflective (land) ghost state: mirror the normal momentum."""
    nlen = jnp.maximum(jnp.linalg.norm(n, axis=0), 1e-12)
    nhat = n / nlen
    qn = u[1] * nhat[0] + u[2] * nhat[1]
    return jnp.stack([u[0],
                      u[1] - 2 * qn * nhat[0],
                      u[2] - 2 * qn * nhat[1]])


def edge_major(normals, neigh_idx, edge_type):
    """The per-element edge arrays in the step's layout, element axis last:
    ``normals`` (..., E, 3edges, 2) -> (..., 2, 3edges, E), ``neigh_idx``
    and ``edge_type`` (..., E, 3edges) -> (..., 3edges, E).  Takes numpy or
    jax arrays."""
    return (normals.swapaxes(-1, -3), neigh_idx.swapaxes(-1, -2),
            edge_type.swapaxes(-1, -2))


def take(a, idx):
    """``a[..., idx]`` for indices that are in bounds by construction
    (``partition_mesh``): no negative-index wrap, no clamp."""
    return a.at[..., idx].get(mode="promise_in_bounds",
                              wrap_negative_indices=False)


@dataclasses.dataclass(frozen=True)
class SWEConfig:
    dt: float = 1e-4
    tidal_amplitude: float = 0.0
    tidal_omega: float = 0.5
    h_sea: float = 1.0


def stable_dt(mesh, swe: SWEConfig = SWEConfig(), cfl: float = 0.5) -> float:
    """``swe.dt``, or less where the mesh needs it: the explicit update is
    stable while ``dt · c · P / A ≤ cfl`` on every element (P perimeter, A
    area), with c the gravity-wave speed at twice the sea depth.  The bight
    generator makes thin elements, and min(A / P) falls faster than the
    mean as the mesh grows: at 86,578 elements a step of 1e-4 is ten times
    too long and the state turns to NaN within 80 steps.  Depends on the
    geometry only, so a mesh rebuilt from a snapshot keeps its step."""
    perimeter = np.linalg.norm(mesh.normals, axis=-1).sum(-1)
    c = np.sqrt(G * 2.0 * swe.h_sea)
    return float(min(swe.dt, cfl * np.min(mesh.area / perimeter) / c))


def make_step_fn(pm: PartitionedMesh, comm_cfg: CommConfig, axis: str = "data",
                 swe: SWEConfig = SWEConfig(), topology=None,
                 round_cfgs=None):
    """Returns step(state, t, area, normals, neigh_idx, edge_type, valid,
    send_idx, send_mask, recv_slot, boundary_idx) -> new state, for use
    inside shard_map, on ``PartitionedMesh``'s row-major arrays: this
    device's partition slice (leading P dim removed), ``state`` (E_max, 3).

    A wrapper that lays the arguments out for :func:`make_step_core` and
    the result back; the segment runner calls the core directly.
    """
    core = make_step_core(pm, comm_cfg, axis, swe, topology, round_cfgs)

    def step(state, t, area, normals, neigh_idx, edge_type, valid, *rest):
        return core(state.T, t, area,
                    *edge_major(normals, neigh_idx, edge_type),
                    valid, *rest).T

    return step


def make_step_core(pm: PartitionedMesh, comm_cfg: CommConfig,
                   axis: str = "data", swe: SWEConfig = SWEConfig(),
                   topology=None, round_cfgs=None):
    """Returns step(state, t, area, normals, neigh_idx, edge_type, valid,
    send_idx, send_mask, recv_slot, boundary_idx) -> new state in the
    component-major layout, for use inside shard_map.

    All arrays are this device's partition slice (leading P dim removed),
    with the element axis last: ``state`` (3, E_max); ``area``, ``valid``
    (E_max,); ``normals`` (2, 3edges, E_max), ``neigh_idx`` and
    ``edge_type`` (3edges, E_max) (:func:`edge_major`).  The exchange
    arrays keep ``PartitionedMesh``'s layout.

    ``comm_cfg.scheduling == OVERLAPPED`` selects the interior/boundary-split
    step (interior compute carries no dependency on the exchange); all other
    schedules use the exchange-then-update step.  Both are bitwise-equal.

    ``topology`` places the partitions on a virtual multi-hop torus
    (:class:`~repro.core.topology.TorusSpec`): exchange edges spanning more
    than one hop are physically routed through intermediate partitions
    (value-identical).  ``round_cfgs`` is the driver's per-edge hop-aware
    selection — one config per exchange round (rounds group edges of
    comparable hop distance); serial scheduling only, and ``comm_cfg``
    remains the step-structure config.
    """
    comm = Communicator((axis,), (pm.n_parts,), topo=topology)
    rounds = pm.rounds
    exchange_cfg = (list(round_cfgs) if round_cfgs is not None
                    and comm_cfg.scheduling != Scheduling.OVERLAPPED
                    else comm_cfg)

    def payloads_for(state, send_idx, send_mask):
        """Each round's (S_max, 3) rows of the elements it sends."""
        return [(take(state, send_idx[r]) * send_mask[r]).T
                for r in range(pm.n_rounds)]

    def fold_round(halo, recv_slot_r, recv):
        """Scatter-add one round's message (or any row-aligned slice of it)
        into its halo slots."""
        ok = recv_slot_r >= 0
        return halo.at[jnp.where(ok, recv_slot_r, pm.h_max - 1)].add(
            jnp.where(ok[:, None], recv, 0.0))

    def exchange(state, send_idx, send_mask, recv_slot):
        """Halo exchange -> (H_max, 3) halo buffer."""
        halo = jnp.zeros((pm.h_max, 3), state.dtype)
        if not rounds:
            return halo
        received = collectives.multi_neighbor_exchange(
            payloads_for(state, send_idx, send_mask), rounds, comm,
            exchange_cfg)
        for r, recv in enumerate(received):
            halo = fold_round(halo, recv_slot[r], recv)
        return halo

    def exchange_overlapped(state, send_idx, send_mask, recv_slot):
        """Double-buffered exchange with chunk-level halo consume: each
        recv_slot-aligned wire chunk is scatter-added into the halo AS IT
        LANDS, so a single large neighbor message overlaps its own assembly
        instead of fencing the fold on the whole round (buffered-mode rounds,
        which have no wire chunks, still fold per round)."""
        halo = jnp.zeros((pm.h_max, 3), state.dtype)
        if not rounds:
            return halo
        # Chunk geometry is shared by every round (payloads are all
        # (S_max, 3)): align to 3 flat elements so a wire chunk always
        # carries whole (h, hu, hv) halo rows.
        probe = jnp.zeros((pm.s_max, 3), state.dtype)
        _, chunk_elems = streaming.aligned_chunks(probe, comm_cfg, align=3)
        rows_per_chunk = chunk_elems // 3

        def fold_chunk(h, r, i, chunk):
            r0 = i * rows_per_chunk
            slots = lax.slice_in_dim(recv_slot[r], r0,
                                     min(r0 + rows_per_chunk, pm.s_max))
            rows = chunk.reshape(-1, 3)[: slots.shape[0]]
            return fold_round(h, slots, rows)

        halo, _ = collectives.multi_neighbor_exchange(
            payloads_for(state, send_idx, send_mask), rounds, comm, comm_cfg,
            consume=lambda h, r, recv: fold_round(h, recv_slot[r], recv),
            init=halo, chunk_consume=fold_chunk, chunk_align=3)
        return halo

    def edge_fluxes(u_own, u_n, n, edge_type, t):
        """Rusanov flux per edge; shape-generic over the trailing element
        axis.

        ``u_own``: (3, ...) element states; ``u_n``: (3, 3edges, ...)
        neighbour states; ``n``: (2, 3edges, ...) scaled normals;
        ``edge_type``: (3edges, ...).
        """
        u = jnp.broadcast_to(u_own[:, None], u_n.shape)
        # ghost states per edge type
        u_land = reflect(u, n)
        h_sea = swe.h_sea + swe.tidal_amplitude * jnp.sin(swe.tidal_omega * t)
        u_sea = jnp.stack([jnp.broadcast_to(h_sea, u[0].shape), u[1], u[2]])
        u_r = jnp.where(edge_type == 1, u_land,
                        jnp.where(edge_type == 2, u_sea, u_n))
        return rusanov(u, u_r, n)                      # (3, 3edges, ...)

    def gather(state, halo, neigh_idx):
        """Each element's three neighbour states, from its own partition or
        the halo, (3, 3edges, ...): element axis last, as the flux reads
        it."""
        ext = jnp.concatenate([state, halo.T], axis=1)  # (3, E_max+H_max)
        return take(ext, neigh_idx)

    def apply_update(state, f, area, valid):
        """``state`` (3, ...), ``f`` (3, 3edges, ...), ``area`` and
        ``valid`` (...): the new state, (3, ...)."""
        div = jnp.sum(f, axis=1)                       # (3, ...)
        new = state - swe.dt / area * div
        new = new * valid
        # keep water depth positive
        return new.at[0].set(jnp.maximum(new[0], 1e-6) * valid)

    # Phases are named scopes (``swe.gather``, ``swe.flux``, ``swe.update``,
    # ``swe.exchange``): every device operation of the step carries its
    # phase in its HLO op_name, which the device trace reads.

    def step_serial(state, t, area, normals, neigh_idx, edge_type, valid,
                    send_idx, send_mask, recv_slot, boundary_idx):
        # 1. fire exchange (streaming: overlaps with local flux compute)
        with obs_trace.scope("swe.exchange", rounds=pm.n_rounds):
            halo = exchange(state, send_idx, send_mask, recv_slot)
        # 2+3. fluxes (local edges depend only on state; remote edges read
        # the halo — XLA schedules the permutes against the local part)
        with obs_trace.scope("swe.gather"):
            u_n = gather(state, halo, neigh_idx)
        with obs_trace.scope("swe.flux"):
            f = edge_fluxes(state, u_n, normals, edge_type, t)
        with obs_trace.scope("swe.update"):
            return apply_update(state, f, area, valid)

    def step_overlapped(state, t, area, normals, neigh_idx, edge_type, valid,
                        send_idx, send_mask, recv_slot, boundary_idx):
        # Interior pass: every element updated against an EMPTY halo — no
        # data dependency on the exchange, so the scheduler runs this while
        # the chunk permutes are in flight.  Boundary rows come out wrong
        # here and are overwritten below.
        with obs_trace.scope("swe.interior"):
            with obs_trace.scope("swe.gather"):
                zero_halo = jnp.zeros((pm.h_max, 3), state.dtype)
                u_n = gather(state, zero_halo, neigh_idx)
            with obs_trace.scope("swe.flux"):
                f_int = edge_fluxes(state, u_n, normals, edge_type, t)
            with obs_trace.scope("swe.update"):
                new = apply_update(state, f_int, area, valid)
        # Double-buffered exchange folds rounds into the halo as they land.
        with obs_trace.scope("swe.exchange", rounds=pm.n_rounds):
            halo = exchange_overlapped(state, send_idx, send_mask, recv_slot)
        # Boundary pass: recompute ONLY the elements with a remote edge
        # against the real halo, then scatter them over the interior result.
        # Padded boundary_idx entries duplicate a real row with identical
        # values, so the scatter stays deterministic.
        with obs_trace.scope("swe.boundary"):
            b = boundary_idx
            with obs_trace.scope("swe.gather"):
                u_b = gather(state, halo, take(neigh_idx, b))
                state_b, normals_b, edge_type_b, area_b, valid_b = (
                    take(a, b) for a in (state, normals, edge_type, area,
                                         valid))
            with obs_trace.scope("swe.flux"):
                f_b = edge_fluxes(state_b, u_b, normals_b, edge_type_b, t)
            with obs_trace.scope("swe.update"):
                new_b = apply_update(state_b, f_b, area_b, valid_b)
                return new.at[:, b].set(new_b)

    if comm_cfg.scheduling == Scheduling.OVERLAPPED:
        return step_overlapped
    return step_serial


def initial_state(mesh, hump: bool = True) -> np.ndarray:
    """Still water + Gaussian hump in the bight (for conservation tests and
    the quickstart scenario)."""
    E = mesh.n_elements
    state = np.zeros((E, 3))
    state[:, 0] = 1.0
    if hump:
        c = mesh.centroids
        state[:, 0] += 0.3 * np.exp(-60.0 * ((c[:, 0] - 0.55) ** 2
                                             + (c[:, 1] - 0.5) ** 2))
    return state


def total_mass(state, area, valid) -> jnp.ndarray:
    return jnp.sum(state[..., 0] * area * valid)
