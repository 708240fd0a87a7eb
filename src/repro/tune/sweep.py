"""Measured configuration sweeps — the b_eff synthetic benchmark, automated.

For every (collective, message size, candidate ``CommConfig``) triple the
engine builds the real SPMD program on the running mesh, times it with warmup
(wall clock, ``block_until_ready``), and records the result in a
:class:`~repro.tune.db.TuneDB`.  Scheduling is honored the way the runtime
honors it: fused configs time K ops inside ONE compiled program (one host
dispatch amortized over the loop), host-scheduled configs block on every call
— the same methodology as ``benchmarks/b_eff.py``.

CLI::

    PYTHONPATH=src python -m repro.tune.sweep --fast            # smoke sweep
    PYTHONPATH=src python -m repro.tune.sweep --sizes 1024,65536 \
        --collectives all_reduce,sendrecv --out .repro_tune/tunedb.json
    # virtual 4x4 torus, per-edge hop-distance axis (TuneEntry.hops)
    PYTHONPATH=src python -m repro.tune.sweep --devices 16 --topology 4x4 \
        --hop-distances 1,2,4 --collectives sendrecv --sizes small
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from contextlib import nullcontext
from functools import partial
from pathlib import Path
from typing import Callable, Sequence

import numpy as np

from repro.core import plans, planstore, reliable
from repro.core.config import (CommConfig, CommMode, Reliability, Scheduling,
                               V5E)
from repro.core.topology import TorusSpec
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.tune import prune as tune_prune
from repro.tune import space as tune_space
from repro.tune.db import TuneDB, TuneEntry, default_db_path, topology_key

# Message sizes (bytes per device) swept by default — the paper's Fig. 4 spans
# 64 B .. 4 MiB; host-CPU meshes get a truncated range to keep compiles sane.
FULL_SIZES = (1 << 10, 1 << 14, 1 << 17, 1 << 20)
FAST_SIZES = (1 << 10, 1 << 14)
# "small" smoke set: one mid + one large size, so the pruning model still
# sees the bandwidth/segmentation-separated regime (a 16 KiB-only sweep
# cannot distinguish segment sizes — every message is a single chunk).
NAMED_SIZES = {"small": (1 << 14, 1 << 20), "full": FULL_SIZES}

SWEEPABLE = ("sendrecv", "all_reduce", "all_gather", "reduce_scatter",
             "multi_neighbor", "all_to_all", "hierarchical_all_reduce")

# Collectives with end-to-end consumer-loop benchmarks (the
# hideable-compute consumers of the paper's §5 argument), one tuple per
# collective.  all_reduce serves three phases with opposite cost
# structures: the training row-parallel matmul+reduce layer, the serving
# decode step (tiny latency-bound per-token combines with almost no
# hideable compute), and prefill (throughput-bound bulk reduces behind a
# large hideable matmul).  Under ``--objective e2e`` each consumer is
# measured separately and recorded as its own TuneEntry (tagged
# ``TuneEntry.consumer``) so ``select_config(consumer=...)`` can answer
# per phase.  The first consumer in each tuple is the primary one — the
# one the pruning model predicts with.
CONSUMERS: dict[str, tuple[str, ...]] = {
    "all_reduce": ("row_parallel", "decode_step", "prefill"),
    "multi_neighbor": ("halo_fold",),
    "all_to_all": ("moe_loop",),
}

# Collectives whose benchmark pattern is parameterized by a torus hop
# distance (the --hop-distances axis): the perm is a translation of the
# whole virtual torus by exactly d hops.
HOP_PATTERNED = ("sendrecv", "multi_neighbor")

OBJECTIVES = ("latency", "e2e")

# row_parallel consumer geometry: the reduced output is (tokens, _ROWPAR_D)
# with tokens*_ROWPAR_D*4 = msg_bytes; the hideable per-device matmul
# contracts over _ROWPAR_FF features.
_ROWPAR_D = 64
_ROWPAR_FF = 128
# moe_loop consumer geometry: (tokens, _MOE_D) dispatch payload with
# tokens*_MOE_D*4 = msg_bytes; each expert's FFN expands to _MOE_FF.
_MOE_D = 32
_MOE_FF = 64
# decode_step consumer geometry: a (batch, _DEC_D) per-token activation with
# batch*_DEC_D*4 = msg_bytes; the per-step matmul contracts over _DEC_D —
# near-zero hideable compute, latency-bound (the serving decode phase).
_DEC_D = 16
# prefill consumer geometry: (tokens, _PRE_FF) activations with
# tokens*_PRE_FF*4 = msg_bytes and a _PRE_FF-wide contraction — a large
# hideable matmul per combine, throughput-bound (the serving prefill phase).
_PRE_FF = 256


def consumer_flops(collective: str, msg_bytes: int,
                   consumer: str | None = None) -> float:
    """Hideable per-iteration compute (FLOPs) of a collective's consumer
    loop — feeds the e2e prediction (compute_s = flops / peak).  With
    ``consumer`` omitted, the collective's primary consumer is assumed."""
    if consumer is None:
        consumer = (CONSUMERS.get(collective) or ("",))[0]
    if collective == "all_reduce":
        if consumer == "decode_step":
            # tiny per-token matmul + the LSE max/sum pair: ~4 flops/elem
            return 4.0 * (msg_bytes / 4.0)
        if consumer == "prefill":
            # bulk matmul: 2 * tokens * ff^2 with tokens*ff = msg_bytes/4
            return 2.0 * _PRE_FF * (msg_bytes / 4.0)
        # matmul: 2 * tokens * ff * d with tokens*d = msg_bytes/4 elements
        return 2.0 * _ROWPAR_FF * (msg_bytes / 4.0)
    if collective == "multi_neighbor":
        # elementwise interior update over the state (~12 flops/element)
        return 12.0 * (msg_bytes / 4.0)
    if collective == "all_to_all":
        # expert FFN: two matmuls (D->FF, FF->D) over tokens*D = msg/4 elems
        return 4.0 * _MOE_FF * (msg_bytes / 4.0)
    return 0.0


# ----------------------------------------------------------------------
# Microbenchmark program builders
# ----------------------------------------------------------------------

def _payload_elems(msg_bytes: int, n: int) -> int:
    """float32 elements per device, padded to a multiple of the mesh size so
    reduce-scatter/all-to-all constraints hold for every collective."""
    elems = max(n, msg_bytes // 4)
    return elems + (-elems) % n


def _mesh_key(mesh) -> tuple:
    """Program-cache key component for the bench mesh's STRUCTURE.

    ``topology_key`` is only platform:n_devices — two factorizations of the
    same device count (an 8-rank axis vs a 4x2 inner/outer mesh) compile
    different programs and must never replay each other's."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape))


def _multi_neighbor_rounds(comm) -> list:
    """The 4-neighbor halo pattern (ring distance ±1, ±2) — the SWE
    exchange.  Single source for both the benchmark op and the hop distance
    recorded with its measurements."""
    return [comm.ring_perm(1), comm.reverse_ring_perm(1),
            comm.ring_perm(2), comm.reverse_ring_perm(2)]


def _pattern_hops(collective: str, comm) -> int:
    """Worst-case torus hop distance of the pattern a collective exercises
    (recorded per TuneEntry so selection can prefer hop-matched results)."""
    if collective == "multi_neighbor":
        return comm.max_hops(
            [e for r in _multi_neighbor_rounds(comm) for e in r])
    if collective == "all_to_all":
        # every rank exchanges with every other rank
        return max((comm.torus_hops(0, j) for j in range(comm.size)),
                   default=0) or 1
    return comm.max_hops(comm.ring_perm())


def _build_op(collective: str, comm, cfg: CommConfig,
              subcomms=None, hop_distance: int | None = None) -> Callable:
    """Per-device body (x -> x-shaped array) exercising one collective op.

    ``subcomms`` is the (inner, outer) communicator pair for the
    hierarchical (cross-pod) all-reduce, which runs over a 2-axis mesh.
    ``hop_distance`` (virtual torus only) replaces the hop-patterned
    collectives' default edge list with a translation perm at exactly that
    many torus hops — the per-edge axis of the hop-distance sweep.
    """
    from jax import numpy as jnp
    from repro.core import collectives

    if hop_distance is not None and collective not in HOP_PATTERNED:
        raise ValueError(f"{collective!r} has no hop-parameterized pattern "
                         f"(hop-patterned: {HOP_PATTERNED})")
    if collective == "sendrecv":
        perm = (comm.hop_perm(hop_distance) if hop_distance is not None
                else comm.ring_perm())
        def op(x):
            return collectives.sendrecv(x, perm, comm, cfg)
    elif collective == "all_reduce":
        def op(x):
            return collectives.all_reduce(x, comm, cfg) / comm.size
    elif collective == "all_gather":
        def op(x):
            y = collectives.all_gather(x, comm, cfg, axis=0)
            # keep x's shape but depend on the whole gathered result so the
            # collective cannot be dead-code-eliminated
            return x + 0.0 * jnp.sum(y)
    elif collective == "reduce_scatter":
        def op(x):
            y = collectives.reduce_scatter(x, comm, cfg)
            return x + 0.0 * jnp.sum(y)
    elif collective == "multi_neighbor":
        if hop_distance is not None:
            mn_rounds = [comm.hop_perm(hop_distance),
                         comm.topo.reverse_hop_perm(hop_distance)]
        else:
            mn_rounds = _multi_neighbor_rounds(comm)
        def op(x):
            outs = collectives.multi_neighbor_exchange(
                [x] * len(mn_rounds), mn_rounds, comm, cfg)
            return sum(outs) / len(outs)
    elif collective == "all_to_all":
        def op(x):
            # (n, elems/n) bucketed payload — the MoE dispatch shape
            y = collectives.all_to_all(x.reshape(comm.size, -1), comm, cfg)
            return x + 0.0 * jnp.sum(y)
    elif collective == "hierarchical_all_reduce":
        inner, outer = subcomms
        def op(x):
            return collectives.hierarchical_all_reduce(
                x, inner, outer, cfg) / (inner.size * outer.size)
    else:
        raise ValueError(f"unknown collective {collective!r} "
                         f"(sweepable: {SWEEPABLE})")
    return op


def _build_consumer_op(collective: str, comm, cfg: CommConfig,
                       msg_bytes: int,
                       hop_distance: int | None = None,
                       consumer: str | None = None
                       ) -> tuple[Callable, tuple]:
    """One iteration of the collective's consumer loop: (op, per_dev_shape).

    ``op`` maps a per-device payload to a same-shaped payload so iterations
    chain; the body is compute the schedule could hide the collective
    behind — the end-to-end time is what the ``e2e`` objective ranks.
    ``hop_distance`` (hop-patterned collectives on a virtual torus) swaps
    the exchange pattern for the same translation perm the bare benchmark
    measures, so a per-hop ``e2e_us`` really routed at that distance.
    ``consumer`` picks one of the collective's loops from
    :data:`CONSUMERS` (default: the primary one) — all_reduce serves
    row_parallel (training TP), decode_step (latency-bound serving), and
    prefill (throughput-bound serving).
    """
    from jax import numpy as jnp
    from repro.core import collectives, streaming

    if consumer is None:
        consumer = (CONSUMERS.get(collective) or ("",))[0]

    if collective == "all_reduce" and consumer == "decode_step":
        # Serving decode step: a tiny (batch, d) per-token activation, the
        # LSE-combine pair (max reduce + sum reduce — exactly the partial-
        # attention combine in models.attention.decode_attention) and a
        # row-parallel output combine with a near-trivial matmul.  Almost
        # no hideable compute: the config's fixed per-op cost dominates,
        # which is what makes decode's winner differ from prefill's.
        b = max(4, msg_bytes // 4 // _DEC_D)
        w = jnp.asarray(
            np.random.RandomState(2).randn(_DEC_D, _DEC_D) * 0.05,
            jnp.float32)

        def op(h):
            m = collectives.all_reduce(h, comm, cfg, op="max")
            if (cfg.mode == CommMode.STREAMING
                    or cfg.scheduling == Scheduling.OVERLAPPED):
                y = streaming.overlapped_matmul_allreduce(h, w, comm, cfg)
            else:
                partial = jnp.dot(h, w, preferred_element_type=jnp.float32)
                y = collectives.all_reduce(partial, comm, cfg)
            return jnp.tanh(h + 1e-3 * (y - 1e-3 * m))

        return op, (b, _DEC_D)

    if collective == "all_reduce" and consumer == "prefill":
        # Serving prefill: bulk (tokens, ff) activations with a wide
        # hideable matmul per combine — throughput-bound; the overlapped
        # schedules can hide most of the wire time behind the contraction.
        tokens = max(8, msg_bytes // 4 // _PRE_FF)
        w = jnp.asarray(
            np.random.RandomState(3).randn(_PRE_FF, _PRE_FF) * 0.05,
            jnp.float32)

        def op(h):
            if (cfg.mode == CommMode.STREAMING
                    or cfg.scheduling == Scheduling.OVERLAPPED):
                y = streaming.overlapped_matmul_allreduce(h, w, comm, cfg)
            else:
                partial = jnp.dot(h, w, preferred_element_type=jnp.float32)
                y = collectives.all_reduce(partial, comm, cfg)
            return jnp.tanh(h + 1e-3 * y)

        return op, (tokens, _PRE_FF)

    if collective == "all_reduce":
        # Row-parallel TP layer: per-device matmul + combine of the partial
        # sum.  Mirrors models.layers.row_parallel: streaming mode or
        # overlapped scheduling routes the chunked, double-buffered
        # overlapped_matmul_allreduce; buffered+fused/host issues one
        # all_reduce after the full matmul.
        tokens = max(8, msg_bytes // 4 // _ROWPAR_D)
        w = jnp.asarray(
            np.random.RandomState(0).randn(_ROWPAR_FF, _ROWPAR_D) * 0.05,
            jnp.float32)

        def op(h):
            if (cfg.mode == CommMode.STREAMING
                    or cfg.scheduling == Scheduling.OVERLAPPED):
                y = streaming.overlapped_matmul_allreduce(h, w, comm, cfg)
            else:
                partial = jnp.dot(h, w, preferred_element_type=jnp.float32)
                y = collectives.all_reduce(partial, comm, cfg)
            # feed the reduced output back into the activation shape so the
            # next iteration depends on this one
            return jnp.tanh(h + 1e-3 * jnp.sum(y, axis=-1, keepdims=True))

        return op, (tokens, _ROWPAR_FF)

    if collective == "multi_neighbor":
        # Halo-fold step: 4-neighbor exchange + fold of the received halos
        # + an interior element update the overlapped schedule can issue
        # while the exchange is in flight.
        if hop_distance is not None:
            rounds = [comm.hop_perm(hop_distance),
                      comm.topo.reverse_hop_perm(hop_distance)]
        else:
            rounds = _multi_neighbor_rounds(comm)
        n = comm.size
        elems = _payload_elems(msg_bytes, n)

        def op(x):
            payloads = [x] * len(rounds)
            interior = x * 0.999 + 0.001 * jnp.tanh(x)     # hideable compute
            if cfg.scheduling == Scheduling.OVERLAPPED:
                halo, _ = collectives.multi_neighbor_exchange(
                    payloads, rounds, comm, cfg,
                    consume=lambda c, r, m: c + m, init=jnp.zeros_like(x))
            else:
                received = collectives.multi_neighbor_exchange(
                    payloads, rounds, comm, cfg)
                halo = sum(received)
            return interior + 1e-3 * jnp.tanh(halo)

        return op, (elems,)

    if collective == "all_to_all":
        # MoE expert loop: dispatch (all_to_all) -> expert FFN -> combine
        # (all_to_all back).  The FFN is the hideable compute: the chunked
        # overlapped dispatch/combine (streaming.chunked_all_to_all) lets
        # the scheduler run expert matmuls on chunk i while chunk i+1 is on
        # the wire — the third consumer of the paper's §5 argument.
        n = comm.size
        tokens = max(n, msg_bytes // 4 // _MOE_D)
        tokens += (-tokens) % n              # all_to_all split constraint
        rng = np.random.RandomState(1)
        w1 = jnp.asarray(rng.randn(_MOE_D, _MOE_FF) * 0.05, jnp.float32)
        w2 = jnp.asarray(rng.randn(_MOE_FF, _MOE_D) * 0.05, jnp.float32)

        def op(x):
            y = collectives.all_to_all(x, comm, cfg)            # dispatch
            h = jnp.tanh(jnp.dot(y, w1,
                                 preferred_element_type=jnp.float32))
            h = jnp.dot(h, w2, preferred_element_type=jnp.float32)
            z = collectives.all_to_all(h.astype(x.dtype), comm, cfg)  # combine
            return jnp.tanh(x + 1e-3 * z)

        return op, (tokens, _MOE_D)

    raise ValueError(f"no consumer-loop benchmark {consumer!r} for "
                     f"{collective!r} (consumers: {CONSUMERS})")


# Per-rep seconds of the most recent _time_program call.  The sweep reads
# this right after each measurement to estimate the candidate's tail
# (TuneEntry.p95_us) without changing the timer's return contract; injected
# test timers never populate it, so the sweep's p95 falls back to 0.0 (the
# "no tail data" sentinel) instead of inheriting a stale run's samples —
# run_sweep clears the list before every timer call.
_LAST_SAMPLES: list[float] = []


def _time_program(op: Callable, mesh, msg_bytes: int, cfg: CommConfig,
                  warmup: int = 1, reps: int = 3, inner: int = 8,
                  per_dev_shape: tuple | None = None,
                  cache_key: tuple | None = None) -> float:
    """Seconds per collective op under the config's scheduling discipline.

    With ``cache_key`` given, the jitted program is fetched from / stored in
    the :mod:`repro.core.plans` program cache: a warm sweep (same process,
    same collective/config/size/topology) replays the compiled program and
    pays zero rebuild/retrace — the plan-cache half of the sweep wall-clock
    win.

    Each rep's per-op seconds are additionally appended to
    :data:`_LAST_SAMPLES` (cleared on entry), the raw material for the
    sweep's per-candidate tail estimate.
    """
    import jax
    from jax import numpy as jnp
    from jax.sharding import PartitionSpec as P

    # Shard dim 0 jointly over every mesh axis (the hierarchical all-reduce
    # benches on a 2-axis inner×outer mesh; everything else on one axis).
    spec = P(tuple(mesh.axis_names))
    n = mesh.devices.size
    if per_dev_shape is None:
        per_dev_shape = (_payload_elems(msg_bytes, n),)
    # Committed to the output sharding up front: every call (including the
    # first) then presents one input layout, so the program compiles once
    # and an AOT-serialized executable replays for all of them.
    x = jax.device_put(jnp.zeros((n,) + tuple(per_dev_shape), jnp.float32),
                       jax.sharding.NamedSharding(mesh, spec))

    def build_single():
        return jax.jit(jax.shard_map(
            lambda xs: op(xs[0])[None], mesh=mesh,
            in_specs=spec, out_specs=spec, check_vma=False))

    if cfg.scheduling != Scheduling.HOST:
        # fused and overlapped are both device-scheduled: one dispatch
        # amortized over the compiled loop
        def build_many():
            def many(xs):
                for _ in range(inner):
                    xs = jax.shard_map(
                        lambda v: op(v[0])[None], mesh=mesh,
                        in_specs=spec, out_specs=spec, check_vma=False)(xs)
                return xs
            return jax.jit(many)

        if cache_key is not None:
            fn = plans.jitted_program(
                cache_key + ("many", inner, tuple(per_dev_shape)), build_many,
                example_args=(x,))
        else:
            fn = build_many()
        for _ in range(warmup):
            x = jax.block_until_ready(fn(x))
        del _LAST_SAMPLES[:]
        t0 = time.perf_counter()
        for _ in range(reps):
            t1 = time.perf_counter()
            x = jax.block_until_ready(fn(x))
            _LAST_SAMPLES.append((time.perf_counter() - t1) / inner)
        return (time.perf_counter() - t0) / (reps * inner)

    # Host scheduling: one dispatch per op, host blocks between dispatches.
    if cache_key is not None:
        single = plans.jitted_program(
            cache_key + ("single", tuple(per_dev_shape)), build_single,
            example_args=(x,))
    else:
        single = build_single()
    for _ in range(warmup):
        x = jax.block_until_ready(single(x))
    del _LAST_SAMPLES[:]
    t0 = time.perf_counter()
    for _ in range(reps):
        t1 = time.perf_counter()
        for _ in range(inner):
            x = jax.block_until_ready(single(x))
        _LAST_SAMPLES.append((time.perf_counter() - t1) / inner)
    return (time.perf_counter() - t0) / (reps * inner)


# ----------------------------------------------------------------------
# Sweep driver
# ----------------------------------------------------------------------

def _seed_calibration(mesh, comm, db: TuneDB, topo: str,
                      sizes: Sequence[int], reps: int, inner: int,
                      log: Callable[[str], None], timer=None,
                      torus: str = ""):
    """Cold-cache calibration seed: measure the sendrecv corner configs so
    the Eq. 1 fit has points on THIS substrate before pruning starts.  The
    seed measurements are real TuneDB entries (they also serve selection)."""
    log("[prune] cold cache: seeding Eq.1 calibration with a sendrecv "
        "corner sweep")
    timer = timer or _time_program
    hops = _pattern_hops("sendrecv", comm)
    for msg_bytes in sizes:
        for cfg in tune_space.enumerate_configs("sendrecv", fast=True):
            try:
                op = _build_op("sendrecv", comm, cfg)
                sec = timer(
                    op, mesh, msg_bytes, cfg, reps=reps, inner=inner,
                    cache_key=("sweep", topo, torus, 0, _mesh_key(mesh),
                               "sendrecv",
                               tuple(sorted(tune_space.config_to_dict(
                                   cfg).items())), int(msg_bytes)))
            except Exception as e:  # noqa: BLE001
                log(f"  seed skip sendrecv/{msg_bytes}B: "
                    f"{type(e).__name__}: {e}")
                continue
            db.add(TuneEntry(
                topo=topo, collective="sendrecv", msg_bytes=int(msg_bytes),
                config=tune_space.config_to_dict(cfg),
                us_per_call=sec * 1e6, gbps=msg_bytes / sec / 1e9,
                hops=hops, torus=torus))
    return tune_prune.calibration_from_db(db, topo)


def run_sweep(mesh=None, collectives: Sequence[str] = SWEEPABLE,
              sizes: Sequence[int] | None = None, fast: bool = False,
              db: TuneDB | None = None, max_configs: int | None = None,
              reps: int = 3, inner: int = 8,
              log: Callable[[str], None] | None = None,
              prune: bool = False,
              prune_ratio: float = tune_prune.DEFAULT_RATIO,
              calibration=None,
              objective: str = "latency",
              stats: dict | None = None,
              topology: TorusSpec | None = None,
              hop_distances: Sequence[int] | None = None,
              loss_rate: float = 0.0,
              timer: Callable | None = None) -> TuneDB:
    """Measure every candidate config and return the populated TuneDB.

    ``prune=True`` enables the paper-style model-guided search: an Eq. 1
    calibration (fitted from existing sendrecv entries, or from a small
    seed sweep on a cold cache) predicts every candidate's latency and the
    sweep skips configs ranked more than ``prune_ratio``× off the predicted
    incumbent.  ``stats`` (optional dict) receives the bookkeeping:
    candidate/measured/pruned counts and wall clock, including the
    estimated exhaustive wall clock the pruning saved and the plan-cache
    hit/miss deltas.

    ``objective="e2e"`` additionally measures each candidate *end-to-end*
    for the collectives with consumer-loop benchmarks (:data:`CONSUMERS`:
    the row-parallel matmul+reduce layer, the serving decode step and
    prefill loops, the halo-fold step, and the MoE
    dispatch→expert-FFN→combine loop) — one measurement and one tagged
    ``TuneEntry`` per consumer, so ``select_config(consumer=...)`` answers
    per phase from a single sweep — keeps consumer-distinct candidates
    (overlapped scheduling) in the space, and — with ``prune=True`` —
    ranks candidates by the overlap-aware e2e prediction instead of bare
    Eq. 1 latency.

    ``topology`` places the bench communicator on a virtual multi-hop torus
    (:class:`~repro.core.topology.TorusSpec`): multi-hop edges physically
    route through intermediate ranks, so measured latency carries the
    per-hop cost.  ``hop_distances`` adds the per-edge sweep axis — the
    hop-patterned collectives (:data:`HOP_PATTERNED`) are measured once per
    distance with ``TuneEntry.hops`` recording it, which is what lets
    ``select_config(hops=...)`` answer per edge.

    ``loss_rate`` > 0 sweeps a LOSSY wire: every candidate is forced to
    ``Reliability.GUARANTEED`` (best-effort delivery cannot survive chunk
    loss), each measurement runs under a seeded
    :class:`~repro.core.reliable.WireFaults` chunk-drop schedule at that
    rate, and entries record ``TuneEntry.loss`` so selection can prefer
    configs measured on a matching wire — the sweep half of the paper's
    "jumbo frames win clean links, small segments win lossy ones" answer.

    ``timer`` overrides the measurement function (signature of
    :func:`_time_program`) — deterministic model-driven timers make the
    selection pipeline testable end-to-end without wall-clock noise.
    """
    import jax
    from repro.launch.mesh import make_mesh
    from repro.core.communicator import Communicator

    if objective not in OBJECTIVES:
        raise ValueError(f"objective must be one of {OBJECTIVES}, "
                         f"got {objective!r}")
    if not 0.0 <= loss_rate < 1.0:
        raise ValueError(f"loss_rate must be in [0, 1), got {loss_rate}")
    # One seeded schedule for the whole sweep: every candidate faces the
    # SAME drop pattern (reliable.inject resets the message counter per
    # measurement), so latency differences are config, not luck.
    wire = (reliable.WireFaults(seed=17, drop=loss_rate)
            if loss_rate > 0.0 else None)
    losskey: tuple = (("loss", loss_rate),) if wire is not None else ()
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), ("x",))
    if sizes is None:
        sizes = FAST_SIZES if fast else FULL_SIZES
    if db is None:
        db = TuneDB()
    if fast:
        reps, inner = min(reps, 2), min(inner, 4)
    log = log or (lambda s: None)
    timer = timer or _time_program
    stats = stats if stats is not None else {}
    stats.update(total=0, measured=0, pruned=0, errors=0, e2e_measured=0,
                 wall_s=0.0)
    # Plan-cache deltas come from the obs.metrics registry (the counters
    # behind plans.cache_stats()), so the warm-sweep report shares one
    # source of truth with every other telemetry consumer.
    reg = obs_metrics.registry()
    # Witness for the elastic runtime's no-resweep guarantee: recovery tests
    # assert this counter stays flat across model-based re-selection.
    reg.counter("sweep.runs").inc()
    cache_ctrs = {k: reg.counter(f"plans.{k}") for k in
                  ("plan_hits", "plan_misses",
                   "program_hits", "program_misses",
                   "disk_hits", "disk_misses")}
    cache_before = {k: int(c.value) for k, c in cache_ctrs.items()}
    t_start = time.perf_counter()

    axis = mesh.axis_names[0]
    comm = Communicator.from_mesh(mesh, axis, topo=topology)
    topo = topology_key(mesh)
    torus = topology.name if topology is not None else ""
    n = mesh.devices.size
    if hop_distances is not None:
        if topology is None:
            raise ValueError("--hop-distances requires --topology "
                             "(hop distances live on a virtual torus)")
        bad = [d for d in hop_distances
               if not 1 <= d <= topology.diameter]
        if bad:
            raise ValueError(f"hop distances {bad} outside this torus's "
                             f"[1, {topology.diameter}]")

    if prune and calibration is None:
        calibration = tune_prune.calibration_from_db(db, topo)
        if calibration is None:
            # Seed wall clock is tracked separately: it is calibration
            # overhead, not sweep time, and must not inflate the
            # estimated-exhaustive comparison.  Seed entries land in the
            # DB, so a sendrecv sweep in the same run keeps the faster of
            # the two measurements per config.
            t_seed = time.perf_counter()
            calibration = _seed_calibration(mesh, comm, db, topo, sizes,
                                            reps, inner, log, timer=timer,
                                            torus=torus)
            stats["seed_s"] = time.perf_counter() - t_seed
        if calibration is None:
            log("[prune] calibration unavailable — sweeping exhaustively")
        else:
            log(f"[prune] {calibration.summary()}")

    for coll in collectives:
        bench_mesh, subcomms = mesh, None
        if coll == "hierarchical_all_reduce":
            if n < 4 or n % 2:
                log(f"[{topo}] {coll}: skipped (needs an even device count "
                    f">= 4, have {n})")
                continue
            # inner (in-pod / ICI) × outer (cross-pod / DCN) factorization
            bench_mesh = make_mesh((n // 2, 2), ("inner", "outer"))
            inner_comm = Communicator.from_mesh(bench_mesh, "inner")
            outer_comm = Communicator.from_mesh(bench_mesh, "outer")
            subcomms = (inner_comm, outer_comm)
        cands = tune_space.enumerate_configs(coll, fast=fast,
                                             objective=objective)
        if wire is not None:
            # Best-effort candidates cannot deliver under chunk loss:
            # promote everything to GUARANTEED and dedup (promotion can
            # collide candidates that differed only in reliability).
            forced, seen_cfg = [], set()
            for c in cands:
                g = dataclasses.replace(c,
                                        reliability=Reliability.GUARANTEED)
                if g not in seen_cfg:
                    seen_cfg.add(g)
                    forced.append(g)
            cands = forced
        if max_configs is not None:
            cands = cands[:max_configs]
        # The per-edge axis: hop-patterned collectives sweep once per
        # requested distance; everything else measures its natural pattern.
        if (hop_distances is not None and coll in HOP_PATTERNED):
            distances: list[int | None] = list(hop_distances)
        else:
            distances = [None]
        consumers = CONSUMERS.get(coll, ()) if objective == "e2e" else ()
        for hop_d in distances:
            hops = hop_d if hop_d is not None else _pattern_hops(coll, comm)
            log(f"[{topo}{'/' + torus if torus else ''}] {coll}: "
                f"{len(cands)} configs x {len(sizes)} sizes "
                f"(pattern hops={hops}"
                + (f", e2e consumers={','.join(consumers)}"
                   if consumers else "") + ")")
            for msg_bytes in sizes:
                stats["total"] += len(cands)
                to_measure = cands
                if prune and calibration is not None:
                    # The primary consumer's compute feeds the prediction;
                    # pruning is shared across the consumer set (a config
                    # hopeless for the primary loop is measured for none).
                    compute_s = (consumer_flops(coll, msg_bytes)
                                 / V5E.peak_flops if consumers else 0.0)
                    to_measure, skipped = tune_prune.prune_candidates(
                        cands, msg_bytes, calibration, prune_ratio,
                        collective=coll,
                        objective="e2e" if consumers else "latency",
                        compute_s=compute_s, hops=hops, loss=loss_rate)
                    stats["pruned"] += len(skipped)
                    reg.counter("sweep.pruned").inc(len(skipped))
                    if skipped:
                        log(f"  prune {coll}/{msg_bytes}B: measuring "
                            f"{len(to_measure)}/{len(cands)} (model skipped "
                            f"{len(skipped)})")
                cfg_key = lambda c: tuple(sorted(
                    tune_space.config_to_dict(c).items()))
                for i, cfg in enumerate(to_measure):
                    try:
                        op = _build_op(coll, comm, cfg, subcomms=subcomms,
                                       hop_distance=hop_d)
                        del _LAST_SAMPLES[:]
                        with obs_trace.span("sweep.candidate", cat="sweep",
                                            collective=coll,
                                            msg_bytes=int(msg_bytes),
                                            hops=hops, cfg=i) as sp:
                            with (reliable.inject(wire) if wire is not None
                                  else nullcontext()):
                                sec = timer(
                                    op, bench_mesh, msg_bytes, cfg,
                                    reps=reps, inner=inner,
                                    cache_key=("sweep", topo, torus,
                                               hop_d or 0,
                                               _mesh_key(bench_mesh),
                                               coll, cfg_key(cfg),
                                               int(msg_bytes)) + losskey)
                            sp.set(us_per_call=sec * 1e6)
                        # Per-rep samples feed both the aggregate series and
                        # this candidate's tail estimate; timers that report
                        # only a mean contribute that single point.
                        samples = [s * 1e6 for s in _LAST_SAMPLES]
                        hist = reg.histogram("sweep.us", collective=coll)
                        for v in (samples or [sec * 1e6]):
                            hist.observe(v)
                        p95_us = obs_metrics.percentile_of(samples, 95.0)
                    except Exception as e:  # noqa: BLE001 — skip unrunnable combos
                        stats["errors"] += 1
                        log(f"  skip {coll}/{msg_bytes}B cfg{i}: "
                            f"{type(e).__name__}: {e}")
                        continue
                    # One e2e measurement per consumer loop: the same bare
                    # candidate yields one TuneEntry per consumer (tagged),
                    # so selection can answer per phase from one sweep.
                    consumer_e2e: dict[str, float] = {}
                    for consumer in consumers:
                        try:
                            cop, shape = _build_consumer_op(
                                coll, comm, cfg, msg_bytes,
                                hop_distance=hop_d, consumer=consumer)
                            with (reliable.inject(wire) if wire is not None
                                  else nullcontext()):
                                e2e_sec = timer(
                                    cop, bench_mesh, msg_bytes, cfg,
                                    reps=reps, inner=inner,
                                    per_dev_shape=shape,
                                    cache_key=("sweep_e2e", topo, torus,
                                               hop_d or 0,
                                               _mesh_key(bench_mesh), coll,
                                               consumer, cfg_key(cfg),
                                               int(msg_bytes)) + losskey)
                            consumer_e2e[consumer] = e2e_sec * 1e6
                            stats["e2e_measured"] += 1
                            reg.histogram("sweep.e2e_us",
                                          collective=coll).observe(
                                              e2e_sec * 1e6)
                        except Exception as e:  # noqa: BLE001
                            stats["errors"] += 1
                            log(f"  skip e2e {coll}/{consumer}/"
                                f"{msg_bytes}B cfg{i}: "
                                f"{type(e).__name__}: {e}")
                    stats["measured"] += 1
                    for consumer, e2e_us in (consumer_e2e.items()
                                             or ((None, 0.0),)):
                        db.add(TuneEntry(
                            topo=topo, collective=coll,
                            msg_bytes=int(msg_bytes),
                            config=tune_space.config_to_dict(cfg),
                            us_per_call=sec * 1e6,
                            gbps=msg_bytes / sec / 1e9,
                            hops=hops, e2e_us=e2e_us, torus=torus,
                            p95_us=p95_us, loss=loss_rate,
                            consumer=consumer or ""))
                best = db.best(coll, msg_bytes, topo, hops=hops)
                if best is not None:
                    log(f"  {coll:15s} {msg_bytes:>8d}B h{hops} best "
                        f"{best.us_per_call:9.1f} us  ({best.gbps:6.3f} GB/s)  "
                        f"{best.config['mode']}/{best.config['scheduling']}"
                        f"/{best.config['algorithm']}")
                for consumer in consumers:
                    be = db.best(coll, msg_bytes, topo, hops=hops,
                                 objective="e2e", consumer=consumer)
                    if be is not None and be.e2e_us > 0.0:
                        log(f"  {coll:15s} {msg_bytes:>8d}B h{hops} best e2e "
                            f"{be.e2e_us:9.1f} us/iter "
                            f"({consumer}) "
                            f"{be.config['mode']}/{be.config['scheduling']}")
    stats["wall_s"] = time.perf_counter() - t_start
    for k, c in cache_ctrs.items():
        stats[k] = int(c.value) - cache_before[k]
    stats["latency_hist"] = reg.find("sweep.us{")
    # The visible pruning win: scale the measured wall clock (minus any
    # calibration-seed overhead) back up to the exhaustive candidate count
    # (per-config cost assumed comparable).
    if stats["measured"]:
        sweep_s = stats["wall_s"] - stats.get("seed_s", 0.0)
        stats["est_exhaustive_s"] = sweep_s * stats["total"] / stats["measured"]
    return db


def sweep_summary(stats: dict) -> str:
    """One-line wall-clock summary (exhaustive vs calibration-pruned), plus
    the plan-cache hit/miss counts behind the warm-sweep win."""
    line = (f"sweep wall clock {stats.get('wall_s', 0.0):.1f}s: measured "
            f"{stats.get('measured', 0)}/{stats.get('total', 0)} candidate "
            f"configs")
    if stats.get("e2e_measured"):
        line += f" ({stats['e2e_measured']} consumer-loop e2e)"
    if stats.get("pruned"):
        line += (f" — {stats['pruned']} pruned by the calibrated model "
                 f"(exhaustive est. ~{stats.get('est_exhaustive_s', 0.0):.1f}s)")
    line += (f" — plan cache: {stats.get('program_hits', 0)} program hits / "
             f"{stats.get('program_misses', 0)} misses, "
             f"{stats.get('plan_hits', 0)} plan hits / "
             f"{stats.get('plan_misses', 0)} misses")
    if stats.get("disk_hits", 0) or stats.get("disk_misses", 0):
        line += (f" — plan store: {stats.get('disk_hits', 0)} disk hits / "
                 f"{stats.get('disk_misses', 0)} disk misses")
    hists = stats.get("latency_hist") or {}
    for name, h in sorted(hists.items()):
        if h.get("count"):
            line += (f"\n  {name}: p50 {h['p50']:.1f} us, "
                     f"p95 {h['p95']:.1f} us over {h['count']} samples")
    return line


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def _ensure_devices(n: int) -> None:
    """Re-exec with N host CPU devices when launched on a single device.

    The flag only sizes the CPU backend: on an accelerator the sweep runs in
    this one process on the accelerator's devices."""
    if os.environ.get("REPRO_TUNE_NO_REEXEC"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
        os.environ["REPRO_TUNE_NO_REEXEC"] = "1"
        os.execv(sys.executable,
                 [sys.executable, "-m", "repro.tune.sweep"] + sys.argv[1:])


def _dump_stats_json(stats: dict) -> None:
    """Machine-readable stats channel: when REPRO_SWEEP_STATS_JSON names a
    path, the (first) sweep's stats dict is written there — how the
    cross-process warm check (and CI) reads a child sweep's wall clock and
    disk hit counts without parsing log lines."""
    path = os.environ.get("REPRO_SWEEP_STATS_JSON")
    if not path:
        return
    payload = {k: v for k, v in stats.items() if k != "latency_hist"}
    Path(path).write_text(json.dumps(payload))


def _cross_process_warm_check(child_argv: Sequence[str],
                              cold_s: float) -> int:
    """The second half of ``--warm-check`` when a plan store is active:
    re-run this exact sweep in a FRESH python process against the populated
    plan dir.  The child must replay plans from disk (``plans.disk_hits``
    > 0) and report a sweep wall clock >= 30% below this process's cold
    run — proving the *disk* store and the persistent compilation cache,
    not the in-process cache, are what make a restart start warm."""
    import subprocess
    import tempfile

    argv = [a for a in child_argv if a != "--warm-check"]
    fd, stats_path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    env = dict(os.environ)
    env.pop("REPRO_TUNE_NO_REEXEC", None)
    env["REPRO_SWEEP_STATS_JSON"] = stats_path
    env[planstore.ENV_VAR] = str(planstore.plan_dir())
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.tune.sweep", *argv],
            capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            print("CROSS-PROCESS WARM-CHECK FAILED: child sweep exited "
                  f"{proc.returncode}\n{proc.stdout[-2000:]}"
                  f"\n{proc.stderr[-2000:]}", file=sys.stderr)
            return 5
        try:
            child = json.loads(Path(stats_path).read_text())
        except (OSError, ValueError):
            print("CROSS-PROCESS WARM-CHECK FAILED: child stats JSON "
                  "missing/unreadable", file=sys.stderr)
            return 5
    finally:
        try:
            os.unlink(stats_path)
        except OSError:
            pass
    warm_s = child.get("wall_s", float("inf"))
    disk_hits = child.get("disk_hits", 0)
    print(f"plan-store cross-process check: cold {cold_s:.1f}s -> "
          f"fresh-process warm {warm_s:.1f}s "
          f"({1.0 - warm_s / max(cold_s, 1e-9):.0%} lower), "
          f"{disk_hits} disk hits / {child.get('disk_misses', 0)} misses")
    if disk_hits <= 0:
        print("CROSS-PROCESS WARM-CHECK FAILED: the fresh process replayed "
              "zero plans from the disk store", file=sys.stderr)
        return 5
    if warm_s > 0.7 * cold_s:
        print("CROSS-PROCESS WARM-CHECK FAILED: fresh-process wall clock "
              "is not >= 30% lower than the cold run (disk store / "
              "compilation cache ineffective)", file=sys.stderr)
        return 5
    return 0


def main(argv: Sequence[str] | None = None) -> int:
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune.sweep",
        description="Measured CommConfig sweep -> TuneDB JSON.")
    ap.add_argument("--fast", action="store_true",
                    help="smoke sweep: corner configs, small sizes")
    ap.add_argument("--devices", type=int, default=8,
                    help="host CPU devices to force when single-device")
    ap.add_argument("--collectives", default=",".join(SWEEPABLE),
                    help=f"comma list from {SWEEPABLE}")
    ap.add_argument("--sizes", default=None,
                    help="comma list of message sizes in bytes, or a named "
                    f"set from {tuple(NAMED_SIZES)}")
    ap.add_argument("--max-configs", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help=f"TuneDB path (default {default_db_path()})")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit latmodel constants from the sweep and report")
    ap.add_argument("--prune", action="store_true",
                    help="model-guided pruning: skip configs the calibrated "
                    "Eq.1 model ranks more than --prune-ratio off the "
                    "predicted incumbent")
    ap.add_argument("--prune-ratio", type=float,
                    default=tune_prune.DEFAULT_RATIO)
    ap.add_argument("--assert-pruned", action="store_true",
                    help="exit non-zero unless the sweep measured strictly "
                    "fewer configs than the exhaustive candidate space "
                    "(CI guard for the pruning path)")
    ap.add_argument("--objective", choices=OBJECTIVES, default="latency",
                    help="ranking metric recorded by the sweep: bare "
                    "collective latency, or 'e2e' — additionally measure "
                    "each candidate inside its consumer loop (row_parallel "
                    "matmul+reduce, halo-fold step, MoE dispatch/combine) "
                    "and record TuneEntry.e2e_us for "
                    "select_config(objective='e2e')")
    ap.add_argument("--topology", default=None,
                    help="virtual torus placement, e.g. '4x4' or "
                    "'2x4:snake' (rows x cols must equal the device "
                    "count); multi-hop edges are physically routed "
                    "through intermediate ranks")
    ap.add_argument("--hop-distances", default=None,
                    help="comma list of torus hop distances to sweep the "
                    "hop-patterned collectives at (requires --topology); "
                    "each distance is recorded as TuneEntry.hops so "
                    "select_config(hops=...) answers per edge")
    ap.add_argument("--loss-rate", type=float, default=0.0,
                    help="sweep a lossy wire: seeded chunk-drop rate in "
                    "[0, 1) injected under every measurement; candidates "
                    "are forced to reliability=guaranteed and entries "
                    "record TuneEntry.loss so select_config(loss=...) can "
                    "prefer lossy-wire measurements")
    ap.add_argument("--plan-dir", default=None,
                    help="disk-backed CommPlan/program store directory "
                    "(also via REPRO_PLAN_DIR): plan schedules persist as "
                    "versioned JSON and traced programs through JAX's "
                    "persistent compilation cache, so a FRESH process "
                    "rerunning this sweep starts warm")
    ap.add_argument("--warm-check", action="store_true",
                    help="run the sweep twice in this process (cold, then "
                    "warm against the populated plan cache) and exit "
                    "non-zero unless the warm sweep's wall clock is at "
                    "least 30%% lower (plan-cache effectiveness guard); "
                    "with a plan dir active, additionally rerun the sweep "
                    "in a FRESH subprocess and require plans.disk_hits > 0 "
                    "plus the same 30%% wall-clock bar cross-process")
    args = ap.parse_args(argv)

    _ensure_devices(args.devices)
    import jax  # after XLA_FLAGS is settled

    if args.plan_dir:
        # Through the env so the re-exec above and the cross-process
        # warm-check child both inherit the same store.
        os.environ[planstore.ENV_VAR] = args.plan_dir
    store = planstore.active()
    if store is not None:
        print(f"plan store: {store.root} "
              f"({store.entry_count()} entries on disk)")
    if args.warm_check and store is not None and jax.default_backend() != "cpu":
        ap.error(f"--warm-check with a plan store reruns the sweep in a "
                 f"child process, which cannot reach the {jax.default_backend()}"
                 f" this process holds; run it with JAX_PLATFORMS=cpu")

    if args.sizes in NAMED_SIZES:
        sizes = NAMED_SIZES[args.sizes]
    else:
        try:
            sizes = ([int(s) for s in args.sizes.split(",")]
                     if args.sizes else None)
        except ValueError:
            ap.error(f"--sizes must be comma-separated integers or one of "
                     f"{tuple(NAMED_SIZES)}, got {args.sizes!r}")
    colls = [c.strip() for c in args.collectives.split(",") if c.strip()]
    unknown = [c for c in colls if c not in SWEEPABLE]
    if unknown:
        ap.error(f"unknown collective(s) {unknown}; sweepable: {SWEEPABLE}")
    topology = None
    if args.topology:
        try:
            topology = TorusSpec.parse(args.topology)
        except ValueError as e:
            ap.error(str(e))
        if topology.n_ranks != jax.device_count():
            ap.error(f"--topology {args.topology} places {topology.n_ranks} "
                     f"ranks but {jax.device_count()} devices are up "
                     f"(use --devices {topology.n_ranks})")
    hop_distances = None
    if args.hop_distances:
        if topology is None:
            ap.error("--hop-distances requires --topology")
        try:
            hop_distances = [int(d) for d in args.hop_distances.split(",")]
        except ValueError:
            ap.error(f"--hop-distances must be comma-separated integers, "
                     f"got {args.hop_distances!r}")

    db = TuneDB.load(args.out)
    stats: dict = {}
    kwargs = dict(collectives=colls, sizes=sizes, fast=args.fast,
                  max_configs=args.max_configs,
                  log=lambda s: print(s, flush=True),
                  prune=args.prune, prune_ratio=args.prune_ratio,
                  objective=args.objective,
                  topology=topology, hop_distances=hop_distances,
                  loss_rate=args.loss_rate)
    db = run_sweep(db=db, stats=stats, **kwargs)
    path = db.save(args.out)
    print(f"wrote {len(db)} entries -> {path}")
    print(sweep_summary(stats))
    _dump_stats_json(stats)

    if args.warm_check:
        warm_stats: dict = {}
        db = run_sweep(db=db, stats=warm_stats, **kwargs)
        db.save(args.out)
        print("warm " + sweep_summary(warm_stats))
        # Cold cost includes any calibration seeding: its compiles are part
        # of what the first run pays and may themselves warm the program
        # cache (a sendrecv sweep with --prune measures the seeded configs).
        # A warm run skipping work via cached programs/calibration is
        # exactly the claimed win; the hits guard below (not the wall
        # clock) is what catches a silently broken cache.
        cold_s = stats.get("wall_s", 0.0)
        warm_s = warm_stats.get("wall_s", 0.0)
        print(f"plan-cache warm check: cold {cold_s:.1f}s -> warm "
              f"{warm_s:.1f}s ({1.0 - warm_s / max(cold_s, 1e-9):.0%} lower)")
        if warm_stats.get("program_hits", 0) <= 0:
            print("WARM-CHECK FAILED: the warm sweep replayed zero cached "
                  "programs (plan cache broken?)", file=sys.stderr)
            return 4
        if warm_s > 0.7 * cold_s:
            print("WARM-CHECK FAILED: warm sweep wall clock is not >= 30% "
                  "lower than cold (plan cache ineffective)",
                  file=sys.stderr)
            return 4
        if planstore.active() is not None:
            rc = _cross_process_warm_check(raw_argv, cold_s)
            if rc:
                return rc

    if args.calibrate:
        from repro.tune.calibrate import calibrate_from_db, model_vs_measured
        result = calibrate_from_db(db)
        print(result.summary())
        for row in model_vs_measured(result, db):
            print("  " + row)
    if args.assert_pruned and stats.get("pruned", 0) <= 0:
        print("ASSERT-PRUNED FAILED: the calibrated model pruned zero "
              "candidates (the sweep measured the exhaustive space)",
              file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    raise SystemExit(main())
