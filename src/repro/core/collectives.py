"""ACCL-X collectives — MPI-like operations over mesh axes.

Two algorithm families, selected by ``CommConfig.algorithm``:

- ``native`` — XLA built-ins (``psum``/``all_gather``/``psum_scatter``/
  ``all_to_all``).  Fastest path when no wire-format control is needed.
- ``ring``   — explicit ``ppermute`` ring algorithms (the CCLO analogue).
  Required for wire compression (int8/bf16 payloads) and for transport/window
  experiments, because XLA built-ins cannot carry a custom wire format.

All functions are SPMD: call them inside ``shard_map`` with the communicator's
axes in scope.  Point-to-point ops take explicit (src, dst) edge lists, as the
shallow-water halo exchange does (paper §4.1).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.communicator import Communicator
from repro.core.config import (CommConfig, CommMode, Compression, Scheduling,
                               Transport)
from repro.core import plans, plugins, streaming, topology
from repro.obs import metrics as obs_metrics, trace as obs_trace


def _nbytes(x) -> int:
    """Static per-rank byte count of a (possibly traced) payload."""
    try:
        return int(x.size) * int(x.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _record_edges(comm: Communicator, perm, nbytes: int) -> None:
    """Per-edge byte accounting: every edge moves ``nbytes``, counted under
    its torus hop distance (the per-edge axis of the paper's Fig. 9).
    Counted when the exchange is traced, so once per compilation, not once
    per execution."""
    reg = obs_metrics.registry()
    for s, d in perm:
        reg.counter("comm.edge_bytes",
                    hops=comm.torus_hops(int(s), int(d))).inc(nbytes)


def resolve_config(cfg, collective: str = "all_reduce",
                   msg_bytes: int = 1 << 20, mesh=None,
                   db_path=None, hops: int | None = None,
                   objective: str = "latency",
                   torus: str | None = None,
                   consumer: str | None = None) -> CommConfig:
    """Resolve a ``CommConfig | "auto" | None`` to a concrete config.

    ``"auto"`` asks the autotuner (:func:`repro.tune.select_config`) for the
    fastest *measured* config for this collective/size/topology, falling back
    to ``OPTIMIZED_CONFIG`` on a cold cache.  ``hops`` is the worst-case torus
    hop distance of the communication pattern (``Communicator.torus_hops``) —
    multi-hop edges prefer configs measured at the same distance (the paper's
    direct-link vs Ethernet-switch distinction).  ``objective="e2e"`` ranks
    by the measured consumer-loop time instead of bare collective latency
    (§5: what wins the microbench is not what scales the application);
    ``consumer`` names which consumer loop's measurements to prefer
    ("decode_step" vs "prefill" vs "row_parallel" — serving's phases
    resolve different configs from the same TuneDB).
    Host-side only — call it before tracing, never inside ``shard_map``.
    """
    if isinstance(cfg, CommConfig):
        return cfg
    if cfg is None or cfg == "auto":
        from repro.tune import select_config
        return select_config(collective, msg_bytes, mesh=mesh, path=db_path,
                             hops=hops, objective=objective, torus=torus,
                             consumer=consumer)
    raise TypeError(f"comm config must be CommConfig or 'auto', got {cfg!r}")


# ----------------------------------------------------------------------
# Point-to-point
# ----------------------------------------------------------------------

def sendrecv(x: jnp.ndarray, perm: Sequence[tuple[int, int]],
             comm: Communicator, cfg: CommConfig) -> jnp.ndarray:
    """Single send/recv along an edge list (each rank sends at most once).

    On a communicator placed on a virtual torus
    (:class:`~repro.core.topology.TorusSpec`) every multi-hop edge is routed:
    the transfer physically executes one single-hop permute per torus hop
    (store-and-forward through the intermediate ranks), value-identical to
    the direct permute.
    """
    perm = plans.validated_perm(comm, perm)
    nbytes = _nbytes(x)
    hops = comm.max_hops(perm)
    _record_edges(comm, perm, nbytes)
    perm = topology.routed_perm(comm, perm)
    with obs_trace.scope("sendrecv", cat="collective", nbytes=nbytes,
                         hops=hops, edges=len(perm.edges)
                         if isinstance(perm, topology.RoutedPerm)
                         else len(perm),
                         mode=cfg.mode, transport=cfg.transport,
                         scheduling=cfg.scheduling,
                         reliability=cfg.reliability):
        if cfg.mode == CommMode.STREAMING:
            return streaming.chunked_permute(x, perm, comm.axis, cfg)
        return streaming.buffered_permute(x, perm, comm.axis, cfg)


def edge_color_rounds(edges: Sequence[tuple[int, int]]):
    """Greedily color a multi-neighbor exchange into ppermute-able rounds.

    Each round is a valid permutation fragment: every rank appears at most
    once as source and once as destination.  The number of rounds is the
    N_max of Eq. 3 — each neighbor costs one more scheduled command.
    Derived once per edge list and replayed from the plan cache.
    """
    return plans.edge_rounds(edges)


def multi_neighbor_exchange(payloads: Sequence[jnp.ndarray],
                            rounds: Sequence[Sequence[tuple[int, int]]],
                            comm: Communicator, cfg,
                            consume=None, init=None,
                            chunk_consume=None, chunk_align: int = 1):
    """Halo exchange with several neighbors: one sendrecv per round.

    ``payloads[r]`` is this rank's message for round ``r`` (ranks not sending
    in a round pass a dummy of the same shape).  Unordered transport leaves
    rounds independent (they overlap); ordered transport chains them.
    Overlapped scheduling routes through the double-buffered engine: rounds
    alternate between two buffers and the ordered ack chain runs per buffer,
    so a consumer can fold one buffer while the other is in flight.

    ``cfg`` may be a sequence of per-round configs (the SWE driver's
    per-edge hop-aware selection: each round's edges share a hop distance
    and get the config tuned for it).  Per-round configs apply to the
    serially scheduled path; the double-buffered overlapped engine pipelines
    all rounds as one schedule and requires a uniform config.

    Overlapped scheduling additionally accepts the engine's consume hooks:
    ``consume(carry, round, message)`` folds whole rounds, and
    ``chunk_consume(carry, round, chunk_index, chunk)`` folds each
    ``chunk_align``-aligned wire chunk as it lands (chunk-level halo
    consume — see :func:`repro.core.streaming.double_buffered_exchange`).
    When either hook is given the return value is ``(carry, received)``;
    otherwise just ``received`` (round order).
    """
    round_cfgs = None
    if not isinstance(cfg, CommConfig):
        round_cfgs = list(cfg)
        if len(round_cfgs) != len(rounds):
            raise ValueError(f"{len(round_cfgs)} per-round configs for "
                             f"{len(rounds)} rounds")
        # Degenerate empty pattern: behave like the uniform-config call
        # (no rounds means no config is ever consulted).
        cfg = round_cfgs[0] if round_cfgs else CommConfig()
    exchange_scope = obs_trace.scope(
        "multi_neighbor", cat="collective", rounds=len(rounds),
        hops=comm.max_hops([e for r in rounds for e in r]),
        nbytes=_nbytes(payloads[0]) if payloads else 0,
        mode=cfg.mode, transport=cfg.transport, scheduling=cfg.scheduling,
        reliability=cfg.reliability)
    if cfg.scheduling == Scheduling.OVERLAPPED:
        if round_cfgs is not None and any(c != cfg for c in round_cfgs):
            raise ValueError(
                "per-round configs require serial scheduling; the "
                "double-buffered overlapped engine pipelines all rounds "
                "under one config")
        # One CommPlan per (pattern, config, payload): the round structure is
        # validated once and replayed, and the chunk/ack layout it caches is
        # what pipelined_consume replays per round.
        if payloads:
            plan = plans.get_plan("multi_neighbor", comm, cfg,
                                  payloads[0].shape, payloads[0].dtype,
                                  align=chunk_align, rounds=rounds)
            rounds = list(plan.perms)
        else:
            # no payload to key a plan on, but malformed rounds must still
            # be rejected, as they always were
            rounds = [plans.validated_perm(comm, perm) for perm in rounds]
        # Virtual-torus lowering happens per round inside the engine so the
        # double-buffered ack chain still runs per buffer.
        rounds = [topology.routed_perm(comm, perm) for perm in rounds]
        with exchange_scope:
            carry, received = streaming.double_buffered_exchange(
                payloads, rounds, comm.axis, cfg, consume=consume, init=init,
                chunk_consume=chunk_consume, chunk_align=chunk_align)
        if consume is not None or chunk_consume is not None:
            return carry, received
        return received
    received = []
    prev = None
    with exchange_scope:
        for r, (payload, perm) in enumerate(zip(payloads, rounds)):
            rcfg = round_cfgs[r] if round_cfgs is not None else cfg
            if rcfg.transport == Transport.ORDERED and prev is not None:
                payload, _ = lax.optimization_barrier((payload, prev))
            out = sendrecv(payload, perm, comm, rcfg)
            received.append(out)
            prev = out
    return received


# ----------------------------------------------------------------------
# Ring collectives (explicit ppermute algorithms; support wire compression)
# ----------------------------------------------------------------------

def _ring_send(payload: jnp.ndarray, comm: Communicator, cfg: CommConfig) -> jnp.ndarray:
    """One ring hop with wire encoding.  On a virtual torus the rank ring's
    multi-hop edges (e.g. row-major wraps) are routed through the fabric —
    place ranks with ``topology.snake_placement`` for an all-hop-1 ring."""
    enc, dec = plugins.wire_encode(payload, cfg)
    perm = topology.routed_perm(comm, comm.ring_perm())
    out = jax.tree.map(
        lambda t: streaming.wire_permute(t, comm.axis, perm), enc)
    return dec(out)


def ring_all_reduce(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
                    op: str = "sum") -> jnp.ndarray:
    """Ring all-reduce = reduce-scatter phase + all-gather phase.

    2·(n−1) ppermute steps moving 2·(n−1)/n of the data per rank — the
    bandwidth-optimal schedule ACCL's CCLO implements.  With int8 wire format
    the bytes-on-wire shrink 4x (compression plugin).
    """
    n = comm.size
    if n == 1:
        return x
    reducer = plugins.reduce_op(op, cfg)
    d = comm.rank()
    flat = x.reshape(-1)
    orig_size = flat.shape[0]
    pad = (-orig_size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    acc = flat.reshape(n, -1)
    if acc.dtype in (jnp.bfloat16, jnp.float16):
        acc = acc.astype(jnp.float32)

    # Phase 1: reduce-scatter. After n-1 steps rank d holds the fully reduced
    # segment (d+1) mod n.
    for t in range(n - 1):
        send_idx = (d - t) % n
        payload = jnp.take(acc, send_idx, axis=0)
        recvd = _ring_send(payload, comm, cfg)
        recv_idx = (d - 1 - t) % n
        updated = reducer(jnp.take(acc, recv_idx, axis=0), recvd)
        acc = lax.dynamic_update_index_in_dim(acc, updated, recv_idx, axis=0)

    my_idx = (d + 1) % n
    cur = jnp.take(acc, my_idx, axis=0)
    out = jnp.zeros_like(acc)
    out = lax.dynamic_update_index_in_dim(out, cur, my_idx, axis=0)

    # Phase 2: all-gather the reduced segments around the ring.
    for t in range(n - 1):
        recvd = _ring_send(cur, comm, cfg)
        idx = (d - t) % n
        out = lax.dynamic_update_index_in_dim(out, recvd, idx, axis=0)
        cur = recvd

    return out.reshape(-1)[:orig_size].reshape(x.shape).astype(x.dtype)


def ring_all_gather(x: jnp.ndarray, comm: Communicator, cfg: CommConfig) -> jnp.ndarray:
    """Ring all-gather; returns (n, *x.shape) stacked by source rank."""
    n = comm.size
    if n == 1:
        return x[None]
    d = comm.rank()
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, d, axis=0)
    cur = x
    for t in range(n - 1):
        recvd = _ring_send(cur, comm, cfg)
        idx = (d - 1 - t) % n
        out = lax.dynamic_update_index_in_dim(out, recvd, idx, axis=0)
        cur = recvd
    return out


def ring_reduce_scatter(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
                        op: str = "sum") -> jnp.ndarray:
    """Reduce-scatter over leading dim (must divide by comm.size)."""
    n = comm.size
    if n == 1:
        return x
    assert x.shape[0] % n == 0, f"leading dim {x.shape[0]} not divisible by {n}"
    reducer = plugins.reduce_op(op, cfg)
    d = comm.rank()
    acc = x.reshape(n, x.shape[0] // n, *x.shape[1:])
    if acc.dtype in (jnp.bfloat16, jnp.float16):
        acc = acc.astype(jnp.float32)
    # Ring offset chosen so rank d finishes holding fully reduced segment d.
    for t in range(n - 1):
        send_idx = (d - t - 1) % n
        payload = jnp.take(acc, send_idx, axis=0)
        recvd = _ring_send(payload, comm, cfg)
        recv_idx = (d - t - 2) % n
        updated = reducer(jnp.take(acc, recv_idx, axis=0), recvd)
        acc = lax.dynamic_update_index_in_dim(acc, updated, recv_idx, axis=0)
    return jnp.take(acc, d, axis=0).astype(x.dtype)


# ----------------------------------------------------------------------
# Dispatching wrappers
# ----------------------------------------------------------------------

def _all_reduce_sum_fwd(x, comm: Communicator, cfg: CommConfig):
    if cfg.algorithm == "ring" and comm.single_axis and comm.size > 1:
        return ring_all_reduce(x, comm, cfg, "sum")
    if cfg.compression == Compression.BF16:
        enc, dec = plugins.wire_encode(x, cfg)
        return dec(lax.psum(enc, comm.axis_names))
    return lax.psum(x, comm.axis_names)


def all_reduce(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
               op: str = "sum") -> jnp.ndarray:
    """All-reduce with *replicated-output* gradient semantics.

    This framework maintains replication invariants manually (the Megatron
    f/g operator scheme): the output of a forward all-reduce is replicated,
    so its true VJP is the identity — every rank's cotangent already equals
    the logical cotangent.  shard_map's default transpose (psum again, or the
    ring algorithm's permute chain) would compound a tp× factor per combine.
    """
    with obs_trace.scope("all_reduce", cat="collective", op=op,
                         nbytes=_nbytes(x), algorithm=cfg.algorithm,
                         mode=cfg.mode, transport=cfg.transport,
                         scheduling=cfg.scheduling,
                         reliability=cfg.reliability,
                         hops=comm.max_hops(comm.ring_perm())
                         if cfg.algorithm == "ring" and comm.single_axis
                         else 1):
        if op == "sum":
            @jax.custom_vjp
            def f(v):
                return _all_reduce_sum_fwd(v, comm, cfg)

            def fwd(v):
                return _all_reduce_sum_fwd(v, comm, cfg), None

            def bwd(_, ct):
                return (ct,)

            f.defvjp(fwd, bwd)
            return f(x)
        if cfg.algorithm == "ring" and comm.single_axis:
            return ring_all_reduce(x, comm, cfg, op)
        if op == "max":
            return lax.pmax(x, comm.axis_names)
        if op == "min":
            return lax.pmin(x, comm.axis_names)
        raise ValueError(f"native all_reduce does not support op={op}")


def all_gather(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
               axis: int = 0, tiled: bool = True) -> jnp.ndarray:
    with obs_trace.scope("all_gather", cat="collective", nbytes=_nbytes(x),
                         algorithm=cfg.algorithm, mode=cfg.mode,
                         transport=cfg.transport, scheduling=cfg.scheduling,
                         reliability=cfg.reliability):
        if cfg.algorithm == "ring" and comm.single_axis:
            stacked = ring_all_gather(x, comm, cfg)
            if not tiled:
                return stacked
            n = comm.size
            parts = [jnp.take(stacked, i, axis=0) for i in range(n)]
            return jnp.concatenate(parts, axis=axis)
        return lax.all_gather(x, comm.axis_names, axis=axis, tiled=tiled)


def reduce_scatter(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
                   op: str = "sum") -> jnp.ndarray:
    with obs_trace.scope("reduce_scatter", cat="collective",
                         nbytes=_nbytes(x), algorithm=cfg.algorithm,
                         mode=cfg.mode, transport=cfg.transport,
                         scheduling=cfg.scheduling,
                         reliability=cfg.reliability):
        if cfg.algorithm == "ring" and comm.single_axis:
            return ring_reduce_scatter(x, comm, cfg, op)
        assert op == "sum"
        return lax.psum_scatter(x, comm.axis_names, scatter_dimension=0,
                                tiled=True)


def all_to_all(x: jnp.ndarray, comm: Communicator, cfg: CommConfig,
               split_axis: int = 0, concat_axis: int = 0) -> jnp.ndarray:
    """All-to-all (MoE dispatch). Wire compression via bf16 cast if enabled.

    Overlapped scheduling with streaming delivery tiles the message into
    independent wire chunks (:func:`repro.core.streaming.chunked_all_to_all`)
    so the dispatch/combine overlaps its own transfer — bitwise-identical
    to the fused op.
    """
    with obs_trace.scope("all_to_all", cat="collective", nbytes=_nbytes(x),
                         mode=cfg.mode, transport=cfg.transport,
                         scheduling=cfg.scheduling,
                         reliability=cfg.reliability):
        if (cfg.scheduling == Scheduling.OVERLAPPED
                and cfg.mode == CommMode.STREAMING):
            return streaming.chunked_all_to_all(x, comm, cfg, split_axis,
                                                concat_axis)
        if (cfg.compression != Compression.NONE
                and cfg.enable_compression_plugin):
            orig = x.dtype
            y = lax.all_to_all(x.astype(jnp.bfloat16), comm.axis_names,
                               split_axis=split_axis, concat_axis=concat_axis,
                               tiled=True)
            return y.astype(orig)
        return lax.all_to_all(x, comm.axis_names, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def broadcast(x: jnp.ndarray, root: int, comm: Communicator,
              cfg: CommConfig) -> jnp.ndarray:
    """Broadcast from ``root`` (one-to-all)."""
    d = comm.rank()
    masked = jnp.where(d == root, x, jnp.zeros_like(x))
    return all_reduce(masked, comm, cfg, op="sum")


def hierarchical_all_reduce(x: jnp.ndarray, inner: Communicator,
                            outer: Communicator, cfg: CommConfig) -> jnp.ndarray:
    """Cross-pod all-reduce: RS in-pod (ICI) → AR across pods (DCN) → AG in-pod.

    Moves 1/n_inner of the data over the slow outer links — the torus version
    of the paper's switch-topology tuning.  Requires leading dim divisible by
    the inner size; falls back to flat psum otherwise.
    """
    with obs_trace.scope("hierarchical_all_reduce", cat="collective",
                         nbytes=_nbytes(x), inner=inner.size,
                         outer=outer.size, mode=cfg.mode,
                         transport=cfg.transport,
                         scheduling=cfg.scheduling,
                         reliability=cfg.reliability):
        flat = x.reshape(-1)
        n = inner.size
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        seg = reduce_scatter(flat, inner, cfg)
        seg = all_reduce(seg, outer, cfg)
        full = all_gather(seg, inner, cfg, axis=0, tiled=True)
        return full[: x.size].reshape(x.shape)
