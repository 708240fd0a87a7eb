"""Streaming (chunked, overlapped) communication engine.

The paper's *streaming* mode forwards message data into the consuming kernel
via AXI streams while the transfer is still in flight.  The TPU-native
equivalent: split the message into wire chunks and issue one
``collective-permute`` per chunk with **no serializing dependency** between
them — XLA's latency-hiding scheduler then runs chunk *i+1*'s DMA while the
consumer computes on chunk *i* (``collective-permute-start``/``-done`` pairs
in the compiled HLO).

Transport semantics (paper §3.4):

- **unordered** ("UDP"): all chunk permutes are independent → maximal overlap,
  but arrival order across messages is not defined; multi-source consumers
  must reorder (see the shallow-water halo's buffered receive).
- **ordered** ("TCP"): chunk *i* may only start once chunk *i - window* has
  been delivered (ack window).  Expressed as a data dependency through
  ``lax.optimization_barrier``; ``window`` is the TCP window-scaling analogue
  and ``chunk_bytes`` the jumbo-frame/MSS analogue.
"""
from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.config import CommConfig, CommMode, Compression, Transport
from repro.core import plans, plugins, reliable
from repro.obs import trace as obs_trace


def num_chunks(nbytes: int, cfg: CommConfig) -> int:
    return max(1, min(cfg.max_chunks, math.ceil(nbytes / cfg.chunk_bytes)))


def wire_permute(t: jnp.ndarray, axis_name: str, perm) -> jnp.ndarray:
    """One wire traversal of an (encoded) tensor: a plain edge list is a
    single ``ppermute``; a :class:`~repro.core.topology.RoutedPerm` (virtual
    multi-hop torus transport) executes each store-and-forward batch as
    sequential single-hop permutes — intermediate ranks forward, arrived
    messages hold via self-edges — and merges batches by destination mask
    (a pure select).  Values are bitwise-identical to the direct permute;
    only the number of physically executed hops differs.
    """
    from repro.core import topology
    if not isinstance(perm, topology.RoutedPerm):
        return lax.ppermute(t, axis_name, perm=list(perm))

    def run_batch(batch):
        out = t
        for rnd in batch.rounds:
            out = lax.ppermute(out, axis_name, perm=list(rnd))
        return out

    if len(perm.batches) == 1:
        return run_batch(perm.batches[0])
    idx = lax.axis_index(axis_name)
    acc = jnp.zeros_like(t)
    for batch in perm.batches:
        out = run_batch(batch)
        is_dst = jnp.zeros((), bool)
        for d in batch.dests:
            is_dst = jnp.logical_or(is_dst, idx == d)
        acc = jnp.where(is_dst, out, acc)
    return acc


def aligned_chunks(x: jnp.ndarray, cfg: CommConfig, align: int = 1
                   ) -> tuple[int, int]:
    """Wire-chunk geometry for streaming ``x``: (n_chunks, chunk_elems).

    ``chunk_elems`` is a multiple of ``align`` flat elements, so a wire chunk
    never splits a logical row of ``align`` elements — the recv_slot-aligned
    chunking that lets a halo consumer scatter-fold whole rows per chunk.
    Derived once per (shape, dtype, config, align) via the plan cache.
    """
    p = plans.chunk_plan(x.shape, x.dtype, cfg, align=align)
    return p.n_chunks, p.chunk_elems


def split_chunks(x: jnp.ndarray, n: int):
    """Flatten and split into n equal chunks (zero-padded). Returns
    (chunks[(n, L)], unsplit_fn)."""
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    shape, dtype = x.shape, x.dtype

    def unsplit(cs: jnp.ndarray) -> jnp.ndarray:
        return cs.reshape(-1)[:size].reshape(shape).astype(dtype)

    return chunks, unsplit


def _reliable_stream(rplan, chunks, perm, axis_name: str, cfg: CommConfig,
                     consume: Callable | None = None, init=None):
    """Execute a :class:`repro.core.reliable.DeliveryPlan`: one real wire
    round per slot, value-preserving.

    Every slot — original transmission, lost transmission, duplicate,
    backoff hold — runs a full ``wire_permute`` of its sequence's chunk, so
    recovery costs real permute rounds (the topology layer's hold-round
    idiom at wire granularity).  Only ``DELIVER`` slots land in the
    receiver's reassembly buffer; the wire output of every other slot is
    threaded through ``lax.optimization_barrier`` into the next slot's
    payload (or the final message), which (a) stops XLA dead-code-eliminating
    the unused permute and (b) serializes recovery after the fault it
    repairs.  Ordered transport chains slot *j* on slot *j - window*'s wire
    output — the ack window at slot granularity, covering retransmissions
    too.

    ``consume(carry, seq, chunk)`` is fired in sequence order via the
    reassembly flush: seq *i* is folded only once every seq ``<= i`` has
    been delivered, so a pipelined consumer's fold order — and therefore
    its float accumulation — is bitwise-identical under any wire reorder.

    Returns ``(carry, [chunk_0, ..., chunk_{n-1}])`` in sequence order.
    """
    reliable.record(rplan, cfg)
    ordered = cfg.transport == Transport.ORDERED
    received: dict = {}
    outs: list = []
    waste = None
    carry = init
    next_flush = 0
    for j, slot in enumerate(rplan.slots):
        payload = chunks[slot.seq]
        with obs_trace.scope("wire.slot", cat="wire", slot=j,
                             of=len(rplan.slots), seq=slot.seq,
                             action=slot.action, attempt=slot.attempt):
            deps = []
            if ordered and j >= cfg.window:
                deps.append(outs[j - cfg.window])
            if waste is not None:
                deps.append(waste)
                waste = None
            if deps:
                bar = lax.optimization_barrier((payload, *deps))
                payload = bar[0]
            enc, dec = plugins.wire_encode(payload, cfg)
            out = jax.tree.map(lambda t: wire_permute(t, axis_name, perm),
                               enc)
            outs.append(out)
            if slot.action == reliable.DELIVER:
                received[slot.seq] = dec(out)
            else:
                waste = out
        if consume is not None:
            while next_flush in received:
                carry = consume(carry, next_flush, received[next_flush])
                next_flush += 1
    if waste is not None:
        # A trailing non-delivered slot (e.g. a duplicate of the last chunk):
        # anchor its wire output on the final message so it survives DCE.
        last = max(received)
        merged = lax.optimization_barrier((received[last], waste))
        received[last] = merged[0]
    return carry, [received[i] for i in range(rplan.n_chunks)]


def chunked_permute(x: jnp.ndarray, perm: Sequence[tuple[int, int]],
                    axis_name: str, cfg: CommConfig) -> jnp.ndarray:
    """Streaming point-to-point transfer of ``x`` along ``perm``.

    One ppermute per wire chunk; chunks are independent (unordered) or chained
    with an ack window (ordered).  Wire format per the compression plugin.
    The chunk layout and ack-window structure replay from the plan cache.
    """
    plan = plans.chunk_plan(x.shape, x.dtype, cfg, equal_split=True)
    n = plan.n_chunks
    chunks, unsplit = split_chunks(x, n)
    rplan = reliable.plan_for(cfg, n)
    if rplan is not None:
        _, seq_chunks = _reliable_stream(rplan, chunks, perm, axis_name, cfg)
        return unsplit(jnp.stack(seq_chunks))
    received = []
    for i in range(n):
        payload = chunks[i]
        with obs_trace.scope("wire.chunk", cat="wire", chunk=i, of=n,
                             elems=int(payload.size),
                             acked=int(plan.ack_of[i])):
            if plan.ack_of[i] >= 0:
                # Ack chain: chunk i waits until chunk i-window was delivered.
                payload, _ = lax.optimization_barrier(
                    (payload, received[plan.ack_of[i]]))
            enc, dec = plugins.wire_encode(payload, cfg)
            out = jax.tree.map(lambda t: wire_permute(t, axis_name, perm),
                               enc)
            received.append(dec(out))
    return unsplit(jnp.stack(received))


def buffered_permute(x: jnp.ndarray, perm: Sequence[tuple[int, int]],
                     axis_name: str, cfg: CommConfig) -> jnp.ndarray:
    """Buffered transfer: one whole-message permute, then a staging copy.

    The ``optimization_barrier`` models the receive buffer in global memory —
    the consumer cannot observe any element until the *entire* message has
    landed (the paper's l_m staging-copy term, which also halves effective
    peak throughput to (1/bw_link + 1/bw_mem)^-1).
    """
    rplan = reliable.plan_for(cfg, 1)
    if rplan is not None:
        # Buffered = a one-chunk message: losing it on the wire costs a
        # whole-message retransmit (why small segments win lossy links).
        _, seq_chunks = _reliable_stream(rplan, [x], perm, axis_name, cfg)
        out = lax.optimization_barrier(seq_chunks[0])
        return out
    with obs_trace.scope("wire.message", cat="wire", elems=int(x.size)):
        enc, dec = plugins.wire_encode(x, cfg)
        out = jax.tree.map(lambda t: wire_permute(t, axis_name, perm), enc)
        out = lax.optimization_barrier(out)
        return dec(out)


def pipelined_consume(x: jnp.ndarray, perm: Sequence[tuple[int, int]],
                      axis_name: str, cfg: CommConfig,
                      consume: Callable, init, align: int = 1):
    """Stream ``x`` to the neighbor and fold ``consume`` over arriving wire
    chunks.

    ``consume(carry, chunk_index, chunk) -> carry`` runs on chunk *i* while
    chunk *i+1* is in flight — the paper's 'process incoming data before the
    transmission is complete'.  ``chunk`` is the decoded flat chunk
    (``chunk_elems`` elements; the tail chunk is zero-padded).  Chunk
    boundaries fall on multiples of ``align`` flat elements, so a consumer
    that folds logical rows of ``align`` elements (the halo's recv_slot rows)
    never sees a split row.  Ordered transport chains chunk *i* on the
    delivery of chunk *i - window* (the ack window), exactly like
    :func:`chunked_permute`.  Returns (carry, received_message).
    """
    plan = plans.chunk_plan(x.shape, x.dtype, cfg, align=align)
    n, chunk_elems = plan.n_chunks, plan.chunk_elems
    flat = x.reshape(-1)
    pad = n * chunk_elems - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, chunk_elems)
    rplan = reliable.plan_for(cfg, n)
    if rplan is not None:
        carry, seq_chunks = _reliable_stream(rplan, chunks, perm, axis_name,
                                             cfg, consume=consume, init=init)
        msg = (jnp.stack(seq_chunks).reshape(-1)[: x.size]
               .reshape(x.shape).astype(x.dtype))
        return carry, msg
    carry = init
    received = []
    for i in range(n):
        payload = chunks[i]
        with obs_trace.scope("wire.chunk", cat="wire", chunk=i, of=n,
                             elems=int(chunk_elems),
                             acked=int(plan.ack_of[i])):
            if plan.ack_of[i] >= 0:
                payload, _ = lax.optimization_barrier(
                    (payload, received[plan.ack_of[i]]))
            enc, dec = plugins.wire_encode(payload, cfg)
            out = jax.tree.map(lambda t: wire_permute(t, axis_name, perm),
                               enc)
            r = dec(out)
            received.append(r)
            carry = consume(carry, i, r)
    msg = jnp.stack(received).reshape(-1)[: x.size].reshape(x.shape).astype(x.dtype)
    return carry, msg


def double_buffered_exchange(payloads: Sequence[jnp.ndarray],
                             perms: Sequence[Sequence[tuple[int, int]]],
                             axis_name: str, cfg: CommConfig,
                             consume: Callable | None = None,
                             init=None,
                             chunk_consume: Callable | None = None,
                             chunk_align: int = 1):
    """Multi-round exchange through two alternating halo buffers.

    Round ``r`` lands in buffer ``r % 2``.  Under ordered transport the ack
    chain runs *within* a buffer (round ``r`` waits on round ``r - 2``), so
    the consumer can fold buffer A's message while buffer B's chunks are in
    flight — the double-buffering that lets the element update start before
    the whole exchange has completed.  Each round's transfer is
    :func:`pipelined_consume` (streaming) or :func:`buffered_permute`
    (buffered), so chunk-level pipelining still applies inside a round.

    Two consume granularities:

    - ``consume(carry, round_index, message) -> carry`` folds each round's
      reassembled message as soon as its buffer allows (e.g. scatter-add
      into the halo slots).
    - ``chunk_consume(carry, round_index, chunk_index, chunk) -> carry``
      folds each ``chunk_align``-aligned wire chunk *as it lands* (streaming
      rounds only): a single large neighbor message overlaps its own
      assembly instead of fencing the fold on the full round.  When given,
      it replaces ``consume`` for streaming rounds; buffered rounds (which
      have no wire chunks) still fold through ``consume``.

    Returns ``(carry, received)`` with ``received`` in round order; values
    are bitwise-identical to a serialized exchange — only the dependency
    structure differs.
    """
    from repro.core import topology
    bufs: tuple[list, list] = ([], [])
    carry = init
    received = []
    for r, (payload, perm) in enumerate(zip(payloads, perms)):
        buf = bufs[r % 2]
        hops = (perm.max_hops if isinstance(perm, topology.RoutedPerm)
                else 1)
        with obs_trace.scope("round", cat="collective", round=r, buf=r % 2,
                             hops=hops, elems=int(payload.size)):
            if cfg.transport == Transport.ORDERED and buf:
                # Per-buffer ack chain: no cross-buffer serialization.
                payload, _ = lax.optimization_barrier((payload, buf[-1]))
            if cfg.mode == CommMode.STREAMING:
                if chunk_consume is not None:
                    carry, msg = pipelined_consume(
                        payload, perm, axis_name, cfg,
                        lambda c, i, ch, _r=r: chunk_consume(c, _r, i, ch),
                        carry, align=chunk_align)
                else:
                    carry, msg = pipelined_consume(
                        payload, perm, axis_name, cfg,
                        lambda c, _i, _chunk: c, carry)
                    if consume is not None:
                        carry = consume(carry, r, msg)
            else:
                msg = buffered_permute(payload, perm, axis_name, cfg)
                if consume is not None:
                    carry = consume(carry, r, msg)
        buf.append(msg)
        received.append(msg)
    return carry, received


def overlapped_matmul_allreduce(h: jnp.ndarray, w: jnp.ndarray,
                                comm, cfg: CommConfig,
                                n_chunks: int | None = None) -> jnp.ndarray:
    """Row-parallel TP matmul with the reduction double-buffered against
    compute.

    ``h``: (tokens, ff_shard) activation shard; ``w``: (ff_shard, d) weight
    shard; result: (tokens, d) fully reduced.  ``comm`` is the caller's TP
    :class:`~repro.core.communicator.Communicator`, reused — not rebuilt —
    so ``torus_hops`` and hop-aware ``select_config`` describe the real
    topology of the TP axis (axis name(s) are still accepted and wrap a
    size-unknown communicator for backward compatibility).

    Token rows are split into wire chunks; each chunk's psum is independent
    of the next chunk's matmul, so the scheduler overlaps collective *i*
    with compute *i+1* (streaming TP).  Under ordered transport the chunks
    form a two-deep ack chain — chunk *i*'s matmul waits on the delivery of
    reduce *i − 2*, the per-layer double buffering of the TP reduce — never
    on the whole history.  With ``n_chunks=1`` this degrades to the
    buffered (sequential) pattern.  Equal to the fused matmul + all-reduce
    up to f32 rounding: the barriers change nothing, but XLA may sum a dot
    over a block of rows in another order than over the whole matrix.
    """
    tokens = h.shape[0]
    if n_chunks is None:
        # Derive the chunk geometry through the plan cache (align = output
        # row width, so a chunk never splits a token row): repeated per-layer
        # combines of the same shape replay one cached ChunkPlan.
        p = plans.chunk_plan((tokens, w.shape[1]), jnp.float32, cfg,
                             align=w.shape[1])
        n_chunks = p.n_chunks
    n_chunks = max(1, min(n_chunks, tokens))
    while tokens % n_chunks:
        n_chunks -= 1
    import dataclasses as _dc
    from repro.core import collectives
    from repro.core.communicator import Communicator
    if not isinstance(comm, Communicator):
        axes = (comm,) if isinstance(comm, str) else tuple(comm)
        comm = Communicator(axes, (1,) * len(axes))
    # The chunked overlap IS the streaming mechanism here; the per-chunk
    # combine itself uses the native collective.
    cfg_native = _dc.replace(
        cfg, algorithm="native",
        compression=(Compression.NONE if cfg.compression == Compression.INT8
                     else cfg.compression))
    parts: list[jnp.ndarray] = []
    rows = tokens // n_chunks
    for i in range(n_chunks):
        hc = lax.dynamic_slice_in_dim(h, i * rows, rows, axis=0)
        if cfg.transport == Transport.ORDERED and i >= 2:
            # Double-buffered ack chain: two reduce buffers alternate; the
            # next chunk's compute waits only on its own buffer's delivery.
            hc, _ = lax.optimization_barrier((hc, parts[i - 2]))
        partial = jnp.dot(hc, w, preferred_element_type=jnp.float32)
        parts.append(collectives.all_reduce(partial, comm, cfg_native))
    return jnp.concatenate(parts, axis=0).astype(h.dtype)


def chunked_all_to_all(x: jnp.ndarray, comm, cfg: CommConfig,
                       split_axis: int = 0, concat_axis: int = 0) -> jnp.ndarray:
    """Streaming all-to-all (MoE dispatch/combine): tile a non-exchanged
    axis into wire chunks, one ``lax.all_to_all`` per chunk.

    Chunk *i*'s exchange carries no data dependency on chunk *i+1*'s
    (unordered transport), so the latency-hiding scheduler overlaps the
    chunks' transfers with each other and with the consumer's per-chunk
    work; ordered transport chains chunk *i* on chunk *i − window* (ack
    window).  Values are bitwise-identical to the single fused all-to-all —
    tiling a non-split axis only partitions pure data movement.  Falls back
    to one call when no tileable axis exists (1-D payloads) or the message
    fits a single chunk.
    """
    axis_names = comm.axis_names if hasattr(comm, "axis_names") else comm

    def one(t: jnp.ndarray) -> jnp.ndarray:
        if cfg.compression != Compression.NONE and cfg.enable_compression_plugin:
            orig = t.dtype
            y = lax.all_to_all(t.astype(jnp.bfloat16), axis_names,
                               split_axis=split_axis, concat_axis=concat_axis,
                               tiled=True)
            return y.astype(orig)
        return lax.all_to_all(t, axis_names, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    tile_axis = next((a for a in range(x.ndim - 1, -1, -1)
                      if a not in (split_axis % x.ndim, concat_axis % x.ndim)),
                     None)
    if tile_axis is None:
        return one(x)
    n = min(num_chunks(x.size * x.dtype.itemsize, cfg), x.shape[tile_axis])
    if n <= 1:
        return one(x)
    dim = x.shape[tile_axis]
    width = math.ceil(dim / n)
    outs: list[jnp.ndarray] = []
    for i, start in enumerate(range(0, dim, width)):
        sl = lax.slice_in_dim(x, start, min(start + width, dim), axis=tile_axis)
        if cfg.transport == Transport.ORDERED and i >= cfg.window:
            sl, _ = lax.optimization_barrier((sl, outs[i - cfg.window]))
        outs.append(one(sl))
    return jnp.concatenate(outs, axis=tile_axis)
