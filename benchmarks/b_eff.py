"""Fig. 4 reproduction: b_eff ping-ping latency/throughput over message size.

Modeled latencies (Eq. 1 with TPU constants) for every communication
approach, plus two measured calibrations on this host:
  - l_k (host dispatch) via scheduler.measure_dispatch_overhead — the 30 µs
    XRT analogue;
  - relative fused-vs-host-scheduled wall time of a real 8-device ring
    exchange (CPU devices; the RATIO is the meaningful number).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import latmodel, scheduler
from repro.core.config import (CommConfig, CommMode, Scheduling, Transport,
                               V5E)

SIZES = [64, 256, 1024, 4096, 16384, 65536, 262144, 1048576, 4194304]

CONFIGS = {
    "buffered_host": CommConfig(mode=CommMode.BUFFERED,
                                scheduling=Scheduling.HOST),
    "buffered_pl": CommConfig(mode=CommMode.BUFFERED,
                              scheduling=Scheduling.FUSED),
    "streaming_host": CommConfig(mode=CommMode.STREAMING,
                                 scheduling=Scheduling.HOST),
    "streaming_pl": CommConfig(mode=CommMode.STREAMING,
                               scheduling=Scheduling.FUSED),
}


def modeled_rows():
    rows = []
    for name, cfg in CONFIGS.items():
        for hops, suffix in ((1, ""), (3, "_ES")):   # ES = via-switch analogue
            for size in SIZES:
                lat = latmodel.pingping_latency(size, cfg, V5E, hops=hops)
                bw = size / lat
                rows.append((f"beff_{name}{suffix}_{size}B",
                             lat * 1e6, f"{bw/1e9:.3f}GB/s"))
    rows.append(("beff_buffered_peak_bw", 0.0,
                 f"{latmodel.buffered_peak_bw(V5E)/1e9:.2f}GB/s"))
    return rows


def measured_rows():
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core.communicator import Communicator

    rows = []
    lk = scheduler.measure_dispatch_overhead()
    rows.append(("beff_measured_dispatch_lk", lk * 1e6, "host_l_k"))

    if jax.device_count() < 2:
        rows.append(("beff_measured_ring", 0.0, "skipped_1device"))
        return rows

    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))
    comm = Communicator.from_mesh(mesh, "x")
    from repro.core import collectives
    cfg = CommConfig()
    x = jnp.zeros((n, 1 << 14), jnp.float32)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def ring_once(xs):
        return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]

    # fused: K exchanges inside ONE program
    def many(xs, k=20):
        for _ in range(k):
            xs = ring_once(xs)
        return xs

    fused = jax.jit(many)
    x = jax.block_until_ready(fused(x))
    t0 = time.perf_counter()
    for _ in range(5):
        x = fused(x)
    jax.block_until_ready(x)
    fused_t = (time.perf_counter() - t0) / (5 * 20)

    single = jax.jit(ring_once)
    x = jax.block_until_ready(single(x))
    t0 = time.perf_counter()
    for _ in range(100):
        x = jax.block_until_ready(single(x))
    host_t = (time.perf_counter() - t0) / 100

    rows.append(("beff_measured_ring_fused", fused_t * 1e6, "per_exchange"))
    rows.append(("beff_measured_ring_hostsched", host_t * 1e6, "per_exchange"))
    rows.append(("beff_measured_sched_speedup", 0.0,
                 f"{host_t/fused_t:.2f}x"))
    return rows


def run():
    return modeled_rows() + measured_rows()
