"""Quickstart: ACCL-X collectives in 60 seconds.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/quickstart.py

Demonstrates the paper's configuration surface on an 8-device mesh:
streaming vs buffered point-to-point, ring all-reduce with int8 wire
compression, and the modeled latency difference (Eq. 1).
"""
import functools

import jax
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import (CommConfig, CommMode, Compression, Communicator,
                        Scheduling, V5E, collectives, latmodel)


def main():
    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))
    comm = Communicator.from_mesh(mesh, "x")
    print(f"mesh: {n} devices")

    x = np.random.RandomState(0).randn(n, 1024).astype(np.float32)

    # --- streaming vs buffered sendrecv --------------------------------
    for mode in (CommMode.STREAMING, CommMode.BUFFERED):
        cfg = CommConfig(mode=mode)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                           out_specs=P("x"))
        def ring(xs):
            return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]

        out = np.asarray(ring(x))
        ok = np.allclose(out, np.roll(x, 1, axis=0))
        lat = latmodel.pingping_latency(x[0].nbytes, cfg, V5E)
        print(f"{mode.value:10s} ring sendrecv ok={ok} "
              f"modeled latency {lat*1e6:.2f} us")

    # --- ring all-reduce with the compression plugin --------------------
    for compression in (Compression.NONE, Compression.INT8):
        cfg = CommConfig(algorithm="ring", compression=compression)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                           out_specs=P("x"))
        def allreduce(xs):
            return collectives.all_reduce(xs[0], comm, cfg)[None]

        out = np.asarray(allreduce(x))
        err = np.abs(out[0] - x.sum(0)).max() / np.abs(x.sum(0)).max()
        wire = latmodel.wire_bytes(x[0].nbytes, cfg)
        print(f"ring all-reduce compression={compression.value:5s} "
              f"rel_err={err:.2e} wire_bytes/msg={wire:.0f}")

    # --- host vs fused ("PL") scheduling (the paper's l_k) --------------
    from repro.core import scheduler
    lk = scheduler.measure_dispatch_overhead()
    print(f"measured host dispatch l_k = {lk*1e6:.1f} us "
          f"(paper: ~30 us through XRT; fused/PL: sub-us)")


if __name__ == "__main__":
    compile_cache.configure()
    main()
