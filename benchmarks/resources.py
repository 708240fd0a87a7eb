"""Fig. 3 reproduction: 'resource utilization' of the comm stack per config.

FPGA LUT/FF/DSP → TPU analogues: HLO op count, collective op count,
generated-code bytes and temp (live-buffer) bytes of a fixed gradient
all-reduce program, per ACCL-X build:

  full      ring + compression + arithmetic plugins
  minimal   plugins compiled out (native psum)
  tcp_opt   ordered transport, window scaling, jumbo chunks
  udp       unordered transport
"""
from __future__ import annotations

import numpy as np


def run():
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core import collectives
    from repro.core.communicator import Communicator
    from repro.core.config import (CommConfig, CommMode, Compression,
                                   Transport)

    if jax.device_count() < 2:
        return [("fig3", 0.0, "skipped_1device")]

    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))
    comm = Communicator.from_mesh(mesh, "x")
    builds = {
        "full_int8ring": CommConfig(algorithm="ring",
                                    compression=Compression.INT8),
        "full_ring": CommConfig(algorithm="ring"),
        "minimal": CommConfig(enable_compression_plugin=False,
                              enable_arithmetic_plugin=False),
        "tcp_opt": CommConfig(mode=CommMode.STREAMING,
                              transport=Transport.ORDERED, window=8,
                              chunk_bytes=1 << 20),
        "udp": CommConfig(mode=CommMode.STREAMING,
                          transport=Transport.UNORDERED),
    }
    x = jnp.zeros((n, 1 << 16), jnp.float32)
    rows = []
    for name, cfg in builds.items():
        @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
        def f(xs):
            return collectives.all_reduce(xs[0], comm, cfg)[None]

        lowered = jax.jit(f).lower(x)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        ops_total = hlo.count(" = ")
        colls = sum(hlo.count(k) for k in
                    ("all-reduce", "collective-permute", "all-gather",
                     "reduce-scatter"))
        rows.append((f"fig3_{name}_hlo_ops", float(ops_total),
                     f"colls{colls}"))
        rows.append((f"fig3_{name}_code_bytes",
                     float(mem.generated_code_size_in_bytes), ""))
        rows.append((f"fig3_{name}_temp_bytes",
                     float(mem.temp_size_in_bytes), ""))
    return rows
