"""Reliable-transport rows: what chunk-level loss recovery costs.

The reliable wire's contract has two quantitative halves.  First, the
zero-fault fast path is free: a GUARANTEED config on a clean wire compiles
the exact same program as BEST_EFFORT (``plan_for`` returns None), so
``rt_guaranteed_overhead`` should sit at ~1.0x.  Second, recovery has a
real latency price: injected chunk loss adds retransmit / timeout-hold /
backoff permute rounds to the traced program, and the ``rt_loss*`` rows
measure that price at the paper's TCP-vs-UDP knob settings.

- ``rt_clean_us``            — best-effort chunked ring permute, clean wire;
- ``rt_guaranteed_clean_us`` — same message, GUARANTEED, clean wire (the
  fast path: must not pay for reliability it never uses);
- ``rt_loss1_us``            — GUARANTEED under 1% injected chunk loss;
- ``rt_loss5_us``            — GUARANTEED under 5% injected chunk loss;
- ``rt_guaranteed_overhead`` — guaranteed-clean / clean ratio (non-latency:
  ~1.0 is the contract);
- ``rt_loss5_penalty``       — loss5 / clean ratio (non-latency: the
  recovery rounds' cost, bigger = more expensive wire).

Loss rows pin the first transmission dropped (the injector's own
determinism rule): a single traced message at a low seeded rate would
usually draw no faults at all, and a row that sometimes measures the clean
program is noise, not data.  Rows ride report-only until a second
committed baseline lands.
"""
from __future__ import annotations

import time


def _time_permute(cfg, faults, x, mesh, perm, reps=30):
    import jax
    import numpy as np
    from repro.core import reliable, streaming

    spec = jax.sharding.PartitionSpec("x")
    body = lambda v: streaming.chunked_permute(v[0], perm, "x", cfg)[None]
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False))
    with reliable.inject(faults):
        jax.block_until_ready(f(x))          # trace bakes recovery rounds in
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    import jax
    if jax.device_count() < 4:
        return [("rt", 0.0, "skipped_lt4devices")]
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core import reliable
    from repro.core.config import (CommConfig, CommMode, Reliability,
                                   Scheduling, Transport)

    n = 4
    mesh = make_mesh((n,), ("x",))
    perm = [(i, (i + 1) % n) for i in range(n)]
    N = 16 * 256                              # 16 x 1 KiB wire chunks
    x = jnp.arange(n * N, dtype=jnp.float32).reshape(n, N) * 0.5 + 1.0

    def cfg(reliability):
        return CommConfig(mode=CommMode.STREAMING,
                          scheduling=Scheduling.OVERLAPPED,
                          transport=Transport.UNORDERED, window=4,
                          chunk_bytes=1024, reliability=reliability,
                          ack_timeout=2, max_retransmits=4,
                          backoff_base=1, backoff_cap=4)

    def lossy(rate):
        return reliable.WireFaults(seed=11, drop=rate,
                                   drop_events=frozenset({(0, 0, 0)}))

    clean_s = _time_permute(cfg(Reliability.BEST_EFFORT), None, x, mesh, perm)
    guar_s = _time_permute(cfg(Reliability.GUARANTEED), None, x, mesh, perm)
    loss1_s = _time_permute(cfg(Reliability.GUARANTEED), lossy(0.01),
                            x, mesh, perm)
    loss5_s = _time_permute(cfg(Reliability.GUARANTEED), lossy(0.05),
                            x, mesh, perm)

    chunks = "16chunks_1KiB"
    return [
        ("rt_clean_us", clean_s * 1e6, f"best_effort_{chunks}"),
        ("rt_guaranteed_clean_us", guar_s * 1e6, f"fast_path_{chunks}"),
        ("rt_loss1_us", loss1_s * 1e6, "drop1pct_pinned_first_loss"),
        ("rt_loss5_us", loss5_s * 1e6, "drop5pct_pinned_first_loss"),
        ("rt_guaranteed_overhead", guar_s / max(clean_s, 1e-9),
         "guaranteed_clean/clean"),
        ("rt_loss5_penalty", loss5_s / max(clean_s, 1e-9),
         "loss5/clean"),
    ]
