"""End-to-end shallow-water simulation (the paper's application, §4).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/swe_simulation.py [--elements 2000]

Simulates tidal flow in a synthetic bight over 8 partitions with ACCL-X
streaming halo exchange, reports mass conservation and step rate, and prints
the Eq. 2/3 scalability model for the paper's configurations.
"""
import argparse
import time

import jax
import numpy as np

from repro.core import latmodel
from repro.core.config import (BASELINE_CONFIG, OVERLAPPED_CONFIG, CommConfig,
                               V5E)
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.obs import trace as obs_trace
from repro.runtime.fault_tolerance import StepWatchdog
from repro.swe import driver


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, default=2000)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--comm", default="streaming",
                    choices=("streaming", "overlapped", "baseline", "auto"),
                    help="halo-exchange config: the paper's streaming/baseline"
                         " constants, 'overlapped' = double-buffered exchange"
                         " with the interior/boundary split, or 'auto' = pick"
                         " from the TuneDB sweep (python -m repro.tune.sweep)")
    ap.add_argument("--objective", default="latency",
                    choices=("latency", "e2e"),
                    help="with --comm auto: rank TuneDB entries by bare "
                         "exchange latency or by the measured halo-fold "
                         "consumer loop (sweep with --objective e2e first)")
    ap.add_argument("--topology", default=None,
                    help="place the partitions on a virtual torus, e.g. "
                         "'2x4' or '2x4:snake' (rows x cols = partition "
                         "count); multi-hop halo edges route through "
                         "intermediate partitions and --comm auto selects "
                         "a config per exchange round at its hop distance")
    ap.add_argument("--plan-dir", default=None,
                    help="persist CommPlans and compiled programs to this "
                         "directory (or set REPRO_PLAN_DIR): a rerun of the "
                         "same simulation starts warm — schedules replay "
                         "from disk and XLA compiles from JAX's "
                         "compilation cache")
    args = ap.parse_args()

    from repro.core import planstore
    if args.plan_dir is not None:
        planstore.configure(args.plan_dir)
    store = planstore.active()
    if store is not None:
        print(f"plan store: {store.root} "
              f"({store.entry_count()} entries on disk)")

    n = jax.device_count()
    mesh = make_mesh((n,), ("data",))
    cfg = {"streaming": CommConfig(), "overlapped": OVERLAPPED_CONFIG,
           "baseline": BASELINE_CONFIG, "auto": "auto"}[args.comm]
    topology = None
    if args.topology:
        from repro.core.topology import TorusSpec
        topology = TorusSpec.parse(args.topology)
    sim = driver.build_simulation(args.elements, mesh, cfg,
                                  objective=args.objective,
                                  topology=topology)
    print(f"comm config ({args.comm}): {sim.comm_cfg}")
    if sim.round_cfgs is not None:
        print("per-edge round configs: "
              + ", ".join(f"r{i}:{c.chunk_bytes >> 10}KiB/{c.transport.value}"
                          for i, c in enumerate(sim.round_cfgs)))
    print(f"mesh: {sim.mesh.n_elements} elements over {n} partitions "
          f"(N_max={sim.pm.n_max}, rounds={sim.pm.n_rounds}"
          + (f", torus={topology.name}" if topology else "") + ")")

    run = driver.make_sim_runner(sim, n_inner=20)
    state = sim.state
    m0 = float(np.sum(np.asarray(state)[..., 0] * sim.pm.area * sim.pm.valid))
    state = jax.block_until_ready(run(state, 0.0))   # compile
    # Segment-level watchdog: each 20-step dispatch is one "step" — a slow
    # segment (straggling host, recompile) shows up as a watchdog.straggler
    # instant in the trace and on the watchdog.stragglers counter.
    watchdog = StepWatchdog(warmup=2, window=16)
    t0 = time.perf_counter()
    t = 20 * sim.swe.dt
    for i in range(args.steps // 20 - 1):
        watchdog.start_step(i)
        state = run(state, t)
        jax.block_until_ready(state)
        watchdog.end_step()
        t += 20 * sim.swe.dt
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / max(args.steps - 20, 1)
    m1 = float(np.sum(np.asarray(state)[..., 0] * sim.pm.area * sim.pm.valid))
    dev = jax.devices()[0]
    print(f"ran {args.steps} steps, {dt*1e6:.0f} us/step on {n} x "
          f"{dev.platform} {dev.device_kind}")
    print(f"mass conservation: {m0:.6f} -> {m1:.6f} "
          f"(drift {(m1-m0)/m0:.2e})")
    print(f"watchdog: median segment {watchdog.median_step*1e3:.1f}ms, "
          f"{len(watchdog.events)} straggler(s)")
    if store is not None:
        from repro.core import plans
        st = plans.cache_stats()
        print(f"plan store: {st['disk_hits']} disk hits / "
              f"{st['disk_misses']} misses / {st['disk_writes']} writes "
              f"-> {store.root}")
    if obs_trace.enabled():
        print(f"tracing: {len(obs_trace.events())} events buffered "
              f"(REPRO_TRACE={obs_trace.mode()!r})")

    # Eq. 2/3 model (with the overlap term) at the paper's scales
    w = driver.build_workload(sim)
    print("\nEq.2/3 model + overlap term (this partitioning, v5e constants):")
    for name, cfg in (("MPI+PCIe baseline", BASELINE_CONFIG),
                      ("ACCL-X streaming", CommConfig()),
                      ("ACCL-X overlapped", OVERLAPPED_CONFIG)):
        thr = latmodel.eq2_throughput_overlap(w, cfg, V5E) * n
        stall = latmodel.stall_fraction_overlap(w, cfg, V5E)
        print(f"  {name:20s}: {thr/1e9:8.2f} GFLOP/s "
              f"(pipeline stall {stall*100:.0f}%, "
              f"overlap {latmodel.overlap_fraction(cfg)*100:.0f}%)")


if __name__ == "__main__":
    compile_cache.configure()
    main()
