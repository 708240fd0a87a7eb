"""Figs. 9 & 10 + Table 1 reproduction: shallow-water scaling.

- fig9  (weak scaling, ~6000 elements/partition, up to 48 partitions):
  modeled Eq. 2 throughput for MPI+PCIe-baseline / ACCL-UDP-ish (streaming,
  unordered) / ACCL-TCP-ish (streaming, ordered window), plus MEASURED
  multi-device wall time on this host's CPU devices at small scale.
- fig10 (strong scaling, fixed meshes): modeled throughput vs partitions,
  annotated with N_max — reproducing the step-wise degradation when extra
  neighbors enter the latency term.  The overlapped series uses the Eq. 2
  overlap term (latmodel.eq2_throughput_overlap): the knee moves to higher
  partition counts because L_comm hides behind interior compute.
- fig11: overlap predicted-vs-measured — wall time of the fused vs the
  overlapped (double-buffered, interior/boundary split) step on this host's
  CPU devices next to the model's predicted speedup.
- table1: "resource utilization" analogue — compiled-program stats of the
  SWE step for the configurations.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import latmodel
from repro.core.config import (BASELINE_CONFIG, OVERLAPPED_CONFIG, CommConfig,
                               CommMode, Scheduling, Transport, V5E)

ACCL_UDP = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.FUSED,
                      transport=Transport.UNORDERED)
ACCL_TCP = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.FUSED,
                      transport=Transport.ORDERED, window=8)
ACCL_OVERLAP = OVERLAPPED_CONFIG

# Host-MPI baseline: buffered + host scheduling (l_k = 30 µs twice + copy).
BASE = BASELINE_CONFIG

_N_MAX_TABLE = {1: 0, 2: 1, 4: 3, 8: 4, 12: 5, 16: 5, 24: 6, 32: 6, 48: 7}


def _nmax(p: int) -> int:
    ks = sorted(_N_MAX_TABLE)
    for k in reversed(ks):
        if p >= k:
            return _N_MAX_TABLE[k]
    return 0


def _workload(e_total: int, parts: int, freq=256e6) -> latmodel.SWEWorkload:
    e_local = e_total // parts
    boundary = int(3.5 * np.sqrt(max(e_local, 1)))  # perimeter elements
    n_max = _nmax(parts) if parts > 1 else 0
    return latmodel.SWEWorkload(
        e_total=e_total, e_core=max(e_local - boundary, 1),
        e_send=boundary, e_recv=boundary, d_ext=0, l_pipe=100,
        n_max=max(n_max, 1) if parts > 1 else 0,
        flop_per_element=260.0, freq=freq,
        msg_bytes=max(boundary // max(n_max, 1), 1) * 12 if parts > 1 else 64)


def fig9_weak_scaling():
    rows = []
    for parts in (1, 2, 4, 8, 16, 24, 32, 48):
        e_total = 6000 * parts
        w = _workload(e_total, parts)
        for name, cfg in (("base_mpi", BASE), ("accl_udp", ACCL_UDP),
                          ("accl_tcp", ACCL_TCP),
                          ("accl_overlap", ACCL_OVERLAP)):
            if parts == 1:
                thr = w.freq * w.flop_per_element  # no comm at all
                stall = 0.0
            else:
                thr = latmodel.eq2_throughput_overlap(w, cfg, V5E) * parts
                stall = latmodel.stall_fraction_overlap(w, cfg, V5E)
            rows.append((f"fig9_{name}_p{parts}",
                         1e6 * e_total * w.flop_per_element / thr,
                         f"{thr/1e12:.3f}TFLOPs_stall{stall:.2f}"))
    return rows


def fig10_strong_scaling():
    rows = []
    for e_total in (27_000, 108_000):
        for parts in (2, 4, 8, 16, 24, 32, 48):
            w = _workload(e_total, parts)
            for name, cfg in (("", ACCL_UDP), ("_overlap", ACCL_OVERLAP)):
                thr = latmodel.eq2_throughput_overlap(w, cfg, V5E) * parts
                rows.append((f"fig10_{e_total//1000}k{name}_p{parts}",
                             1e6 * e_total * w.flop_per_element / thr,
                             f"{thr/1e12:.3f}TFLOPs_Nmax{w.n_max}"))
    return rows


def fig11_overlap_predicted_vs_measured():
    """Fused vs overlapped SWE step: measured wall clock on this host's CPU
    devices next to the Eq. 2 overlap-term prediction (same workload)."""
    import jax
    rows = []
    n = jax.device_count()
    if n < 2:
        return [("fig11_overlap", 0.0, "skipped_1device")]
    from repro.swe import driver
    from repro.launch.mesh import make_mesh
    for parts in (2, 4, 8):
        if parts > n:
            break
        dmesh = make_mesh((parts,), ("data",))
        measured = {}
        w = None
        for name, cfg in (("fused", ACCL_UDP), ("overlapped", ACCL_OVERLAP)):
            sim = driver.build_simulation(600 * parts, dmesh, cfg)
            run = driver.make_sim_runner(sim, n_inner=20)
            s = jax.block_until_ready(run(sim.state, 0.0))   # compile+warm
            t0 = time.perf_counter()
            for _ in range(3):
                s = run(s, 0.0)
            jax.block_until_ready(s)
            measured[name] = (time.perf_counter() - t0) / (3 * 20)
            if w is None:
                w = driver.build_workload(sim)
        pred = {name: 1.0 / latmodel.eq2_throughput_overlap(w, cfg, V5E)
                for name, cfg in (("fused", ACCL_UDP),
                                  ("overlapped", ACCL_OVERLAP))}
        pred_speedup = pred["fused"] / pred["overlapped"]
        meas_speedup = measured["fused"] / measured["overlapped"]
        for name in ("fused", "overlapped"):
            rows.append((f"fig11_{name}_p{parts}", measured[name] * 1e6,
                         "measured_us_per_step"))
        rows.append((f"fig11_speedup_p{parts}", meas_speedup,
                     f"predicted{pred_speedup:.2f}x"))
    return rows


def fig9_measured():
    """Measured weak scaling on this host's CPU devices (relative numbers)."""
    import jax
    rows = []
    n = jax.device_count()
    if n < 2:
        return [("fig9_measured", 0.0, "skipped_1device")]
    from repro.swe import driver
    from repro.launch.mesh import make_mesh
    for parts in (1, 2, 4, 8):
        if parts > n:
            break
        dmesh = make_mesh((parts,), ("data",))
        sim = driver.build_simulation(600 * parts, dmesh, ACCL_UDP)
        run = driver.make_sim_runner(sim, n_inner=20)
        s = jax.block_until_ready(run(sim.state, 0.0))
        t0 = time.perf_counter()
        for _ in range(3):
            s = run(s, 0.0)
        jax.block_until_ready(s)
        dt_step = (time.perf_counter() - t0) / (3 * 20)
        rows.append((f"fig9_measured_p{parts}", dt_step * 1e6,
                     f"{sim.mesh.n_elements}elems"))
    return rows


def table1_resources():
    """Compiled-program stats of one SWE step per comm config (the FPGA
    LUT/BRAM table's TPU analogue: code size + temp memory + op counts)."""
    import jax
    rows = []
    if jax.device_count() < 2:
        return [("table1", 0.0, "skipped_1device")]
    from repro.swe import driver
    from repro.launch.mesh import make_mesh
    dmesh = make_mesh((jax.device_count(),), ("data",))
    for name, cfg in (("base", BASE), ("accl_udp", ACCL_UDP),
                      ("accl_tcp", ACCL_TCP), ("accl_overlap", ACCL_OVERLAP)):
        sim = driver.build_simulation(2000, dmesh, cfg)
        # lower one fused inner step
        run = driver.make_sim_runner(sim, n_inner=1)
        import jax.numpy as jnp
        args = driver._static_args(sim)
        lowered = jax.jit(lambda s: run(s, 0.0)).lower(sim.state)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        n_coll = hlo.count("collective-permute")
        rows.append((f"table1_{name}_codebytes",
                     float(mem.generated_code_size_in_bytes), f"permutes{n_coll}"))
        rows.append((f"table1_{name}_tempbytes",
                     float(mem.temp_size_in_bytes), ""))
    return rows


def run():
    return (fig9_weak_scaling() + fig10_strong_scaling() + fig9_measured()
            + fig11_overlap_predicted_vs_measured() + table1_resources())
