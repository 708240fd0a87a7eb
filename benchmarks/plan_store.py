"""Disk-backed plan store rows: cold sweep vs fresh-process warm start.

The ACCL+ restart story in benchmark form: a sweep populates a plan
directory (``REPRO_PLAN_DIR``), then a *separate process* runs the identical
sweep against it.  The warm process replays schedule plans from JSON,
deserializes AOT-compiled programs, and hits the XLA compilation cache — so
its wall clock measures exactly what persistence saves a new CLI invocation,
CI job, or serving replica:

- ``pstore_cold_sweep_us`` — cold-process sweep wall clock (empty store;
  derived column: disk misses it wrote);
- ``pstore_warm_sweep_us`` — fresh-process sweep wall clock against the
  populated store (derived: disk hits it replayed);
- ``pstore_warm_ratio`` — warm/cold ratio (non-latency row: smaller is
  better; the CI gate asserts <= 0.7 on the same configuration).

Each leg is a subprocess so "fresh process" is literal — nothing in this
driver's in-memory plan cache can leak into the measurement.  New rows ride
this PR report-only until a second committed baseline lands.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

SWEEP_ARGS = ("--fast", "--devices", "8", "--collectives", "sendrecv",
              "--sizes", "small")


def _run_sweep(plan_dir: str, out_db: str, stats_path: str) -> float:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["REPRO_TUNE_NO_REEXEC"] = "1"
    env["REPRO_SWEEP_STATS_JSON"] = stats_path
    env["REPRO_PLAN_DIR"] = plan_dir
    repo = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo / "src")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.tune.sweep", *SWEEP_ARGS,
         "--out", out_db],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo))
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(f"sweep subprocess failed (rc={proc.returncode}): "
                           f"{proc.stderr[-500:]}")
    return wall


def run():
    import jax
    from repro.launch.mesh import exit_unless_host_cpu
    exit_unless_host_cpu("benchmarks.plan_store")   # starts child sweeps
    if jax.device_count() < 8:
        return [("pstore", 0.0, "skipped_lt8devices")]
    with tempfile.TemporaryDirectory(prefix="repro-pstore-bench-") as td:
        plan_dir = os.path.join(td, "store")
        stats_cold = os.path.join(td, "cold.json")
        stats_warm = os.path.join(td, "warm.json")
        cold_s = _run_sweep(plan_dir, os.path.join(td, "db-cold.json"),
                            stats_cold)
        warm_s = _run_sweep(plan_dir, os.path.join(td, "db-warm.json"),
                            stats_warm)
        with open(stats_cold) as f:
            cold = json.load(f)
        with open(stats_warm) as f:
            warm = json.load(f)
    return [
        ("pstore_cold_sweep_us", cold_s * 1e6,
         f"disk_misses{cold.get('disk_misses', 0)}"),
        ("pstore_warm_sweep_us", warm_s * 1e6,
         f"disk_hits{warm.get('disk_hits', 0)}"),
        ("pstore_warm_ratio", warm_s / max(cold_s, 1e-9),
         f"fresh_process_warm/cold_hits{warm.get('disk_hits', 0)}"),
    ]
