"""Run harness code in a fresh interpreter on host CPU devices.

The benchmark turns on JAX's persistent compilation cache and needs a given
number of devices; a test process must do neither to itself, so every test
that drives the harness does it in a child process, with a cache directory
of the test's own.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

PRELUDE = f"""
import json, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
from bench import harness
from bench.ref import mesh as ref_mesh

N_REQ = 3000
SMALL = {{"n_elements_requested": N_REQ,
          "n_elements": ref_mesh.bight_mesh(N_REQ, 0).n_elements}}


def run(cell, seed=2**31 + 7, seconds=0.3, substitute=None):
    return harness.run_cell(cell, seed, seconds, False, require_tpu=False,
                            overrides=SMALL, substitute=substitute,
                            cache_dir=CACHE)


def emit(**results):
    print("RESULTS " + json.dumps(results), flush=True)
"""


def run(code: str, devices: int, cache_dir: str, timeout: int = 600) -> dict:
    """Run ``PRELUDE + code`` on ``devices`` CPU devices with the compile
    cache in ``cache_dir``; return what it passed to ``emit``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    head = f"CACHE = {cache_dir!r}\n"
    proc = subprocess.run(
        [sys.executable, "-c", head + PRELUDE + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(ROOT))
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS "):
            return json.loads(line[len("RESULTS "):])
    raise AssertionError(f"child failed (rc={proc.returncode})\n"
                         f"{proc.stdout[-3000:]}\n{proc.stderr[-5000:]}")
