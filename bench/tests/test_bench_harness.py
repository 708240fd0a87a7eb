"""The harness as data: every cell of BENCHMARK.json resolves to files found
by name, and every cell runs end to end on host CPU devices at a small
mesh.  The measuring path refuses a host without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench_subprocess import ROOT, run

sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    c = harness.find(SPEC, "workloads", cell)
    config = json.loads(harness.config_file(SPEC, c["config"]).read_text())
    assert config["name"] == c["config"]
    traffic = json.loads(harness.traffic_file(c["traffic"]).read_text())
    entry = harness.load_module(harness.entry_file(traffic["entry"]))
    for fn in ("prepare", "measure", "compare"):
        assert callable(getattr(entry, fn))
    limits = json.loads(harness.limits_file(cell).read_text())["limits"]
    assert limits
    kinds = {"end_to_end": 0, "per_layer": 0}
    for kind in kinds:
        for m in harness.cell_metrics(SPEC, cell, kind):
            reader = harness.load_module(harness.metric_file(m["name"]))
            assert callable(reader.read)
            kinds[kind] += 1
    assert kinds["per_layer"] >= 1
    names = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
    assert "setup_s" in names and len(names) >= 2


def test_every_file_of_the_spec_exists():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert harness.metric_file(m["name"]).is_file()
    assert SPEC["paths"] == ["bench"]


@pytest.fixture(scope="module")
def small_runs(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    one = [c for c in CELLS if harness.find(SPEC, "workloads", c)["chips"] == 1]
    four = [c for c in CELLS if harness.find(SPEC, "workloads", c)["chips"] == 4]
    code = "emit(**{c: run(c) for c in %r})"
    out = run(code % one, 1, cache)
    out.update(run(code % four, 4, cache))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_on_small_mesh(small_runs, cell):
    r = small_runs[cell]
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["count"] == harness.find(SPEC, "workloads", cell)["chips"]
    assert list(r)[-1] == "checks"


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(ROOT))
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert "not a TPU" in proc.stderr
