"""The program's own view of a traced run: device time by named scope
(``bench/scope_reduce.py``) on a hand-made trace and on one recorded on four
TPU v5e chips, and the readers of the program's spans
(``bench/program_trace.py``) on a recorded ring buffer and on a small run on
host CPU devices.  The reduction the benchmark already had is pinned on its
recorded trace."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

from bench_subprocess import ROOT, run

sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402
from bench import scope_reduce as sr  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED_4C = DATA / "swe1e5_4c_scoped_trace.json.gz"
SPEC = harness.load_spec()
PROGRAM_SPAN_METRICS = [m["name"] for m in SPEC["per_layer"]
                        if m["source"] == "program_span"]
STEP = "jit(body)/shard_map/while/body/closed_call/"

# One chip, times in ns.  A while loop contains the rest; an exchange
# permute (20-50) is overlapped by a gather fusion (40-60); a layout copy
# under no phase runs 70-80; the host is inside a segment 0-15 and 85-100.
HAND = sr.Scoped(
    events=tr.Events(
        device={0: [
            ("%while.1 = while()", 5, 95),
            ("%copy.1 = copy()", 10, 20),
            ("%collective-permute-start.1 = collective-permute-start()", 20,
             50),
            ("%fusion.2 = fusion()", 40, 60),
            ("%fusion.3 = fusion()", 60, 65),
            ("%copy.2 = copy()", 70, 80),
        ]},
        host=[("bench.window", 0, 100), ("bench.dispatch", 0, 15),
              ("bench.block", 15, 85), ("bench.dispatch", 85, 100)]),
    scopes={0: ["jit(body)/shard_map/while",
                STEP + "swe.args/squeeze",
                STEP + "swe.exchange/multi_neighbor/sendrecv/ppermute",
                STEP + "swe.boundary/swe.gather/gather",
                STEP + "swe.update/add",
                "jit(body)/shard_map/while/body"]},
    spans=[("swe.segment", 0, 12), ("swe.segment.put_t", 0, 2),
           ("swe.segment.launch", 2, 12), ("swe.segment", 85, 100)])


def test_hand_made_breakdown():
    b = sr.breakdown(HAND)
    ns = 1e-9
    assert b.window_s == pytest.approx(100 * ns)
    assert b.busy_s == pytest.approx(65 * ns)       # 10-65, 70-80
    ph = b.phases
    assert ph["swe.exchange"] == pytest.approx(30 * ns)   # 20-50, hidden too
    assert ph["swe.gather"] == pytest.approx(10 * ns)     # 50-60 only
    assert ph["swe.args"] == pytest.approx(10 * ns)
    assert ph["swe.update"] == pytest.approx(5 * ns)
    assert ph["swe.flux"] == 0.0
    assert ph["unscoped"] == pytest.approx(10 * ns)        # the copy
    assert sum(ph.values()) == pytest.approx(b.busy_s)
    # idle 0-10 and 85-100 inside a segment; 80-85 and 65-70 outside
    assert b.idle_launch_s == pytest.approx(25 * ns)
    s = b.per_step(2)
    assert s["comm.halo_us"] == pytest.approx(15e-3)
    assert s["dispatch_us.swe"] == pytest.approx((12 + 15) / 2 * 1e-3)
    assert s["swe.segment.launch_us"] == pytest.approx(10e-3)
    assert s["idle_launch_share.swe"] == pytest.approx(25.0)
    # the breakdown agrees with the benchmark's own reduction
    assert b.busy_s == pytest.approx(tr.summarize(HAND.events).busy_s)


@pytest.mark.parametrize("op_name,phase", [
    (STEP + "swe.args/squeeze", "swe.args"),
    (STEP + "swe.interior/swe.flux/mul", "swe.flux"),
    (STEP + "swe.exchange/multi_neighbor/round/wire.chunk/ppermute",
     "swe.exchange"),
    ("jit(body)/shard_map/while/body/closed_call", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of(op_name, phase):
    assert sr.phase_of(op_name) == phase


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
     "%fusion.3 = fusion()"),
    ("%collective-permute-start.1 = (f32[8], f32[8]) "
     "collective-permute-start(f32[8] %p)",
     "%collective-permute-start.1 = collective-permute-start()"),
    ("%while.3 = while()", "%while.3 = while()"),
])
def test_cut_keeps_name_and_opcode(text, want):
    assert sr.cut(text) == want
    assert tr.op_kind(sr.cut(text)) == tr.op_kind(text)


def test_recorded_1c_summary_unchanged():
    """The benchmark's reduction of its recorded one-chip trace, number for
    number, as it was when the program's spans and scopes were added."""
    s = tr.summarize(tr.load_json(str(DATA / "swe1e5_1c_trace.json.gz")))
    assert s.window_s == pytest.approx(0.060011911, rel=1e-12)
    assert s.busy_s == pytest.approx(0.056070287, rel=1e-12)
    assert s.compute_s == pytest.approx(0.056070287, rel=1e-12)
    assert s.collective_s == s.exposed_collective_s == 0.0
    assert [(n, pytest.approx(v, rel=1e-9)) for n, v in s.idle_gaps] == [
        ("bench.block", 0.00394084), ("bench.dispatch", 7.84e-07)]
    assert [n for n, _ in s.device_ops[:3]] == ["reshape", "copy", "fusion"]


def test_program_span_readers_on_small_runs(tmp_path):
    """On host CPU devices, a cell's entry drives the program with its
    tracing on, as in a traced run: each reader of the program's spans
    returns a number, and a segment's span is no longer than the unit the
    harness timed around it."""
    code = """
from bench import program_trace
from repro.obs import trace as obs_trace


def program_view(cell):
    obs_trace.clear()
    spec = harness.load_spec()
    c = harness.find(spec, "workloads", cell)
    config = json.loads(harness.config_file(spec, c["config"]).read_text())
    config.update(SMALL)
    traffic = json.loads(harness.traffic_file(c["traffic"]).read_text())
    entry = harness.load_module(harness.entry_file(traffic["entry"]))
    harness.configure_jax(CACHE)
    devices = harness.devices_for(c["chips"], require_tpu=False)
    ctx = harness.Context(cell=c, config=config, traffic=traffic,
                          chips=c["chips"], device_kind="cpu")
    p = entry.prepare(ctx, devices, 2**31 + 11)
    ctx.window = entry.measure(p, 0.3, traced=False)
    readers = {m["name"]: (m, harness.load_module(
        harness.metric_file(m["name"])))
        for m in harness.cell_metrics(spec, cell, "per_layer")
        if m["source"] == "program_span"}
    got = harness.read_metrics(readers, ctx)
    return {"metrics": {k: v["value"] for k, v in got.items()},
            "unit_us": min(ctx.window.unit_seconds) * 1e6,
            "segments": len(program_trace.spans("swe.segment")),
            "units": ctx.window.units}


emit(**{c: program_view(c) for c in %r})
"""
    cache = str(tmp_path / "jax_cache")
    one = [c["name"] for c in SPEC["workloads"] if c["chips"] == 1]
    four = [c["name"] for c in SPEC["workloads"] if c["chips"] == 4]
    out = run(code % one, 1, cache)
    out.update(run(code % four, 4, cache))
    for cell, r in out.items():
        want = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                        "per_layer")
                if m["source"] == "program_span"}
        assert set(r["metrics"]) == want == set(PROGRAM_SPAN_METRICS)
        assert all(v > 0 for v in r["metrics"].values()), r
        # two warm segments in set-up, then the window's
        assert r["segments"] == r["units"] + 2
        assert r["metrics"]["dispatch_us.swe"] <= r["unit_us"]


def test_recorded_4c_scopes_and_idle_fill_the_window():
    """Two 20-step segments of ``swe1e5-4c`` on four TPU v5 lite chips,
    traced with the program's scopes and host spans (instruction texts cut
    to name and opcode): the phases and idle time fill the window, busy
    time is the benchmark's own, and each segment's children lie in it."""
    sc = sr.load_json(str(RECORDED_4C))
    b = sr.breakdown(sc)
    assert b.chips == 4
    assert b.busy_s == pytest.approx(tr.summarize(sc.events).busy_s)
    idle = b.window_s - b.busy_s
    assert sum(b.phases.values()) + idle == pytest.approx(b.window_s,
                                                          rel=0.01)
    assert all(b.phases[p] > 0 for p in sr.PHASES), b.phases
    segments = [s for s in sc.spans if s[0] == sr.SEGMENT]
    assert len(segments) == len(b.spans[sr.SEGMENT]) >= 2
    for name in ("swe.segment.put_t", "swe.segment.launch"):
        children = [s for s in sc.spans if s[0] == name]
        assert len(children) == len(segments)
        for (_, a, z), (_, sa, sz) in zip(children, segments):
            assert sa <= a and z <= sz
    s = b.per_step(20 * len(segments))
    assert 0 < s["swe.segment.put_t_us"] < s["dispatch_us.swe"]
    assert 0 < s["idle_launch_share.swe"] < 100
    assert s["comm.halo_us"] > 0


@pytest.fixture
def recorded_ring_buffer():
    """The program's ring buffer as the recorded four-chip run left it:
    set-up, two warm segments, then the window's."""
    import gzip
    import json
    from repro.obs import trace as obs_trace
    saved = obs_trace._TRACER
    with gzip.open(RECORDED_4C, "rt") as f:
        program = json.load(f)["program"]
    obs_trace._TRACER = obs_trace.Tracer()
    for e in program:
        obs_trace._TRACER.emit(e)
    try:
        yield program
    finally:
        obs_trace._TRACER = saved


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_program_span_readers_on_recorded_ring_buffer(recorded_ring_buffer,
                                                      cell):
    segments = [e for e in recorded_ring_buffer if e["name"] == sr.SEGMENT]
    units = len(segments) - 2
    c = harness.find(SPEC, "workloads", cell)
    w = harness.Window(seconds=0.01, unit_seconds=[0.005] * units,
                       work_per_unit=20, compiles=0)
    ctx = harness.Context(cell=c, config={}, traffic={}, chips=c["chips"],
                          device_kind="TPU v5 lite", window=w)
    readers = {m["name"]: (m, harness.load_module(
        harness.metric_file(m["name"])))
        for m in harness.cell_metrics(SPEC, cell, "per_layer")
        if m["source"] == "program_span"}
    got = harness.read_metrics(readers, ctx)
    assert set(got) == set(PROGRAM_SPAN_METRICS)
    assert got["dispatch_us.swe"]["value"] == pytest.approx(
        sum(e["dur"] for e in segments[2:]) / units)
    assert got["setup.mesh_gen_s"]["value"] > 0
    assert got["setup.partition_s"]["value"] > 0


def test_op_names_from_compiled_module():
    """Each instruction's name stack, read from the compiled module's text
    as the tool reads XLA's dump of it, gives its phase."""
    import jax
    import jax.numpy as jnp
    from repro.obs import trace as obs_trace

    @jax.jit
    def f(x, idx):
        with obs_trace.scope("swe.gather"):
            g = x[idx]
        with obs_trace.scope("swe.flux"):
            return jnp.sin(g) * 2.0

    text = f.lower(jnp.ones(8), jnp.arange(8) % 4).compile().as_text()
    names = sr.op_names(text)
    phases = {sr.phase_of(v) for v in names.values()}
    assert {"swe.gather", "swe.flux"} <= phases
    assert all(k in text for k in names)


def test_tool_refuses_without_a_tpu():
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "scope_reduce.py"),
         "--workload", SPEC["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1"],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(ROOT))
    assert proc.returncode == 3
    assert "not a TPU" in proc.stderr
