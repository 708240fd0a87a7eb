"""Observability substrate: span tracer, metrics registry, Chrome export,
report CLI, watchdog telemetry — and the zero-overhead guarantee (tracing
off must leave the instrumented collectives bitwise-identical)."""
import json
import os

import pytest

from helpers import run_multidevice

from repro.obs import metrics, trace
from repro.obs import report as obs_report


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test starts with tracing off and ends restoring the env gate."""
    trace.configure("0")
    yield
    trace.configure("0")


# ----------------------------------------------------------------------
# trace: disabled path
# ----------------------------------------------------------------------

def test_disabled_span_is_null_singleton():
    assert not trace.enabled()
    s1 = trace.span("a", cat="collective", hops=3)
    s2 = trace.span("b", cat="wire")
    assert s1 is s2 is trace._NULL_SPAN   # no per-call allocation
    with s1 as s:
        s.set(result=1)                   # all no-ops
    trace.instant("x", cat="watchdog")
    assert trace.events() == []
    assert trace.flush() is None
    assert trace.mode() is None


def test_configure_modes(tmp_path):
    assert trace.configure("") is None
    assert trace.configure("0") is None
    t = trace.configure("1")
    assert t is not None and trace.enabled() and trace.mode() == "1"
    path = str(tmp_path / "t.json")
    t = trace.configure(f"chrome:{path}")
    assert t.sink == path and trace.mode() == f"chrome:{path}"
    with pytest.raises(ValueError):
        trace.configure("bogus")


# ----------------------------------------------------------------------
# trace: enabled path
# ----------------------------------------------------------------------

def test_span_nesting_and_args():
    trace.configure("1")
    with trace.span("outer", cat="collective", hops=2):
        with trace.span("inner", cat="wire", chunk=0) as sp:
            sp.set(us_per_call=42.0)
    evs = trace.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"] == {"chunk": 0, "us_per_call": 42.0}
    assert outer["args"] == {"hops": 2}
    # time containment on the same track = nesting in Perfetto
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["pid"] == outer["pid"]
    assert inner["tid"] == outer["tid"]


def test_instant_and_rank_tracks():
    trace.configure("1")
    trace.instant("watchdog.straggler", cat="watchdog", step=7)
    with trace.span("s", cat="collective", rank=2):
        pass
    evs = trace.events()
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["step"] == 7
    span_ev = next(e for e in evs if e["ph"] == "X")
    assert span_ev["pid"] == 3        # rank 2 -> pid 3 (pid 0 = host)


def test_ring_buffer_drops_oldest():
    trace._TRACER = trace.Tracer(capacity=8)
    for i in range(20):
        trace.instant(f"e{i}", cat="x")
    assert len(trace.events()) == 8
    assert trace.tracer().dropped == 12
    assert trace.events()[0]["name"] == "e12"
    # the export reports the drop count
    assert (trace.tracer().to_chrome()["otherData"]["dropped_events"]
            == 12)


def test_traced_decorator_checks_enablement_per_call():
    calls = []

    @trace.traced("work", cat="sweep")
    def work():
        calls.append(1)
        return 5

    assert work() == 5 and trace.events() == []   # disabled: plain call
    trace.configure("1")
    assert work() == 5
    assert [e["name"] for e in trace.events()] == ["work"]


def test_chrome_export_schema(tmp_path):
    path = str(tmp_path / "trace.json")
    trace.configure(f"chrome:{path}")
    with trace.span("sendrecv", cat="collective", hops=2, nbytes=1024):
        with trace.span("wire.chunk", cat="wire", chunk=0, of=2):
            pass
    trace.instant("watchdog.step", cat="watchdog", step=0, rank=1)
    out = trace.flush()
    assert out == path
    with open(path) as f:
        payload = json.load(f)
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    x = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"sendrecv", "wire.chunk"}
    assert all(isinstance(e["dur"], (int, float)) and e["dur"] >= 0
               for e in x)
    assert all(isinstance(e["ts"], (int, float)) for e in x)
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "host" for e in meta)
    assert payload.get("otherData", {}).get("dropped_events", 0) == 0


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------

def test_counter_gauge_and_labels():
    reg = metrics.Registry()
    c = reg.counter("comm.bytes")
    c.inc()
    c.inc(9)
    assert c.value == 10
    assert reg.counter("comm.bytes") is c          # get-or-create
    c2 = reg.counter("comm.edge_bytes", hops=2)
    c3 = reg.counter("comm.edge_bytes", hops=3)
    assert c2 is not c3
    c2.inc(5)
    assert reg.snapshot()["comm.edge_bytes{hops=2}"] == 5
    g = reg.gauge("queue.depth")
    g.set(7)
    assert g.value == 7
    with pytest.raises(TypeError):
        reg.gauge("comm.bytes")                    # type mismatch on a name
    reg.reset()
    assert c.value == 0 and g.value == 0


def test_histogram_percentiles():
    reg = metrics.Registry()
    h = reg.histogram("lat.us")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    # fixed 1-2-5 buckets: percentiles are interpolated, so allow slack
    assert 30 <= s["p50"] <= 70
    assert s["p95"] >= s["p50"] and s["p99"] >= s["p95"]
    assert s["p99"] <= 100.0 * 1.01
    assert s["mean"] == pytest.approx(50.5)
    h.reset()
    assert h.summary()["count"] == 0


def test_find_prefix():
    reg = metrics.Registry()
    reg.counter("sweep.pruned").inc(3)
    reg.histogram("sweep.us", collective="all_reduce").observe(7.0)
    reg.counter("plans.plan_hits").inc()
    found = reg.find("sweep.")
    assert set(found) == {"sweep.pruned", "sweep.us{collective=all_reduce}"}


def test_plans_cache_stats_shim():
    """plans.cache_stats() keeps its dict shape but is backed by the metrics
    registry — the same counters the sweep and report read."""
    from repro.core import plans
    from repro.core.config import CommConfig
    plans.reset_stats()
    base = metrics.registry().counter("plans.plan_misses").value
    plans.chunk_plan((64, 3), "float32", CommConfig())
    st = plans.cache_stats()
    assert set(st) >= {"plan_hits", "plan_misses", "program_hits",
                       "program_misses", "size"}
    assert all(isinstance(v, int) for v in st.values())
    assert metrics.registry().counter("plans.plan_misses").value > base


# ----------------------------------------------------------------------
# watchdog telemetry + bounded retention
# ----------------------------------------------------------------------

def test_watchdog_event_cap_and_dropped_counter():
    from repro.runtime.fault_tolerance import StepWatchdog
    metrics.registry().counter("watchdog.events_dropped").reset()
    wd = StepWatchdog(k=0.0, warmup=1, window=4, max_events=3)
    # k=0: every step beyond the first warmup is a "straggler"
    import time as _t
    for i in range(10):
        wd.start_step(i)
        _t.sleep(0.001 * (1 + i % 3))
        wd.end_step()
    assert len(wd.events) <= 3
    assert wd.events_dropped > 0
    assert (metrics.registry().counter("watchdog.events_dropped").value
            == wd.events_dropped)
    # durations memory is bounded too
    assert wd.durations.maxlen is not None


def test_watchdog_double_end_step_is_noop():
    """Regression: end_step must consume the start mark — a second call at
    the same boundary used to append the duration twice (skewing the median)
    and could emit a phantom straggler."""
    from repro.runtime.fault_tolerance import StepWatchdog
    wd = StepWatchdog(k=0.0, warmup=1, window=4)
    wd.start_step(0)
    wd.end_step()
    assert len(wd.durations) == 1
    assert wd.end_step() is None            # no start mark -> no-op
    assert len(wd.durations) == 1
    assert len(wd.events) == 0
    # the next real step still measures normally
    wd.start_step(1)
    wd.end_step()
    assert len(wd.durations) == 2


def test_watchdog_emits_trace_instants():
    from repro.runtime.fault_tolerance import StepWatchdog
    trace.configure("1")
    wd = StepWatchdog(warmup=100)          # no stragglers, just step marks
    for i in range(3):
        wd.start_step(i)
        wd.end_step()
    steps = [e for e in trace.events() if e["name"] == "watchdog.step"]
    assert len(steps) == 3
    assert all(e["cat"] == "watchdog" and e["ph"] == "i" for e in steps)


# ----------------------------------------------------------------------
# report CLI
# ----------------------------------------------------------------------

def _make_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    trace.configure(f"chrome:{path}")
    for hops in (1, 1, 2):
        with trace.span("sendrecv", cat="collective", hops=hops, nbytes=64):
            with trace.span("wire.chunk", cat="wire", chunk=0, of=1):
                pass
    with trace.span("swe.segment", cat="driver", steps=20):
        pass
    trace.instant("watchdog.step", cat="watchdog", step=0)
    trace.flush()
    return path


def test_report_cli_tables(tmp_path, capsys):
    path = _make_trace(tmp_path)
    assert obs_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "sendrecv@h1" in out and "sendrecv@h2" in out
    assert "wire" in out and "watchdog.step" in out
    assert "collective" in out
    # per-edge rows carry the torus hop distances
    agg = obs_report.summarize(obs_report.load_trace(path))
    assert agg["per_edge"]["sendrecv@h1"]["count"] == 2
    assert agg["per_edge"]["sendrecv@h2"]["hops"] == 2


def test_report_cli_json_and_errors(tmp_path, capsys):
    path = _make_trace(tmp_path)
    assert obs_report.main([path, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert "per_edge" in payload and "instants" in payload
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert obs_report.main([str(bad)]) == 2
    assert obs_report.main([str(tmp_path / "missing.json")]) == 2


# ----------------------------------------------------------------------
# zero-overhead + parity (subprocess: multi-device, env-gated)
# ----------------------------------------------------------------------

_EXCHANGE_CODE = """
import os
os.environ["REPRO_TRACE"] = {trace_mode!r}
import jax, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import CommConfig, CommMode, Transport, Communicator, collectives
from repro.obs import trace

mesh = make_mesh((2,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(7).randn(2, 384).astype(np.float32)
cfg = CommConfig(mode=CommMode.STREAMING, transport=Transport.ORDERED,
                 chunk_bytes=512, window=1)

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
def g(xs):
    return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]

out = np.asarray(g(x))
assert np.array_equal(out, np.roll(x, 1, axis=0))
print("digest", out.tobytes().hex()[:64])
print("n_events", len(trace.events()))
print("enabled", trace.enabled())
"""


def test_tracing_off_is_zero_cost_and_bitwise_identical():
    """REPRO_TRACE=0 leaves the instrumented exchange bitwise-identical to
    the traced run AND records nothing (the zero-overhead guarantee)."""
    off = run_multidevice(_EXCHANGE_CODE.format(trace_mode="0"), n_devices=2)
    on = run_multidevice(_EXCHANGE_CODE.format(trace_mode="1"), n_devices=2)

    def field(out, key):
        return next(l for l in out.splitlines()
                    if l.startswith(key)).split(" ", 1)[1]

    assert field(off, "digest") == field(on, "digest")   # bitwise parity
    assert field(off, "n_events") == "0"
    assert field(off, "enabled") == "False"
    assert int(field(on, "n_events")) > 0
    assert field(on, "enabled") == "True"


def test_two_rank_exchange_exports_nested_chrome_trace(tmp_path):
    """A 2-rank torus exchange with REPRO_TRACE=chrome:<path> leaves a
    well-formed nested trace: collective spans containing wire chunks."""
    path = str(tmp_path / "trace.json")
    run_multidevice("""
import os
os.environ["REPRO_TRACE"] = "chrome:" + {path!r}
import jax, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import CommConfig, CommMode, Communicator, collectives
from repro.obs import trace

mesh = make_mesh((2,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.arange(2 * 256, dtype=np.float32).reshape(2, 256)
cfg = CommConfig(mode=CommMode.STREAMING, chunk_bytes=512)

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
def g(xs):
    return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]

np.asarray(g(x))
trace.flush()
print("OK")
""".format(path=path), n_devices=2)
    evs = obs_report.load_trace(path)
    colls = [e for e in evs if e.get("cat") == "collective"]
    wires = [e for e in evs if e.get("cat") == "wire"]
    assert colls and wires
    outer = next(e for e in colls if e["name"] == "sendrecv")
    # wire chunks nest inside the collective span (time containment,
    # same track)
    inner = [e for e in wires
             if e["pid"] == outer["pid"] and e["tid"] == outer["tid"]
             and outer["ts"] <= e["ts"]
             and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3]
    assert len(inner) >= 2            # multiple chunks per message
    assert all(e["args"]["of"] >= 2 for e in inner)
    assert outer["args"]["hops"] == 1 and outer["args"]["nbytes"] == 1024


# ----------------------------------------------------------------------
# spans on the profiler's clock; scopes in the compiled HLO
# ----------------------------------------------------------------------

def test_disabled_span_opens_no_profiler_annotation(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a profiler annotation was opened")

    monkeypatch.setattr(trace, "_ANNOTATION", refuse)
    s = trace.span("swe.segment", cat="driver", segment=0)
    assert s is trace._NULL_SPAN
    with s:
        pass
    assert trace.events() == []


def test_import_repro_obs_imports_no_jax():
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items() if k != "REPRO_TRACE"}
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-c", "import sys, repro.obs; "
         "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))"],
        capture_output=True, text=True, env=env, timeout=120, check=True)
    assert out.stdout.strip() == "[]"


def _profile(fn, tmp_path):
    """Run ``fn`` inside a CPU ``jax.profiler`` trace; return the host
    plane's events as ``(name, start_ns since the epoch, duration_ns)``."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    planes = {p.name: p for p in pd.planes}
    start = dict(planes["Task Environment"].stats)["profile_start_time"]
    return [(e.name, start + e.start_ns, e.duration_ns)
            for line in planes["/host:CPU"].lines for e in line.events]


def test_enabled_span_lands_in_profiler_host_plane(tmp_path):
    trace.configure("1")

    def work():
        with trace.span("obs.probe", cat="driver", segment=3):
            sum(range(10000))

    host = _profile(work, tmp_path)
    (name, start_ns, dur_ns), = [e for e in host if e[0] == "obs.probe"]
    ev, = [e for e in trace.events() if e["name"] == "obs.probe"]
    assert ev["args"] == {"segment": 3}
    # the ring buffer's clock is the profiler's: the two starts agree
    assert abs(ev["ts"] * 1e3 - start_ns) < 100e3
    assert ev["dur"] * 1e3 >= dur_ns


def test_scope_names_compiled_hlo_and_records_nothing_when_off():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with trace.scope("obs.phase", rounds=2):
            return jnp.sin(x) * 2.0

    text = f.lower(jnp.ones(8)).compile().as_text()
    assert "/obs.phase/" in text
    assert trace.events() == []


def test_scope_records_structure_event_when_on():
    import jax
    import jax.numpy as jnp
    trace.configure("1")

    @jax.jit
    def f(x):
        with trace.scope("obs.phase", rounds=2):
            return x + 1.0

    f(jnp.ones(4))
    f(jnp.ones(4))                        # cached: no second trace
    evs = [e for e in trace.events() if e["name"] == "obs.phase"]
    assert len(evs) == 1
    assert evs[0]["cat"] == "phase"
    assert evs[0]["args"] == {"rounds": 2}
