"""The reduction from a device trace to busy time, idle share, exposed
collective time and the breakdown: on a hand-made trace whose answers are
worked out below, and on a trace recorded on a TPU v5e."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

from bench_subprocess import ROOT

sys.path.insert(0, str(ROOT))
from bench import trace_reduce as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

# One chip, times in ns.  A while loop (0-100) contains everything else;
# a collective permute (30-50) is overlapped by a fusion from 40 on.
HAND = tr.Events(
    device={0: [
        ("%while.1 = (f32[8]) while((f32[8]) %t), body=%body", 0, 100),
        ("%fusion.3 = f32[8] fusion(f32[8] %a), kind=kLoop", 10, 30),
        ("%collective-permute-start.1 = (f32[8], f32[8]) "
         "collective-permute-start(f32[8] %p)", 30, 32),
        ("%collective-permute-done.1 = f32[8] collective-permute-done("
         "(f32[8], f32[8]) %collective-permute-start.1)", 32, 50),
        ("%fusion.4 = f32[8] fusion(f32[8] %b), kind=kLoop", 40, 60),
        ("%copy.2 = f32[8] copy(f32[8] %c)", 70, 80),
    ]},
    host=[("bench.window", 0, 100), ("bench.dispatch", 0, 10),
          ("bench.block", 10, 95), ("bench.fold", 95, 100)])


def test_hand_made_trace():
    s = tr.summarize(HAND)
    ns = 1e-9
    assert s.window_s == pytest.approx(100 * ns)
    assert s.busy_s == pytest.approx(60 * ns)         # 10-60, 70-80
    assert s.compute_s == pytest.approx(50 * ns)      # fusions and copy
    assert s.collective_s == pytest.approx(20 * ns)   # 30-50
    assert s.exposed_collective_s == pytest.approx(10 * ns)  # 30-40
    assert s.idle_share == pytest.approx(0.4)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.dispatch"] == pytest.approx(10 * ns)     # 0-10
    assert gaps["bench.block"] == pytest.approx(30 * ns)  # 60-70, 80-100
    ops = dict(s.device_ops)
    assert "while" not in ops
    assert ops["fusion"] == pytest.approx(40 * ns)
    assert ops["collective-permute-done"] == pytest.approx(18 * ns)


def test_clipped_to_the_window():
    ev = tr.Events(device=HAND.device,
                   host=[("bench.window", 20, 45)] + HAND.host[1:])
    s = tr.summarize(ev)
    ns = 1e-9
    assert s.window_s == pytest.approx(25 * ns)
    assert s.busy_s == pytest.approx(25 * ns)
    assert s.exposed_collective_s == pytest.approx(10 * ns)
    assert s.idle_share == pytest.approx(0.0)


@pytest.mark.parametrize("text,coll", [
    ("%collective-permute-start.12 = (f32[1]) collective-permute-start()", True),
    ("%all-reduce-done = f32[4] all-reduce-done(f32[4] %x)", True),
    ("%all-gather.3 = f32[4] all-gather(f32[1] %x)", True),
    ("%fusion.7 = f32[4] fusion(f32[4] %x), kind=kLoop", False),
    ("%copy-done.2 = f32[4] copy-done((f32[4]) %copy-start.2)", False),
])
def test_collective_ops(text, coll):
    assert tr.is_collective(text) is coll


def test_union_and_subtract():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr.subtract(u, [(1, 6)]) == [(0, 1), (6, 9)]
    assert tr.length(u) == 7


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize(tr.Events(device=HAND.device, host=HAND.host[1:]))


def _sweep_busy(events, lo, hi):
    """Busy time by a sweep over interval end points (independent of
    ``trace_reduce.union``), ignoring operations that contain others."""
    points = []
    for _, a, b in tr.leaves(events):
        a, b = max(a, lo), min(b, hi)
        if a < b:
            points += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(points):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_v5e_trace():
    """Two 20-step segments of the 86,578-element mesh on one TPU v5 lite,
    traced by the harness (instruction texts cut to name and opcode)."""
    ev = tr.load_json(str(DATA / "swe1e5_1c_trace.json.gz"))
    s = tr.summarize(ev)
    (lo, hi), = [(a, b) for n, a, b in ev.host if n == tr.WINDOW]
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.busy_s == pytest.approx(_sweep_busy(ev.device[0], lo, hi) * 1e-9)
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert 0.0 < s.idle_share < 0.2
    # one chip: no collective operations at all
    assert s.collective_s == 0.0 and s.exposed_collective_s == 0.0
    assert s.compute_s == pytest.approx(s.busy_s)
    # the idle time is all named, mostly while the host blocked
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert max(gaps, key=gaps.get) == "bench.block"
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert {n for n, _ in b["device_ops"][:3]} == {"reshape", "copy",
                                                    "fusion"}
    assert "while" not in dict(s.device_ops)


def _traced_context(cell: str):
    """A harness context for ``cell`` holding the recorded trace's summary
    and a window of its two 20-step segments."""
    from bench import harness
    spec = harness.load_spec()
    c = harness.find(spec, "workloads", cell)
    ev = tr.load_json(str(DATA / "swe1e5_1c_trace.json.gz"))
    s = tr.summarize(ev)
    w = harness.Window(seconds=s.window_s, unit_seconds=[s.window_s / 2] * 2,
                       work_per_unit=20, compiles=0)
    config = {"n_elements": 86578}
    ctx = harness.Context(cell=c, config=config, traffic={}, chips=c["chips"],
                          device_kind="TPU v5 lite", window=w, trace=s)
    return spec, ctx


def _readers(spec, cell, names):
    from bench import harness
    return {m["name"]: (m, harness.load_module(harness.metric_file(m["name"])))
            for m in harness.cell_metrics(spec, cell, "per_layer")
            if m["name"] in names}


def test_per_layer_readers_on_recorded_trace():
    from bench import harness
    spec, ctx = _traced_context("swe1e5-1c")
    names = {"idle_share.swe", "solver_us", "swe_step_mfu"}
    got = harness.read_metrics(_readers(spec, "swe1e5-1c", names), ctx)
    assert set(got) == names
    s = ctx.trace
    assert got["idle_share.swe"]["value"] == pytest.approx(s.idle_share * 100)
    assert got["solver_us"]["value"] == pytest.approx(s.compute_s / 40 * 1e6)
    assert 0.0 < got["swe_step_mfu"]["value"] < 100.0


def test_listed_metric_that_reads_nothing_is_an_error():
    """The one-chip trace holds no collective: ``comm_us.swe`` finds nothing
    to read there, which is an error in a cell its ``workloads`` name."""
    from bench import harness
    spec, ctx = _traced_context("swe1e5-4c")
    readers = _readers(spec, "swe1e5-4c", {"comm_us.swe"})
    assert "workloads" in readers["comm_us.swe"][0]
    with pytest.raises(RuntimeError, match="found nothing to read"):
        harness.read_metrics(readers, ctx)
    # without a workloads key the metric is only left out of the line
    m, reader = readers["comm_us.swe"]
    m = {k: v for k, v in m.items() if k != "workloads"}
    assert harness.read_metrics({"comm_us.swe": (m, reader)}, ctx) == {}
