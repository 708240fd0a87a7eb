"""Fault injection, degradation monitoring, and elastic recovery.

Covers the PR 8 contract end to end: deterministic fault schedules, the
wire-layer degraded-link emulation on TorusSpec (hold rounds, reroute,
shrink), the hysteresis-gated DegradationMonitor fed from the metrics
registry, model-based config re-selection (NO sweep during recovery —
asserted via the ``sweep.runs`` counter), preemption-guard semantics
(SIGINT, chaining, nesting), torn-checkpoint recovery, and the two
kill-and-resume end-to-end paths (SWE segment loop, LM train loop) with
bitwise-identical result streams.
"""
import json
import os
import signal
import time

import numpy as np
import pytest

from helpers import run_multidevice


# ----------------------------------------------------------------------
# Fault schedules
# ----------------------------------------------------------------------

def test_schedule_generate_is_deterministic():
    from repro.runtime.faults import FaultSchedule
    a = FaultSchedule.generate(7, 100, n_ranks=8, degraded_links=2,
                               rank_losses=1, stragglers=2, preempts=1)
    b = FaultSchedule.generate(7, 100, n_ranks=8, degraded_links=2,
                               rank_losses=1, stragglers=2, preempts=1)
    assert a == b
    c = FaultSchedule.generate(8, 100, n_ranks=8, degraded_links=2,
                               rank_losses=1, stragglers=2, preempts=1)
    assert a != c
    # events land in the middle 80% so recovery has steps left to run
    assert all(10 <= e.step < 90 for e in a)
    kinds = sorted(e.kind for e in a)
    assert kinds == ["degraded_link", "degraded_link", "preempt",
                     "rank_lost", "straggler", "straggler"]


def test_schedule_parse_compact():
    from repro.runtime.faults import (DegradedLink, FaultSchedule, Preempt,
                                      RankLost, Straggler)
    s = FaultSchedule.parse(
        "degraded_link@5=0-1x3.0; rank_lost@10=r5; straggler@7=r2x4.0;"
        "preempt@30")
    assert DegradedLink(5, (0, 1), 3.0) in s.events
    assert RankLost(10, 5) in s.events
    assert Straggler(7, 2, 4.0) in s.events
    assert Preempt(30) in s.events
    # events come back sorted by step regardless of input order
    assert [e.step for e in s] == sorted(e.step for e in s)
    with pytest.raises(ValueError):
        FaultSchedule.parse("meteor@5=r1")
    with pytest.raises(ValueError):
        FaultSchedule.parse("rank_lost@ten=r1")


def test_schedule_parse_chunk_loss():
    from repro.runtime.faults import ChunkLoss, FaultSchedule
    s = FaultSchedule.parse("chunk_loss@5=0.05")
    assert ChunkLoss(5, drop=0.05) in s.events
    s = FaultSchedule.parse("chunk_loss@3=0.05d0.02r0.1")
    assert ChunkLoss(3, drop=0.05, dup=0.02, reorder=0.1) in s.events
    # a pure dup/reorder wire is a legal schedule (drop may be 0)
    s = FaultSchedule.parse("chunk_loss@0=0d0.2")
    assert ChunkLoss(0, drop=0.0, dup=0.2) in s.events


def test_schedule_parse_rejects_malformed_items():
    """Every malformed compact item raises a ValueError naming the item —
    a bad string must never silently drop or double-fire an event."""
    from repro.runtime.faults import FaultSchedule
    bad = [
        "degraded_link@5",                 # missing argument
        "degraded_link@5=0-1",            # missing slowdown
        "degraded_link@5=2-2x3.0",        # self-loop edge
        "degraded_link@5=0-1x0.5",        # slowdown below 1
        "rank_lost@-1=r0",                 # negative step
        "rank_lost@5",                     # missing rank
        "rank_lost@5=rr3",                 # mangled rank
        "straggler@5=r1",                  # missing factor
        "straggler@5=r1x0.2",              # factor below 1
        "preempt@5=r1",                    # trailing argument
        "preempt",                         # missing '@step'
        "chunk_loss@5",                    # missing rate
        "chunk_loss@5=1.0",                # rate out of [0, 1)
        "chunk_loss@5=-0.1",               # negative rate
        "chunk_loss@5=0.05d1.5",           # dup rate out of range
        "chunk_loss@5=0",                  # all-zero rates
        "chunk_loss@5=oops",               # non-numeric rate
    ]
    for item in bad:
        with pytest.raises(ValueError, match="bad fault item|missing"):
            FaultSchedule.parse(item)


def test_schedule_parse_rejects_exact_duplicates():
    from repro.runtime.faults import FaultSchedule
    with pytest.raises(ValueError, match="would fire twice"):
        FaultSchedule.parse("rank_lost@10=r5; rank_lost@10=r5")
    with pytest.raises(ValueError, match="would fire twice"):
        FaultSchedule.parse("chunk_loss@5=0.05;chunk_loss@5=0.05")
    # same kind at a different step (or args) is fine
    s = FaultSchedule.parse("chunk_loss@5=0.05; chunk_loss@9=0.1")
    assert len(s.events) == 2


def test_schedule_json_roundtrip(tmp_path):
    from repro.runtime.faults import FaultSchedule
    s = FaultSchedule.generate(3, 50, n_ranks=4, degraded_links=1,
                               rank_losses=1)
    assert FaultSchedule.from_json(s.to_json()) == s
    p = s.save(tmp_path / "sched.json")
    assert FaultSchedule.load(p) == s
    bad = json.loads(s.to_json())
    bad["version"] = 99
    with pytest.raises(ValueError):
        FaultSchedule.from_json(json.dumps(bad))


# ----------------------------------------------------------------------
# Injector
# ----------------------------------------------------------------------

def test_injector_fires_each_event_once_across_boundaries():
    from repro.runtime.faults import (FaultInjector, FaultSchedule,
                                      RankLostError)
    # events at steps 3 and 10; the loop only polls every 5 steps
    sched = FaultSchedule.parse("degraded_link@3=0-1x2.5;rank_lost@10=r2")
    inj = FaultInjector(sched)
    assert inj.poll(0) == []
    fired = inj.poll(5)                      # step 3 skipped over -> fires now
    assert [e.kind for e in fired] == ["degraded_link"]
    assert inj.active_slowdowns == {(0, 1): 2.5}
    assert inj.poll(9) == []                 # never fires twice
    with pytest.raises(RankLostError) as ei:
        inj.poll(10)
    assert ei.value.rank == 2 and ei.value.step == 10
    # max-merge on repeat degradation of the same link
    inj2 = FaultInjector(FaultSchedule.parse(
        "degraded_link@1=0-1x3.0;degraded_link@2=1-0x2.0"))
    inj2.poll(5)
    assert inj2.active_slowdowns == {(0, 1): 3.0}


def test_injector_same_boundary_degradation_survives_rank_loss():
    """RankLostError is raised LAST: a degradation due at the same boundary
    is applied before the loop unwinds."""
    from repro.runtime.faults import (FaultInjector, FaultSchedule,
                                      RankLostError)
    inj = FaultInjector(FaultSchedule.parse(
        "degraded_link@5=2-3x2.0;rank_lost@5=r1"))
    with pytest.raises(RankLostError):
        inj.poll(5)
    assert inj.active_slowdowns == {(2, 3): 2.0}


def test_injector_straggler_delay_and_preempt():
    from repro.runtime.faults import FaultInjector, FaultSchedule
    from repro.runtime.fault_tolerance import PreemptionGuard
    slept = []
    inj = FaultInjector(FaultSchedule.parse("straggler@4=r0x3.0;preempt@6"),
                        base_step_s=0.01, sleep=slept.append)
    inj.poll(3)
    assert slept == []
    inj.poll(4)                              # 3x slower: +2 x base per step
    assert slept == [pytest.approx(0.02)]
    assert inj.straggler_delay_s(4 + 5) == 0.0   # default duration is 5 steps
    guard = PreemptionGuard()
    inj.poll(6, guard=guard)
    assert guard.preempted


def test_injector_edge_samples_deterministic():
    from repro.runtime.faults import FaultInjector, FaultSchedule
    sched = FaultSchedule.parse("degraded_link@2=0-1x4.0")
    a, b = FaultInjector(sched), FaultInjector(sched)
    for inj in (a, b):
        inj.poll(2)
    ea = a.edge_latency_samples(7, [(0, 1), (1, 2)])
    eb = b.edge_latency_samples(7, [(0, 1), (1, 2)])
    assert ea == eb                          # seeded by (seed, step, edge)
    assert ea[(0, 1)] > 3.5                  # carries the 4x slowdown
    assert 0.9 < ea[(1, 2)] < 1.1            # healthy edge: noise only


# ----------------------------------------------------------------------
# TorusSpec degradation (wire layer)
# ----------------------------------------------------------------------

def test_degraded_spec_validation_and_identity():
    from repro.core.topology import TorusSpec
    spec = TorusSpec.parse("4x2")
    d = spec.with_link_slowdown(1, 0, 3.0)   # canonicalized to (0, 1)
    assert d.degraded_links == ((0, 1),)
    assert d.link_slowdown(0, 1) == 3.0 and d.link_slowdown(1, 0) == 3.0
    assert d.link_slowdown(0, 2) == 1.0
    # plan-cache identity changes; TuneDB identity (name) does not
    assert d.key() != spec.key()
    assert d.name == spec.name
    assert d.with_reroute(True).key() != d.key()
    assert d.without_degradations().key() == spec.key()
    # a factor of exactly 1.0 is a no-op, not a degradation
    assert spec.with_link_slowdown(0, 1, 1.0).degraded_links == ()
    with pytest.raises(ValueError):
        spec.with_link_slowdown(0, 5, 2.0)   # not a physical 1-hop link
    with pytest.raises(ValueError):
        spec.with_link_slowdown(0, 1, 0.5)   # speedups are not faults


def test_route_reroutes_around_confirmed_degradation():
    from repro.core.topology import TorusSpec, route
    spec = TorusSpec.parse("4x4")
    primary = route(spec, 0, 5)              # rows first: 0 -> 4 -> 5
    assert primary == [0, 4, 5]
    hurt = spec.with_link_slowdown(0, 4, 4.0)
    # physics alone does not move routes: belief lags until confirmation
    assert route(hurt, 0, 5) == primary
    believed = hurt.with_reroute(True)
    assert route(believed, 0, 5) == [0, 1, 5]   # cols first dodges the link
    # ties keep rows-first: healthy fabrics route identically under reroute
    assert route(spec.with_reroute(True), 0, 5) == primary


def test_route_rounds_insert_hold_rounds():
    from repro.core.topology import TorusSpec, route_rounds
    spec = TorusSpec.parse("4x2")
    edges = [(0, 2), (1, 3)]
    healthy = route_rounds(spec, edges)
    hurt = route_rounds(spec.with_link_slowdown(0, 2, 3.0), edges)
    n_h = sum(len(b.rounds) for b in healthy.batches)
    n_d = sum(len(b.rounds) for b in hurt.batches)
    assert n_d == n_h + 2                    # ceil(3.0) - 1 hold rounds
    holds = [r for b in hurt.batches for r in b.rounds
             if all(s == d for s, d in r)]
    assert len(holds) == 2                   # every hold is pure self-forward
    # destinations (the value contract) are untouched by the slowdown
    assert tuple(d for b in hurt.batches for d in b.dests) == \
        tuple(d for b in healthy.batches for d in b.dests)


def test_shrink_factorizations():
    from repro.core.topology import TorusSpec
    spec = TorusSpec.parse("4x2").with_link_slowdown(0, 1, 2.0)
    assert spec.shrink(7).shape == (1, 7)    # prime survivor count -> ring
    assert spec.shrink(6).shape == (2, 3)    # squarest factorization
    assert spec.shrink(4).shape == (2, 2)
    # degradations belong to the dead fabric; survivors start clean
    assert spec.shrink(6).degraded_links == ()
    with pytest.raises(ValueError):
        spec.shrink(9)                       # cannot grow


# ----------------------------------------------------------------------
# Degradation monitor
# ----------------------------------------------------------------------

def _private_monitor(**kw):
    from repro.obs.metrics import Registry
    from repro.runtime.faults import DegradationMonitor
    reg = Registry()
    return DegradationMonitor(registry=reg, **kw), reg


def test_monitor_confirms_only_after_hysteresis():
    mon, _ = _private_monitor(threshold=1.5, hysteresis=3, cooldown=100)
    e = (0, 1)
    assert mon.observe(0, {e: 1.0}) == []    # first sample seeds the baseline
    assert mon.observe(1, {e: 3.0}) == []
    assert mon.observe(2, {e: 3.0}) == []
    assert mon.observe(3, {e: 3.0}) == [e]   # third consecutive flag confirms
    # flagged samples never refresh the baseline (no self-normalization)
    assert mon.baseline(e) == 1.0


def test_monitor_never_flaps_under_steady_noise():
    mon, reg = _private_monitor(threshold=1.5, hysteresis=3, cooldown=5)
    rng = np.random.RandomState(0)
    for step in range(200):
        samples = {(0, 1): 1.0 + 0.3 * rng.rand(),
                   (1, 2): 1.0 + 0.3 * rng.rand()}
        assert mon.observe(step, samples) == []
    assert mon.confirmed == set()
    assert reg.counter("monitor.confirmations").value == 0


def test_monitor_streak_resets_on_healthy_sample():
    mon, _ = _private_monitor(threshold=1.5, hysteresis=3, cooldown=100)
    e = (0, 1)
    mon.observe(0, {e: 1.0})
    for step, x in enumerate((3.0, 3.0, 1.0, 3.0, 3.0), start=1):
        assert mon.observe(step, {e: x}) == []   # the dip breaks the streak
    assert mon.observe(6, {e: 3.0}) == [e]


def test_monitor_cooldown_suppresses_reconfirmation():
    mon, reg = _private_monitor(threshold=1.5, hysteresis=2, cooldown=20)
    e = (2, 3)
    mon.observe(0, {e: 1.0})
    assert mon.observe(1, {e: 4.0}) == []
    assert mon.observe(2, {e: 4.0}) == [e]
    # still degraded, still flagged — but inside the cooldown window
    for step in range(3, 22):
        assert mon.observe(step, {e: 4.0}) == []
    # the persistent degradation re-confirms the moment cooldown expires
    assert mon.observe(22, {e: 4.0}) == [e]
    assert reg.counter("monitor.confirmations").value == 2


def test_monitor_registry_deltas_and_traffic_gate():
    from repro.obs.metrics import Registry
    from repro.runtime.faults import DegradationMonitor
    reg = Registry()
    mon = DegradationMonitor(threshold=1.5, hysteresis=1, registry=reg)
    reg.counter("comm.edge_bytes", hops=1).inc(100)
    reg.counter("comm.edge_bytes", hops=2).inc(40)
    reg.counter("watchdog.stragglers").inc()
    d = mon.registry_deltas()
    assert d["edge_bytes"] == {1: 100, 2: 40}
    assert d["traffic"] == 140 and d["stragglers"] == 1
    d2 = mon.registry_deltas()               # deltas, not totals
    assert d2["traffic"] == 0 and d2["stragglers"] == 0
    # no traffic since last observation -> no verdict (streaks frozen)
    e = (0, 1)
    mon.observe(0, {e: 1.0})
    assert mon.observe(1, {e: 9.0}, require_traffic=True) == []
    reg.counter("comm.edge_bytes", hops=1).inc(10)
    assert mon.observe(2, {e: 9.0}, require_traffic=True) == [e]
    assert mon.last_straggler_delta == 0


def test_injector_chunk_loss_arms_wire_faults():
    from repro.core.reliable import WireFaults
    from repro.runtime.faults import FaultInjector, FaultSchedule
    sched = FaultSchedule.parse("chunk_loss@5=0.05d0.02r0.1")
    inj = FaultInjector(sched)
    assert inj.wire_faults() is None         # not fired yet
    inj.poll(5)
    wf = inj.wire_faults()
    assert isinstance(wf, WireFaults)
    assert (wf.drop, wf.dup, wf.reorder) == (0.05, 0.02, 0.1)
    # a requested drop rate pins the first transmission lost, so short
    # traces deterministically exercise recovery
    assert (0, 0, 0) in wf.drop_events
    # pure dup/reorder wires pin nothing (no drop to guarantee)
    inj2 = FaultInjector(FaultSchedule.parse("chunk_loss@0=0d0.2"))
    inj2.poll(0)
    assert inj2.wire_faults().drop_events == frozenset()


def test_monitor_wire_signal_hysteresis_and_cooldown():
    """Sustained wire.retransmits growth confirms a lossy wire exactly once
    per episode — same streak/cooldown discipline as the edge signal."""
    mon, reg = _private_monitor(threshold=1.5, hysteresis=3, cooldown=10)
    e = (0, 1)
    confirmations = []
    for step in range(8):
        reg.counter("wire.retransmits").inc(2)   # steady retransmit stream
        mon.observe(step, {e: 1.0})
        confirmations.append(mon.wire_confirmed)
    # streak reaches hysteresis at the 3rd observation, then cooldown
    # suppresses re-confirmation while the stream persists
    assert confirmations == [False, False, True,
                             False, False, False, False, False]
    assert mon.wire_confirmations == 1
    assert reg.counter("monitor.wire_confirmations").value == 1
    assert mon.last_retransmit_delta == 2
    # cooldown expiry + persistent loss re-confirms
    for step in range(8, 14):
        reg.counter("wire.retransmits").inc(1)
        mon.observe(step, {e: 1.0})
    assert mon.wire_confirmations == 2


def test_monitor_wire_streak_resets_when_clean():
    mon, reg = _private_monitor(threshold=1.5, hysteresis=3, cooldown=10)
    e = (0, 1)
    for step, delta in enumerate((3, 3, 0, 3, 3)):   # the gap breaks it
        if delta:
            reg.counter("wire.retransmits").inc(delta)
        mon.observe(step, {e: 1.0})
        assert not mon.wire_confirmed
    reg.counter("wire.retransmits").inc(3)
    mon.observe(5, {e: 1.0})
    assert mon.wire_confirmed                        # 3rd consecutive delta
    assert mon.confirmed == set()                    # edge signal untouched


def test_parse_labels_roundtrip():
    from repro.obs.metrics import parse_labels
    assert parse_labels("comm.edge_bytes{hops=2}") == \
        ("comm.edge_bytes", {"hops": "2"})
    assert parse_labels("sweep.runs") == ("sweep.runs", {})
    assert parse_labels("x{a=1,b=two}") == ("x", {"a": "1", "b": "two"})


# ----------------------------------------------------------------------
# Model-based re-selection (no sweep)
# ----------------------------------------------------------------------

def _engineered_db():
    """A synthetic TuneDB whose calibrated Eq. 1 model reorders configs
    across hop distance and link slowdown: at 64 KiB, 1 hop favors buffered
    while 3 hops favor streaming; at 16 KiB / 2 hops, a 3x link slowdown
    flips the streaming chunk size from 4096 to 1024."""
    import dataclasses
    from repro.core import latmodel
    from repro.core.config import CommConfig, CommMode, V5E
    from repro.tune.db import TuneDB, TuneEntry
    from repro.tune.space import config_to_dict
    buf = CommConfig(mode=CommMode.BUFFERED)
    s4k = CommConfig(mode=CommMode.STREAMING, chunk_bytes=4096)
    s1k = CommConfig(mode=CommMode.STREAMING, chunk_bytes=1024)
    hw = dataclasses.replace(V5E, host_dispatch=50e-6, fused_dispatch=2e-6,
                             ici_latency=5e-6, ici_bw=0.25e9, hbm_bw=20e9,
                             ici_hop_latency=20e-6)
    db = TuneDB()
    topo = "cpu:8"
    for cfg in (buf, s4k, s1k):
        for size in (4096, 16384, 65536, 1 << 20):
            for hops in (1, 3):
                sec = latmodel.pingping_latency(size, cfg, hw, hops=hops)
                for coll in ("sendrecv", "multi_neighbor"):
                    db.add(TuneEntry(topo=topo, collective=coll,
                                     msg_bytes=size,
                                     config=config_to_dict(cfg),
                                     us_per_call=sec * 1e6, hops=hops))
    return db, (buf, s4k, s1k)


def test_model_reselect_flips_with_hop_distance():
    from repro.core.config import CommMode
    from repro.tune.elastic import model_reselect
    db, _ = _engineered_db()
    near = model_reselect("multi_neighbor", 65536, db=db, hops=1,
                          topo="cpu:8")
    far = model_reselect("multi_neighbor", 65536, db=db, hops=3,
                         topo="cpu:8")
    assert near.mode == CommMode.BUFFERED
    assert far.mode == CommMode.STREAMING


def test_model_reselect_flips_with_link_slowdown():
    from repro.core.config import CommMode
    from repro.tune.elastic import model_reselect
    db, _ = _engineered_db()
    healthy = model_reselect("multi_neighbor", 16384, db=db, hops=2,
                             link_slowdown=1.0, topo="cpu:8")
    degraded = model_reselect("multi_neighbor", 16384, db=db, hops=2,
                              link_slowdown=3.0, topo="cpu:8")
    assert healthy.mode == CommMode.STREAMING
    assert healthy.chunk_bytes == 4096
    assert degraded.mode == CommMode.STREAMING
    assert degraded.chunk_bytes == 1024      # slower wire -> smaller windows


def test_model_reselect_cold_db_falls_back_without_sweep():
    from repro.core.config import CommConfig, CommMode
    from repro.obs import metrics as obs_metrics
    from repro.tune.db import TuneDB
    from repro.tune.elastic import model_reselect
    reg = obs_metrics.registry()
    sweeps0 = reg.counter("sweep.runs").value
    cold0 = reg.counter("tune.reselect_cold_fallbacks").value
    fb = CommConfig(mode=CommMode.BUFFERED)
    out = model_reselect("multi_neighbor", 4096, db=TuneDB(), fallback=fb)
    assert out == fb
    assert reg.counter("tune.reselect_cold_fallbacks").value == cold0 + 1
    assert reg.counter("sweep.runs").value == sweeps0


def test_reselect_round_configs_per_round_and_no_sweep():
    from repro.core.communicator import Communicator
    from repro.core.config import CommMode
    from repro.core.topology import TorusSpec
    from repro.obs import metrics as obs_metrics
    from repro.tune.elastic import reselect_round_configs
    db, _ = _engineered_db()
    spec = TorusSpec.parse("4x2")
    comm = Communicator(("data",), (8,), topo=spec)
    rounds = [[(0, 2)], [(0, 5)]]            # a 1-hop round and a 3-hop round
    sweeps0 = obs_metrics.registry().counter("sweep.runs").value
    rep, per_round = reselect_round_configs(rounds, comm, 65536, db=db,
                                            topo="cpu:8")
    assert obs_metrics.registry().counter("sweep.runs").value == sweeps0
    assert rep.mode == CommMode.STREAMING    # representative = worst hop
    assert per_round is not None and len(per_round) == 2
    assert per_round[0].mode == CommMode.BUFFERED
    assert per_round[1].mode == CommMode.STREAMING
    # scheduling discipline is unified with the representative
    assert len({c.scheduling for c in per_round}) == 1


# ----------------------------------------------------------------------
# Preemption guard
# ----------------------------------------------------------------------

def test_guard_handles_sigint_by_default():
    from repro.runtime.fault_tolerance import PreemptionGuard
    before = signal.getsignal(signal.SIGINT)
    with PreemptionGuard() as g:
        assert not g.preempted
        signal.raise_signal(signal.SIGINT)   # a Ctrl-C drains, not crashes
        assert g.preempted
    assert signal.getsignal(signal.SIGINT) is before


def test_guard_chains_preexisting_custom_handler():
    from repro.runtime.fault_tolerance import PreemptionGuard
    calls = []
    orig = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda signum, frame: calls.append(signum))
    try:
        with PreemptionGuard() as g:
            signal.raise_signal(signal.SIGTERM)
            assert g.preempted
            assert calls == [signal.SIGTERM]    # the launcher's hook still ran
        # exit hands the signal back to the custom handler, not the default
        signal.raise_signal(signal.SIGTERM)
        assert calls == [signal.SIGTERM, signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, orig)


def test_guard_nested_restores_in_order():
    from repro.runtime.fault_tolerance import PreemptionGuard
    orig = signal.getsignal(signal.SIGTERM)
    outer, inner = PreemptionGuard(), PreemptionGuard()
    with outer:
        h_outer = signal.getsignal(signal.SIGTERM)
        with inner:
            assert signal.getsignal(signal.SIGTERM) is not h_outer
            signal.raise_signal(signal.SIGTERM)
            assert inner.preempted
            assert outer.preempted           # inner chains to outer's handler
        assert signal.getsignal(signal.SIGTERM) is h_outer
    assert signal.getsignal(signal.SIGTERM) is orig


def test_guard_reentrant_same_instance():
    from repro.runtime.fault_tolerance import PreemptionGuard
    orig = signal.getsignal(signal.SIGTERM)
    g = PreemptionGuard()
    with g:
        with g:                              # eval loop inside the train loop
            pass
        assert signal.getsignal(signal.SIGTERM) is not orig
    assert signal.getsignal(signal.SIGTERM) is orig


# ----------------------------------------------------------------------
# Torn checkpoints
# ----------------------------------------------------------------------

def test_latest_step_skips_torn_checkpoint(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.obs import metrics as obs_metrics
    tree = {"w": np.arange(8, dtype=np.float32)}
    ck = Checkpointer(tmp_path)
    ck.save(1, tree)
    ck.save(2, tree)
    assert ck.latest_step() == 2
    # crash between the npz and the COMMIT marker: step 2 is torn
    os.remove(tmp_path / "ckpt_00000002.COMMIT")
    # plus a leaked tmp from a killed writer — must not crash the scan
    (tmp_path / "ckpt_00000003.12345.tmp.npz").write_bytes(b"garbage")
    skipped0 = obs_metrics.registry().counter("ckpt.skipped_partial").value
    assert ck.latest_step() == 1             # falls back to newest committed
    assert obs_metrics.registry().counter(
        "ckpt.skipped_partial").value == skipped0 + 1
    assert ck.latest_step() == 1             # rescans count each torn step once
    assert obs_metrics.registry().counter(
        "ckpt.skipped_partial").value == skipped0 + 1
    restored = ck.restore(1, tree)
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_latest_step_none_when_nothing_committed(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer
    ck = Checkpointer(tmp_path)
    ck.save(5, {"w": np.ones(2, np.float32)})
    os.remove(tmp_path / "ckpt_00000005.COMMIT")
    assert ck.latest_step() is None


def test_emergency_save_carries_opt_state(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer, emergency_save
    params = {"w": np.full((4,), 2.0, np.float32)}
    opt = {"m": np.full((4,), 0.5, np.float32)}
    emergency_save(tmp_path, 7, params, opt_state=opt)
    assert Checkpointer(tmp_path).latest_step() == 7
    opt_ck = Checkpointer(tmp_path / "opt")
    assert opt_ck.latest_step() == 7
    np.testing.assert_array_equal(opt_ck.restore(7, opt)["m"], opt["m"])


# ----------------------------------------------------------------------
# End-to-end: SWE kill-and-resume (subprocess, 8 emulated ranks)
# ----------------------------------------------------------------------

# A TuneDB whose MEASURED multi_neighbor rows favor buffered while the
# calibrated model favors streaming at small halo messages: the initial
# (measured) selection and the recovery-time (model) re-selection then
# provably disagree, which is what the config-changed assertions need.
_SPLIT_DB_SNIPPET = """
import dataclasses
from repro.core import latmodel
from repro.core.config import CommConfig, CommMode, V5E
from repro.tune.db import TuneDB, TuneEntry
from repro.tune.space import config_to_dict

def build_split_db(path):
    buf = CommConfig(mode=CommMode.BUFFERED)
    s4k = CommConfig(mode=CommMode.STREAMING, chunk_bytes=4096)
    s1k = CommConfig(mode=CommMode.STREAMING, chunk_bytes=1024)
    hw = dataclasses.replace(V5E, host_dispatch=50e-6, fused_dispatch=2e-6,
                             ici_latency=5e-6, ici_bw=0.25e9, hbm_bw=20e9,
                             ici_hop_latency=20e-6)
    db = TuneDB()
    for topo in ("cpu:8", "cpu:7", "cpu:4"):
        # model-consistent calibration points (what the Eq. 1 fit reads)
        for cfg in (buf, s4k, s1k):
            for size in (4096, 16384, 65536, 1 << 20):
                for hops in (1, 3):
                    sec = latmodel.pingping_latency(size, cfg, hw, hops=hops)
                    db.add(TuneEntry(topo=topo, collective="sendrecv",
                                     msg_bytes=size,
                                     config=config_to_dict(cfg),
                                     us_per_call=sec * 1e6, hops=hops))
        # "measured" rows for the consumers: buffered wins every lookup
        for coll in ("multi_neighbor", "all_reduce"):
            for cfg, us in ((buf, 1.0), (s4k, 100.0), (s1k, 100.0)):
                for size in (256, 4096, 65536, 1 << 20):
                    for hops in (1, 2, 3):
                        db.add(TuneEntry(topo=topo, collective=coll,
                                         msg_bytes=size,
                                         config=config_to_dict(cfg),
                                         us_per_call=us, hops=hops))
    db.save(path)
    return db
"""


def test_swe_kill_and_resume_bitwise(tmp_path):
    """Lose rank 5 at step 10 of 30 on a 4x2 torus: the run recovers onto 7
    survivors with model-re-selected configs (no sweep), the digest stream
    is bitwise-reproducible across two same-seed faulted runs, and the final
    digest matches the no-fault reference."""
    out = run_multidevice(_SPLIT_DB_SNIPPET + f"""
import numpy as np
from repro.core.topology import TorusSpec
from repro.obs import metrics as obs_metrics
from repro.runtime.elastic import run_swe_elastic
from repro.runtime.faults import FaultSchedule

db_path = {str(tmp_path / "tunedb.json")!r}
build_split_db(db_path)
topo = TorusSpec.parse("4x2")
reg = obs_metrics.registry()

ref = run_swe_elastic(300, 8, topo, n_steps=30, segment=10,
                      tune_db_path=db_path)
assert ref.recoveries == [] and ref.n_parts == [8, 8, 8]

sched = FaultSchedule.parse("rank_lost@10=r5")
resel0 = reg.counter("tune.model_reselects", collective="multi_neighbor").value
runs = [run_swe_elastic(300, 8, topo, n_steps=30, segment=10,
                        schedule=sched, tune_db_path=db_path)
        for _ in range(2)]
f1, f2 = runs

# recovery happened, and on the survivors' sub-torus
assert len(f1.recoveries) == 1 and f1.recoveries[0].kind == "rank_lost"
assert f1.n_parts[-1] == 7
# NO sweep ran during recovery (the counter is the witness)
assert f1.sweep_runs_delta == 0 and ref.sweep_runs_delta == 0
# recovery re-selected from the model, and the configs actually changed
assert reg.counter("tune.model_reselects",
                   collective="multi_neighbor").value > resel0
assert f1.recoveries[0].config_changed()
# bitwise-reproducible across two same-seed faulted runs
assert f1.digests == f2.digests
assert f1.final_digest == f2.final_digest
# recovery is value-preserving: same answer as the no-fault reference
assert f1.final_digest == ref.final_digest
print("SWE KILL-RESUME OK", f1.final_digest[:16])
""")
    assert "SWE KILL-RESUME OK" in out


def test_swe_degraded_link_confirm_and_reroute(tmp_path):
    """A degraded link slows the wire physically at once, but routes and
    configs move only after the monitor confirms (hysteresis); the answer
    stays bitwise-identical to the healthy run throughout."""
    out = run_multidevice(_SPLIT_DB_SNIPPET + f"""
import numpy as np
from repro.core.topology import TorusSpec
from repro.runtime.elastic import run_swe_elastic
from repro.runtime.faults import DegradationMonitor, FaultSchedule

db_path = {str(tmp_path / "tunedb.json")!r}
build_split_db(db_path)
topo = TorusSpec.parse("4x2")

ref = run_swe_elastic(300, 8, topo, n_steps=30, segment=5,
                      tune_db_path=db_path)
sched = FaultSchedule.parse("degraded_link@2=0-1x3.0")
runs = [run_swe_elastic(
            300, 8, topo, n_steps=30, segment=5, schedule=sched,
            tune_db_path=db_path,
            monitor=DegradationMonitor(threshold=1.5, hysteresis=2,
                                       cooldown=100))
        for _ in range(2)]
f1, f2 = runs
assert len(f1.recoveries) == 1 and f1.recoveries[0].kind == "degraded_link"
assert "(0, 1)" in f1.recoveries[0].detail
assert f1.sweep_runs_delta == 0
assert f1.n_parts[-1] == 8                  # degraded-but-alive: no shrink
assert f1.digests == f2.digests             # deterministic recovery
# hold rounds and rerouting are value-preserving
assert f1.final_digest == ref.final_digest
print("SWE DEGRADED OK", f1.recoveries[0].detail)
""")
    assert "SWE DEGRADED OK" in out


# ----------------------------------------------------------------------
# End-to-end: LM train loop survives rank loss (subprocess)
# ----------------------------------------------------------------------

def test_lm_rank_loss_elastic_reselect(tmp_path):
    """RANK_LOST mid-train: the loop emergency-checkpoints the last completed
    step, elastic_restore re-forms on the survivors with a model-re-selected
    CommConfig (no sweep), and the whole faulted flow is bitwise-reproducible
    across two same-seed runs."""
    out = run_multidevice(_SPLIT_DB_SNIPPET + f"""
import dataclasses, shutil
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig, CommMode
from repro.core.topology import TorusSpec
from repro.data.pipeline import DataConfig
from repro.launch import setup
from repro.obs import metrics as obs_metrics
from repro.optim import adamw
from repro.runtime.fault_tolerance import elastic_restore
from repro.runtime.faults import FaultInjector, FaultSchedule, RankLostError
from repro.train import loop as loop_mod

db_path = {str(tmp_path / "tunedb.json")!r}
build_split_db(db_path)
cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype=jnp.float32)
oc = adamw.OptConfig(lr=1e-3, zero1=False)
comm = CommConfig(mode=CommMode.BUFFERED)
data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
topo = TorusSpec.parse("4x2")
reg = obs_metrics.registry()
sweeps0 = reg.counter("sweep.runs").value

def faulted_run(ckpt_dir):
    mesh = make_mesh((8, 1), ("data", "model"))
    sess = setup.build_session(cfg, mesh, comm, oc=oc)
    inj = FaultInjector(FaultSchedule.parse("rank_lost@3=r7"))
    losses = []
    try:
        loop_mod.train(sess, data,
                       loop_mod.LoopConfig(n_steps=10, ckpt_every=100,
                                           ckpt_dir=ckpt_dir, log_every=100),
                       log=lambda *_: None, faults=inj)
        raise AssertionError("rank loss never fired")
    except RankLostError as e:
        assert e.rank == 7 and e.step == 3
    # the loop drained an emergency checkpoint before unwinding
    from repro.checkpoint.checkpointer import Checkpointer
    assert Checkpointer(ckpt_dir).latest_step() == 3
    # survivors: 4 devices; recovery re-selects from the model, not a sweep
    mesh2 = make_mesh((4, 1), ("data", "model"))
    sess2, start = elastic_restore(ckpt_dir, cfg, mesh2, comm, oc,
                                   reselect=True, tune_db_path=db_path,
                                   topology=topo)
    assert start == 3
    hist = loop_mod.train(sess2, data,
                          loop_mod.LoopConfig(n_steps=3, ckpt_every=100,
                                              ckpt_dir=None, log_every=100),
                          log=lambda *_: None)
    return sess2.rt.comm, hist

cc1, h1 = faulted_run({str(tmp_path / "ck1")!r})
cc2, h2 = faulted_run({str(tmp_path / "ck2")!r})

# the survivors' config was re-selected by the model and actually differs
# from the dead mesh's config
assert cc1.mode != comm.mode, (cc1, comm)
assert cc1 == cc2
assert reg.counter("tune.model_reselects", collective="all_reduce").value >= 2
assert reg.counter("sweep.runs").value == sweeps0     # never swept
# bitwise-reproducible post-recovery loss stream across same-seed runs
assert h1 == h2, (h1, h2)
assert all(np.isfinite(h1))
print("LM RANK-LOSS OK", cc1.mode.value, [round(x, 4) for x in h1])
""")
    assert "LM RANK-LOSS OK" in out


# ----------------------------------------------------------------------
# End-to-end: preemption drain + fresh-process resume (subprocess x2)
# ----------------------------------------------------------------------

_TRAIN_COMMON = """
import dataclasses, json
import jax, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs.registry import get_smoke_config
from repro.core.config import CommConfig
from repro.data.pipeline import DataConfig
from repro.launch import setup
from repro.optim import adamw
from repro.train import loop as loop_mod

cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype=jnp.float32)
oc = adamw.OptConfig(lr=1e-3, zero1=False)
data = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)

def fresh_session():
    mesh = make_mesh((1, 1), ("data", "model"))
    return setup.build_session(cfg, mesh, CommConfig(), oc=oc)
"""


def test_preemption_drain_then_fresh_process_resumes(tmp_path):
    """guard.request() drains an emergency checkpoint (params + opt state)
    at the step boundary; a FRESH PROCESS resumes at the same step and the
    combined loss stream is bitwise-identical to the uninterrupted run."""
    ck = tmp_path / "ck"
    # phase 1: reference run + drained run, in one process
    run_multidevice(_TRAIN_COMMON + f"""
from repro.runtime.faults import FaultInjector, FaultSchedule

ref = loop_mod.train(fresh_session(), data,
                     loop_mod.LoopConfig(n_steps=8, ckpt_every=100,
                                         log_every=100),
                     log=lambda *_: None)

# Preempt@4 -> guard.request() -> the loop drains at the step-4 boundary
inj = FaultInjector(FaultSchedule.parse("preempt@4"))
part1 = loop_mod.train(fresh_session(), data,
                       loop_mod.LoopConfig(n_steps=8, ckpt_every=100,
                                           ckpt_dir={str(ck)!r},
                                           log_every=100),
                       log=lambda *_: None, faults=inj)
assert len(part1) == 4, len(part1)
from repro.checkpoint.checkpointer import Checkpointer
assert Checkpointer({str(ck)!r}).latest_step() == 4
json.dump({{"ref": ref, "part1": part1}},
          open({str(tmp_path / "phase1.json")!r}, "w"))
print("PHASE1 OK")
""", n_devices=1)
    # phase 2: a fresh process resumes from the drained checkpoint
    out = run_multidevice(_TRAIN_COMMON + f"""
from repro.runtime.fault_tolerance import resume_session

sess, start = resume_session({str(ck)!r}, fresh_session())
assert start == 4
part2 = loop_mod.train(sess, data,
                       loop_mod.LoopConfig(n_steps=4, ckpt_every=100,
                                           log_every=100),
                       log=lambda *_: None)
saved = json.load(open({str(tmp_path / "phase1.json")!r}))
resumed = saved["part1"] + part2
assert len(resumed) == len(saved["ref"]) == 8
# opt state rode the drain: the resumed stream is bitwise identical
assert resumed == saved["ref"], (resumed, saved["ref"])
print("RESUME OK", [round(x, 4) for x in part2])
""", n_devices=1)
    assert "RESUME OK" in out
