"""Reliable wire transport: protocol simulation + bitwise parity harness.

Three layers of guarantee for :mod:`repro.core.reliable`:

1. Host-side protocol properties: the send-window / ack-timeout /
   retransmit / backoff simulation delivers every chunk exactly once in
   order, under ANY in-window drop/reorder/dup pattern (hypothesis), with
   monotone capped backoff and honest counters.
2. Fault-schedule determinism: seeded :class:`WireFaults` replay
   identically and reject malformed rates.
3. Bitwise parity (subprocess, 4 emulated devices): every streaming path
   (chunked / buffered / pipelined) x scheduling x fault pattern produces
   values identical to the lossless reference, with the wire counters
   attesting that recovery really fired (and stayed silent on the clean
   runs — the zero-fault fast path).
"""
import pytest

from helpers import require_hypothesis, run_multidevice

from repro.core import reliable
from repro.core.config import CommConfig, Reliability
from repro.obs import metrics as obs_metrics


def _plan(n, drops=(), dups=(), order=None, **kw):
    args = dict(window=4, ack_timeout=2, max_retransmits=4,
                backoff_base=1, backoff_cap=4)
    args.update(kw)
    return reliable.simulate_delivery(n, drops=frozenset(drops),
                                      dups=frozenset(dups), order=order,
                                      **args)


# ----------------------------------------------------------------------
# Protocol simulation
# ----------------------------------------------------------------------

def test_clean_message_is_trivial_in_order():
    plan = _plan(6)
    assert [s.action for s in plan.slots] == [reliable.DELIVER] * 6
    assert [s.seq for s in plan.slots] == list(range(6))
    assert plan.retransmits == plan.dup_dropped == plan.timeouts == 0
    assert plan.backoff_holds == 0 and plan.extra_slots == 0


def test_drop_costs_timeout_backoff_and_retransmit():
    plan = _plan(4, drops=[(1, 0)])
    assert plan.retransmits == 1
    assert plan.timeouts == 1
    assert plan.backoff_holds >= 1          # capped-exponential hold rounds
    assert plan.extra_slots > 0             # recovery has a latency price
    assert sorted(plan.delivered_seqs()) == list(range(4))
    actions = [s.action for s in plan.slots]
    assert reliable.LOST in actions and reliable.HOLD in actions


def test_dup_is_dropped_by_receiver_dedup():
    plan = _plan(4, dups=[2])
    assert plan.dup_dropped == 1
    assert plan.retransmits == 0
    assert sorted(plan.delivered_seqs()) == list(range(4))


def test_dropped_duplicate_of_delivered_chunk_terminates():
    # Regression: chunk 0's original is dropped, its retransmit delivers,
    # and only then does the queued wire-duplicate drain — and the wire
    # drops that too.  The lost dup copy must not resurrect chunk 0 into
    # the unacked set (the retransmit loop would spin forever: every retry
    # deduped, the state never cleared).
    plan = _plan(8, window=2, ack_timeout=1, backoff_cap=2,
                 drops=[(1, 0), (4, 0), (0, 0)], dups=[0, 1, 3],
                 order=(0, 2, 1, 3, 4, 5, 6, 7))
    assert sorted(plan.delivered_seqs()) == list(range(8))
    assert plan.retransmits >= 1 and plan.dup_dropped >= 1


def test_reorder_still_reassembles_in_order():
    plan = _plan(5, order=(4, 3, 2, 1, 0))
    assert plan.retransmits == 0
    assert sorted(s.seq for s in plan.slots
                  if s.action == reliable.DELIVER) == list(range(5))
    assert plan.delivered_seqs() == [4, 3, 2, 1, 0]  # wire arrival order


def test_undeliverable_drop_pattern_raises():
    # every attempt of chunk 0 dropped -> exceeds the retransmit cap
    drops = [(0, a) for a in range(6)]
    with pytest.raises(ValueError, match="undeliverable"):
        _plan(2, drops=drops, max_retransmits=4)


def test_order_must_be_a_permutation():
    with pytest.raises(ValueError):
        _plan(3, order=(0, 0, 2))


def test_backoff_monotone_and_capped():
    prev = 0
    for attempt in range(1, 10):
        h = reliable.backoff_holds(attempt, 1, 4)
        assert h >= prev
        assert h <= 4
        prev = h
    assert reliable.backoff_holds(1, 1, 64) == 1
    assert reliable.backoff_holds(4, 1, 64) == 8
    with pytest.raises(ValueError):
        reliable.backoff_holds(0, 1, 4)


def test_window_stalls_without_acks():
    # window=1 + ordered delivery: chunk i+1 cannot launch before chunk i
    # is acked, so a drop of chunk 0 stalls the whole message.
    plan = _plan(3, drops=[(0, 0)], window=1)
    deliver_pos = [i for i, s in enumerate(plan.slots)
                   if s.action == reliable.DELIVER]
    seqs = [plan.slots[i].seq for i in deliver_pos]
    assert seqs == sorted(seqs)             # strictly in-order launches


# ----------------------------------------------------------------------
# WireFaults determinism + plan memoization
# ----------------------------------------------------------------------

def test_wire_faults_deterministic_and_validated():
    a = reliable.WireFaults(seed=3, drop=0.3, dup=0.1, reorder=0.2)
    b = reliable.WireFaults(seed=3, drop=0.3, dup=0.1, reorder=0.2)
    for msg in range(8):
        assert a.outcomes(msg, 6, 4) == b.outcomes(msg, 6, 4)
    # seeded drops never exhaust the retransmit budget (wire relents)
    heavy = reliable.WireFaults(seed=0, drop=0.9)
    for msg in range(16):
        drops, _, _ = heavy.outcomes(msg, 4, 3)
        assert all(a < 3 for _, a in drops)
    with pytest.raises(ValueError, match="rate"):
        reliable.WireFaults(drop=1.0)
    with pytest.raises(ValueError, match="rate"):
        reliable.WireFaults(reorder=-0.1)


def test_plan_for_fast_path_and_best_effort_guard():
    cfg = CommConfig(reliability=Reliability.GUARANTEED)
    assert reliable.plan_for(cfg, 4) is None          # no faults injected
    faults = reliable.WireFaults(seed=0, drop_events=frozenset({(0, 0, 0)}))
    with reliable.inject(faults):
        plan = reliable.plan_for(cfg, 4)
        assert plan is not None and plan.retransmits == 1
    with reliable.inject(faults):
        with pytest.raises(ValueError, match="best_effort|BEST_EFFORT"):
            reliable.plan_for(CommConfig(), 4)
    assert reliable.active() is None                  # context restored


def test_delivery_plan_memoized():
    reg = obs_metrics.registry()
    cfg = CommConfig(reliability=Reliability.GUARANTEED)
    drops = frozenset({(0, 0)})
    reliable.delivery_plan(64, cfg, drops, frozenset(), tuple(range(64)))
    hits0 = reg.counter("plans.plan_hits").value
    p1 = reliable.delivery_plan(64, cfg, drops, frozenset(),
                                tuple(range(64)))
    p2 = reliable.delivery_plan(64, cfg, drops, frozenset(),
                                tuple(range(64)))
    assert p1 is p2
    assert reg.counter("plans.plan_hits").value >= hits0 + 2


# ----------------------------------------------------------------------
# Hypothesis: any in-window fault pattern reassembles to identity
# ----------------------------------------------------------------------

def test_property_delivery_identity_under_faults():
    hypothesis = require_hypothesis()
    from hypothesis import given, settings, strategies as st

    @st.composite
    def fault_case(draw):
        n = draw(st.integers(1, 12))
        max_rt = draw(st.integers(1, 4))
        drops = set()
        for seq in range(n):
            # a contiguous run of failed attempts, within the cap
            k = draw(st.integers(0, max_rt))
            drops.update((seq, a) for a in range(k))
        dups = draw(st.sets(st.integers(0, n - 1), max_size=n))
        order = draw(st.permutations(list(range(n))))
        window = draw(st.integers(1, 8))
        return n, max_rt, drops, dups, tuple(order), window

    @given(fault_case())
    @settings(max_examples=120, deadline=None)
    def check(case):
        n, max_rt, drops, dups, order, window = case
        plan = reliable.simulate_delivery(
            n, window=window, ack_timeout=2, max_retransmits=max_rt,
            backoff_base=1, backoff_cap=4,
            drops=frozenset(drops), dups=frozenset(dups), order=order)
        # exactly-once reassembly: arrival order is a permutation
        assert sorted(plan.delivered_seqs()) == list(range(n))
        delivered = [s.seq for s in plan.slots
                     if s.action == reliable.DELIVER]
        assert sorted(delivered) == list(range(n))
        assert len(delivered) == n                    # dedup: exactly once
        # counters are honest
        assert plan.retransmits == sum(
            1 for s in plan.slots
            if s.attempt > 0 and s.action in (reliable.DELIVER,
                                              reliable.LOST))
        assert plan.extra_slots == len(plan.slots) - n

    check()


def test_property_backoff_monotone_capped():
    require_hypothesis()
    from hypothesis import given, settings, strategies as st

    @given(st.integers(1, 16), st.integers(0, 8), st.integers(0, 64))
    @settings(max_examples=200, deadline=None)
    def check(attempt, base, cap):
        cap = max(cap, base)                 # config invariant
        h = reliable.backoff_holds(attempt, base, cap)
        assert 0 <= h <= cap or h == base    # capped
        assert h <= cap
        if attempt > 1:
            assert h >= reliable.backoff_holds(attempt - 1, base, cap)

    check()


# ----------------------------------------------------------------------
# Bitwise parity matrix (subprocess, 4 emulated devices)
# ----------------------------------------------------------------------

def test_reliable_parity_matrix_bitwise():
    out = run_multidevice("""
import itertools
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.core import reliable, streaming
from repro.core.config import (CommConfig, CommMode, Reliability,
                               Scheduling, Transport)
from repro.obs import metrics as obs_metrics

mesh = make_mesh((4,), ("x",))
perm = [(i, (i + 1) % 4) for i in range(4)]
N = 8 * 128
x = jnp.arange(4 * N, dtype=jnp.float32).reshape(4, N) * 0.37 + 1.0

# Each traced run sends exactly one message (msg 0), so every explicit
# event pins msg 0.  Out-of-range seqs (e.g. seq 2 on the 1-chunk buffered
# path) are harmless: the protocol never transmits them.  Reorder uses the
# seeded rate, not an explicit order, because an explicit order must match
# the path's chunk count — and buffered's single chunk cannot reorder.
FAULTS = {
    "clean": None,
    "drop": reliable.WireFaults(seed=1, drop_events=frozenset(
        {(0, 0, 0), (0, 2, 0), (0, 2, 1)})),
    "reorder": reliable.WireFaults(seed=1, reorder=0.9),
    "dup": reliable.WireFaults(seed=1, dup_events=frozenset(
        {(0, 0), (0, 3)})),
    "combined": reliable.WireFaults(seed=1, drop=0.25, dup=0.2,
                                    reorder=0.3,
                                    drop_events=frozenset({(0, 0, 0)}),
                                    dup_events=frozenset({(0, 0)})),
}

def run(path, cfg):
    spec = jax.sharding.PartitionSpec("x")
    if path == "chunked":
        body = lambda v: streaming.chunked_permute(v[0], perm, "x",
                                                   cfg)[None]
    elif path == "buffered":
        body = lambda v: streaming.buffered_permute(v[0], perm, "x",
                                                    cfg)[None]
    else:
        def body(v):
            carry, msg = streaming.pipelined_consume(
                v[0], perm, "x", cfg,
                consume=lambda c, i, m: c + jnp.sum(m),
                init=jnp.float32(0.0))
            return (msg + carry)[None]
    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=spec, out_specs=spec,
                              check_vma=False))
    return np.asarray(f(x))

reg = obs_metrics.registry()
checked = 0
for path, sched in itertools.product(
        ("chunked", "buffered", "pipelined"),
        (Scheduling.FUSED, Scheduling.OVERLAPPED)):
    base = CommConfig(mode=CommMode.STREAMING, scheduling=sched,
                      transport=Transport.UNORDERED, window=2,
                      chunk_bytes=512)
    ref = run(path, base)
    for fname, faults in FAULTS.items():
        cfg = CommConfig(mode=CommMode.STREAMING, scheduling=sched,
                         transport=Transport.UNORDERED, window=2,
                         chunk_bytes=512,
                         reliability=Reliability.GUARANTEED,
                         ack_timeout=1, max_retransmits=4,
                         backoff_base=1, backoff_cap=2)
        before = reliable.wire_counters()
        with reliable.inject(faults):
            got = run(path, cfg)
        after = reliable.wire_counters()
        d = {k: after[k] - before[k] for k in after}
        assert np.array_equal(ref, got), (path, sched, fname)
        if fname == "clean":
            assert all(v == 0 for v in d.values()), (path, sched, d)
        elif fname == "drop":
            assert d["retransmits"] > 0, (path, sched, d)
        elif fname == "dup":
            assert d["dup_dropped"] > 0, (path, sched, d)
        elif fname == "reorder":
            if path == "buffered":
                # a 1-chunk message cannot reorder: stays on the fast path
                assert d["messages_recovered"] == 0, (path, sched, d)
            else:
                assert d["messages_recovered"] > 0, (path, sched, d)
        else:
            assert d["retransmits"] > 0, (path, sched, d)
            # On the 1-chunk buffered path the pinned (0,0,0) drop also
            # swallows the duplicate copy (dups transmit at attempt 0),
            # so only the retransmit witness is guaranteed there.
            if path != "buffered":
                assert d["dup_dropped"] > 0, (path, sched, d)
        checked += 1
print("PARITY MATRIX OK", checked)
""", n_devices=4)
    assert "PARITY MATRIX OK 30" in out
