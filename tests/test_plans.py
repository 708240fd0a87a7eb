"""CommPlan cache: keying, hit/miss accounting, no-retrace replay, and
bitwise parity of cached vs uncached execution across the scheduling x
transport matrix."""
import numpy as np
import pytest

from helpers import require_hypothesis, run_multidevice


# ----------------------------------------------------------------------
# Keying: what hits and what misses
# ----------------------------------------------------------------------

def _fresh_plans():
    from repro.core import plans
    plans.clear_cache()
    plans.reset_stats()
    return plans


def test_plan_keying_hits_and_misses():
    """Identical call hits; config, shape, dtype, and communicator changes
    each miss."""
    import dataclasses
    plans = _fresh_plans()
    from repro.core.communicator import Communicator
    from repro.core.config import CommConfig, Transport

    cfg = CommConfig(chunk_bytes=1 << 12)
    comm = Communicator(("x",), (8,))

    p1 = plans.get_plan("sendrecv", comm, cfg, (1024,), np.float32)
    assert plans.cache_stats()["plan_misses"] == 1
    p2 = plans.get_plan("sendrecv", comm, cfg, (1024,), np.float32)
    assert p2 is p1                          # identical call -> hit
    assert plans.cache_stats()["plan_hits"] == 1

    # a fresh-but-equal communicator still hits (value keying, not identity)
    p2b = plans.get_plan("sendrecv", Communicator(("x",), (8,)), cfg,
                         (1024,), np.float32)
    assert p2b is p1

    # each of these must MISS
    before = plans.cache_stats()["plan_misses"]
    plans.get_plan("sendrecv", comm,
                   dataclasses.replace(cfg, transport=Transport.ORDERED),
                   (1024,), np.float32)                       # config change
    plans.get_plan("sendrecv", comm, cfg, (2048,), np.float32)  # shape change
    plans.get_plan("sendrecv", comm, cfg, (1024,), np.int8)     # dtype change
    plans.get_plan("sendrecv", Communicator(("y",), (4,)), cfg,
                   (1024,), np.float32)                       # comm change
    plans.get_plan("all_reduce", comm, cfg, (1024,), np.float32)  # collective
    assert plans.cache_stats()["plan_misses"] == before + 5


def test_plan_keying_distinct_inputs_never_alias():
    """Hypothesis property: two get_plan calls differing in ANY component —
    collective, communicator axes/sizes, **topology spec** (shape, per-hop
    cost, placement), config, shape, or dtype — must never return the same
    cached plan object; identical inputs always must."""
    hypothesis = require_hypothesis()
    from hypothesis import given, settings, strategies as st

    import dataclasses
    plans = _fresh_plans()
    from repro.core.communicator import Communicator
    from repro.core.config import CommConfig, Transport
    from repro.core.topology import TorusSpec, snake_placement

    specs = st.one_of(
        st.none(),
        st.builds(lambda shape, hop, snake: TorusSpec(
            shape, per_hop_ns=hop,
            placement=snake_placement(shape) if snake else None),
            st.sampled_from([(2, 4), (4, 2), (1, 8), (2, 2)]),
            st.sampled_from([250.0, 500.0]),
            st.booleans()))

    inputs = st.tuples(
        st.sampled_from(["sendrecv", "multi_neighbor", "all_reduce"]),
        st.sampled_from([("x",), ("y",)]),
        specs,
        st.sampled_from([1 << 12, 1 << 16]),        # chunk_bytes
        st.sampled_from(list(Transport)),
        st.sampled_from([(256,), (1024,), (64, 3)]),
        st.sampled_from(["float32", "int8"]),
    )

    def build(inp):
        coll, axes, spec, chunk, transport, shape, dtype = inp
        n = spec.n_ranks if spec is not None else 8
        comm = Communicator(axes, (n,), topo=spec)
        cfg = CommConfig(chunk_bytes=chunk, transport=transport)
        return plans.get_plan(coll, comm, cfg, shape, np.dtype(dtype))

    @settings(max_examples=60, deadline=None)
    @given(a=inputs, b=inputs)
    def prop(a, b):
        pa, pb = build(a), build(b)
        if a == b:
            assert pa is pb
        else:
            assert pa is not pb
        # and replay is stable
        assert build(a) is pa

    prop()


def test_chunk_plan_matches_streaming_layouts():
    """The cached layouts replay exactly what the engines derived inline:
    equal_split == split_chunks/num_chunks, aligned == aligned_chunks."""
    import math
    plans = _fresh_plans()
    import jax.numpy as jnp
    from repro.core import streaming
    from repro.core.config import CommConfig, Transport

    rng = np.random.RandomState(0)
    for _ in range(30):
        size = int(rng.randint(1, 5000))
        align = int(rng.choice([1, 3, 7, 16]))
        cfg = CommConfig(chunk_bytes=int(rng.choice([512, 2048, 1 << 16])),
                         max_chunks=int(rng.choice([2, 8, 16])),
                         transport=Transport.ORDERED,
                         window=int(rng.choice([1, 2, 4])))
        x = jnp.zeros((size,), jnp.float32)
        n_ref = streaming.num_chunks(size * 4, cfg)
        p_eq = plans.chunk_plan((size,), np.float32, cfg, equal_split=True)
        assert p_eq.n_chunks == n_ref
        assert p_eq.chunk_elems == math.ceil(size / n_ref)
        n_al, elems_al = streaming.aligned_chunks(x, cfg, align=align)
        p_al = plans.chunk_plan((size,), np.float32, cfg, align=align)
        assert (p_al.n_chunks, p_al.chunk_elems) == (n_al, elems_al)
        assert elems_al % align == 0
        # ack structure mirrors the ordered-transport window rule
        for i, a in enumerate(p_eq.ack_of):
            assert a == (i - cfg.window if i >= cfg.window else -1)


def test_edge_rounds_and_ring_perm_cached():
    plans = _fresh_plans()
    from repro.core.collectives import edge_color_rounds
    from repro.core.communicator import Communicator

    edges = [(0, 1), (1, 2), (0, 2), (3, 0)]
    r1 = edge_color_rounds(edges)
    r2 = edge_color_rounds(list(edges))
    assert r1 is r2
    # every edge exactly once, every round ppermute-valid
    flat = [e for r in r1 for e in r]
    assert sorted(flat) == sorted(edges)
    for r in r1:
        assert len({s for s, _ in r}) == len(r)
        assert len({d for _, d in r}) == len(r)

    comm = Communicator(("x",), (8,))
    assert comm.ring_perm() == [(i, (i + 1) % 8) for i in range(8)]
    assert comm.reverse_ring_perm(2) == [(i, (i - 2) % 8) for i in range(8)]


def test_validated_perm_still_rejects_invalid():
    """Caching must not swallow the validation errors."""
    plans = _fresh_plans()
    from repro.core.communicator import Communicator
    comm = Communicator(("x",), (4,))
    with pytest.raises(ValueError):
        plans.validated_perm(comm, [(0, 1), (0, 2)])   # duplicate source
    with pytest.raises(ValueError):
        plans.validated_perm(comm, [(0, 9)])           # outside communicator
    assert plans.validated_perm(comm, [(0, 1), (1, 0)]) == ((0, 1), (1, 0))


def test_cache_bypass_env(monkeypatch):
    plans = _fresh_plans()
    from repro.core.config import CommConfig
    monkeypatch.setenv("REPRO_PLAN_CACHE", "0")
    p1 = plans.chunk_plan((100,), np.float32, CommConfig())
    p2 = plans.chunk_plan((100,), np.float32, CommConfig())
    assert p1 is not p2 and p1 == p2       # re-derived, identical values
    assert plans.cache_stats()["plan_hits"] == 0
    monkeypatch.delenv("REPRO_PLAN_CACHE")
    p3 = plans.chunk_plan((100,), np.float32, CommConfig())
    p4 = plans.chunk_plan((100,), np.float32, CommConfig())
    assert p3 is p4


def test_memo_caches_none_result():
    """Regression: a build that legitimately returns None (or any falsy
    value) must be cached like everything else — the old truthiness check
    turned it into a perpetual miss that re-ran the build every call."""
    plans = _fresh_plans()
    calls = []

    def build():
        calls.append(1)
        return None

    r1 = plans._memo("regress", ("none-key",), build,
                     "plan_hits", "plan_misses")
    r2 = plans._memo("regress", ("none-key",), build,
                     "plan_hits", "plan_misses")
    assert r1 is None and r2 is None
    assert len(calls) == 1
    st = plans.cache_stats()
    assert st["plan_hits"] == 1 and st["plan_misses"] == 1


# ----------------------------------------------------------------------
# Jitted-program replay: no retrace on the second call
# ----------------------------------------------------------------------

def test_jitted_program_no_retrace_on_second_call():
    """Trace-count probe: the builder (and the trace it wraps) runs once;
    the second call replays the cached program."""
    plans = _fresh_plans()
    import jax
    import jax.numpy as jnp

    traces = []

    def build():
        def f(x):
            traces.append(1)          # python side effect = one trace
            return x * 2.0
        return jax.jit(f)

    x = jnp.arange(8.0)
    f1 = plans.jitted_program(("probe", 8), build)
    y1 = f1(x)
    f2 = plans.jitted_program(("probe", 8), build)
    y2 = f2(x)
    assert f1 is f2
    assert len(traces) == 1            # no retrace on the second call
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    stats = plans.cache_stats()
    assert stats["program_hits"] == 1 and stats["program_misses"] == 1
    # a different key is a different program
    plans.jitted_program(("probe", 16), build)(x)
    assert len(traces) == 2


def test_commplan_program_replay():
    plans = _fresh_plans()
    import jax
    import jax.numpy as jnp
    from repro.core.config import CommConfig

    plan = plans.get_plan("all_reduce", None, CommConfig(), (8,), np.float32)
    builds = []

    def build():
        builds.append(1)
        return jax.jit(lambda v: v + 1.0)

    p1 = plan.program(build)
    p2 = plan.program(build)
    assert p1 is p2 and len(builds) == 1
    assert float(p1(jnp.zeros(()))) == 1.0


def test_commplan_program_race_builds_once():
    """Regression: CommPlan.program's check-then-set must hold the cache
    lock — concurrent same-key callers used to race past the check and each
    run the (expensive) build."""
    import threading
    import time
    plans = _fresh_plans()
    import jax
    from repro.core.config import CommConfig

    plan = plans.get_plan("all_reduce", None, CommConfig(), (8,), np.float32)
    plans.reset_stats()
    builds = []

    def build():
        builds.append(1)
        time.sleep(0.05)               # widen the race window
        return jax.jit(lambda v: v + 1.0)

    barrier = threading.Barrier(4)
    results = []

    def worker():
        barrier.wait()
        results.append(plan.program(build))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(builds) == 1
    assert all(r is results[0] for r in results)
    st = plans.cache_stats()
    assert st["program_misses"] == 1 and st["program_hits"] == 3


# ----------------------------------------------------------------------
# Bitwise parity: cached vs uncached across scheduling x transport
# ----------------------------------------------------------------------

def test_cached_vs_uncached_bitwise_parity_matrix():
    """Every (scheduling, transport) combination of sendrecv, multi-neighbor
    exchange, and ring all-reduce must produce bit-identical results with
    the plan cache enabled and bypassed (REPRO_PLAN_CACHE=0)."""
    out = run_multidevice("""
import os
import numpy as np
import jax
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import plans
from repro.core.config import (CommConfig, CommMode, Scheduling, Transport)
from repro.core.communicator import Communicator
from repro.core import collectives

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(0).randn(8, 130).astype(np.float32)

def run_all(cfg):
    results = []
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def p2p(xs):
        return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]
    results.append(np.asarray(p2p(x)))
    rounds = [comm.ring_perm(1), comm.reverse_ring_perm(1), comm.ring_perm(2)]
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def mn(xs):
        outs = collectives.multi_neighbor_exchange(
            [xs[0]] * len(rounds), rounds, comm, cfg)
        return sum(outs)[None]
    results.append(np.asarray(mn(x)))
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    def ar(xs):
        import dataclasses
        rcfg = dataclasses.replace(cfg, algorithm="ring")
        return collectives.all_reduce(xs[0], comm, rcfg)[None]
    results.append(np.asarray(ar(x)))
    return results

# HOST scheduling lowers the same per-op programs as FUSED (dispatch
# granularity is a caller concern), so FUSED x OVERLAPPED x transports x
# modes covers every distinct traced path.
for mode in (CommMode.STREAMING, CommMode.BUFFERED):
    for sched in (Scheduling.FUSED, Scheduling.OVERLAPPED):
        for tr in (Transport.ORDERED, Transport.UNORDERED):
            cfg = CommConfig(mode=mode, scheduling=sched, transport=tr,
                             chunk_bytes=512, window=2)
            os.environ.pop("REPRO_PLAN_CACHE", None)
            plans.clear_cache(); plans.reset_stats()
            cached = run_all(cfg)
            # the multi-round exchange replays the same chunk/perm plans
            # within one run: the cache was exercised, not bypassed
            assert plans.cache_stats()["plan_hits"] > 0, (mode, sched, tr)
            os.environ["REPRO_PLAN_CACHE"] = "0"
            plans.clear_cache()
            bypassed = run_all(cfg)
            os.environ.pop("REPRO_PLAN_CACHE", None)
            for a, c in zip(cached, bypassed):
                assert a.tobytes() == c.tobytes(), (mode, sched, tr)
print("PLAN PARITY OK")
""", timeout=540)
    assert "PLAN PARITY OK" in out


# ----------------------------------------------------------------------
# Warm sweep: the plan cache must make the second sweep cheaper
# ----------------------------------------------------------------------

def test_warm_sweep_reuses_programs_and_is_faster():
    out = run_multidevice("""
from repro.launch.mesh import make_mesh
from repro.core import plans
from repro.tune import TuneDB, run_sweep

mesh = make_mesh((8,), ("x",))
cold, warm = {}, {}
db = run_sweep(mesh=mesh, collectives=("sendrecv",), sizes=(1024,),
               fast=True, max_configs=4, reps=1, inner=2, stats=cold)
db = run_sweep(mesh=mesh, collectives=("sendrecv",), sizes=(1024,),
               fast=True, max_configs=4, reps=1, inner=2, db=db, stats=warm)
assert cold["program_misses"] > 0 and cold["program_hits"] == 0, cold
assert warm["program_hits"] >= cold["program_misses"], (cold, warm)
assert warm["program_misses"] == 0, warm
# wall clock: warm must be at least 30% lower (it skips every compile)
assert warm["wall_s"] < 0.7 * cold["wall_s"], (cold["wall_s"], warm["wall_s"])
print("WARM SWEEP OK", round(cold["wall_s"], 2), round(warm["wall_s"], 2))
""", timeout=540)
    assert "WARM SWEEP OK" in out


# ----------------------------------------------------------------------
# Disk store: a FRESH PROCESS warm-starts from REPRO_PLAN_DIR, bit-identical
# ----------------------------------------------------------------------

_DISK_PARITY_CODE = """
import hashlib
import dataclasses
import numpy as np
import jax
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import plans, collectives
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, CommMode, Scheduling, Transport

plans.reset_stats()
mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x")
x = np.random.RandomState(0).randn(8, 130).astype(np.float32)
cfg = CommConfig(mode=CommMode.STREAMING, scheduling=Scheduling.FUSED,
                 transport=Transport.ORDERED, chunk_bytes=512, window=2)

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
def p2p(xs):
    return collectives.sendrecv(xs[0], comm.ring_perm(), comm, cfg)[None]

rcfg = dataclasses.replace(cfg, algorithm="ring")

@partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
def ar(xs):
    return collectives.all_reduce(xs[0], comm, rcfg)[None]

outs = [np.asarray(p2p(x)), np.asarray(ar(x))]
digest = hashlib.sha256(b"".join(o.tobytes() for o in outs)).hexdigest()
st = plans.cache_stats()
print("DIGEST", digest)
print("DISK", st["disk_hits"], st["disk_misses"], st["disk_writes"])
"""


def _parse_parity(out):
    lines = dict(l.split(" ", 1) for l in out.splitlines()
                 if l.startswith(("DIGEST", "DISK")))
    hits, misses, writes = (int(v) for v in lines["DISK"].split())
    return lines["DIGEST"], hits, misses, writes


def test_disk_store_cross_process_warm_start_bitwise(tmp_path, monkeypatch):
    """The PR's acceptance criterion: a fresh process pointed at a populated
    REPRO_PLAN_DIR reports disk hits and produces bit-identical collective
    results — and both match a run with the cache bypassed entirely."""
    monkeypatch.setenv("REPRO_PLAN_DIR", str(tmp_path / "store"))

    cold_digest, cold_hits, _, cold_writes = _parse_parity(
        run_multidevice(_DISK_PARITY_CODE))
    assert cold_hits == 0 and cold_writes > 0       # populated the store

    warm_digest, warm_hits, _, _ = _parse_parity(
        run_multidevice(_DISK_PARITY_CODE))         # fresh process, warm disk
    assert warm_hits > 0, "fresh process must warm-start from the store"
    assert warm_digest == cold_digest               # bitwise parity

    monkeypatch.setenv("REPRO_PLAN_CACHE", "0")     # disk + memory bypassed
    bypass_digest, bypass_hits, _, bypass_writes = _parse_parity(
        run_multidevice(_DISK_PARITY_CODE))
    assert bypass_hits == 0 and bypass_writes == 0
    assert bypass_digest == cold_digest
