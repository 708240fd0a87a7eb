"""Virtual multi-hop torus transport: the collective-conformance harness.

Three layers of guarantees, mirroring ACCL+'s per-topology conformance
matrix:

1. **Topology math** (host-side): placements, hop distances, routes, and the
   translation perms of the hop-distance sweep axis — up to the paper's 48
   ranks (a 6x8 torus).
2. **Bitwise parity** (8 host devices): every perm-based collective
   (sendrecv, multi-neighbor exchange, ring all-reduce) plus all_to_all and
   the hierarchical all-reduce produce bit-identical values on a torus-placed
   communicator vs the flat mesh, across torus shapes x placements x
   transports x scheduling modes, with and without the plan cache.
3. **Per-edge selection** (deterministic model timer): a >= 3-hop-distance
   sweep records ``TuneEntry.hops`` per measured edge and makes
   ``select_config(hops=...)`` return *different* winners per edge — the
   jumbo-segment config wins the direct link, small segments win the routed
   edge (chunk wormholing) — and the SWE driver turns that into distinct
   per-round configs.
"""
import dataclasses

import numpy as np
import pytest

from helpers import run_multidevice


# ----------------------------------------------------------------------
# Topology math (host-side, up to 48 ranks)
# ----------------------------------------------------------------------

def test_torus_spec_parse_and_validation():
    from repro.core.topology import TorusSpec, snake_placement

    spec = TorusSpec.parse("4x4")
    assert spec.shape == (4, 4) and spec.n_ranks == 16
    assert spec.diameter == 4
    snake = TorusSpec.parse("2x4:snake")
    assert snake.placement == snake_placement((2, 4))
    assert snake.name == "2x4:snake" and spec.name == "4x4"
    with pytest.raises(ValueError):
        TorusSpec.parse("4by4")
    with pytest.raises(ValueError):
        TorusSpec.parse("4x4:spiral")
    with pytest.raises(ValueError):
        TorusSpec((2, 4), placement=(0, 1, 2, 3, 4, 5, 6, 6))
    with pytest.raises(ValueError):
        TorusSpec((0, 4))


def test_torus_hops_and_placement():
    from repro.core.topology import TorusSpec, snake_placement

    spec = TorusSpec((4, 4))
    assert spec.hops(0, 1) == 1
    assert spec.hops(0, 15) == 2          # wrap both dims
    assert spec.hops(0, 10) == 4          # (0,0)->(2,2)
    for a in range(16):
        for b in range(16):
            assert spec.hops(a, b) == spec.hops(b, a) <= spec.diameter

    # placement permutes which RANKS are close, not the torus itself
    snake = TorusSpec((2, 4), placement=snake_placement((2, 4)))
    ring = [(i, (i + 1) % 8) for i in range(8)]
    assert snake.max_hops(ring) == 1
    assert TorusSpec((2, 4)).max_hops(ring) == 2   # row-major wrap edges


def test_routes_are_minimal_and_valid_up_to_48_ranks():
    """Dimension-ordered routes: length == hop distance; the hop-distance
    translation perms schedule in ONE lockstep batch whose every sub-round
    is a valid ppermute (unique sources and destinations) — on the paper's
    48-rank torus."""
    from repro.core.topology import TorusSpec, route, route_rounds

    spec = TorusSpec((6, 8))           # 48 ranks
    rng = np.random.RandomState(3)
    for _ in range(50):
        a, b = rng.randint(0, 48, size=2)
        r = route(spec, int(a), int(b))
        assert r[0] == a and r[-1] == b
        assert len(r) == spec.hops(int(a), int(b)) + 1
        assert len(set(r)) == len(r)   # no revisits on a minimal route

    for d in range(1, spec.diameter + 1):
        perm = spec.hop_perm(d)
        assert all(spec.hops(s, t) == d for s, t in perm)
        rp = route_rounds(spec, perm)
        assert len(rp.batches) == 1 and rp.n_rounds == d
        for rnd in rp.batches[0].rounds:
            srcs = [s for s, _ in rnd]
            dsts = [t for _, t in rnd]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)
        assert sorted(rp.batches[0].dests) == sorted(t for _, t in perm)


def test_route_rounds_batches_cover_irregular_patterns():
    """Irregular (RCB-style) edge lists split into conflict-free batches;
    every destination is delivered exactly once."""
    from repro.core.topology import TorusSpec, route_rounds

    spec = TorusSpec((2, 4))
    rng = np.random.RandomState(0)
    for _ in range(20):
        ranks = list(rng.permutation(8))
        k = int(rng.randint(2, 5))
        edges = list(zip(ranks[:k], ranks[k:2 * k]))
        edges = [(int(s), int(d)) for s, d in edges if s != d]
        if not edges:
            continue
        rp = route_rounds(spec, edges)
        assert sorted(d for b in rp.batches for d in b.dests) == \
            sorted(d for _, d in edges)
        for b in rp.batches:
            for rnd in b.rounds:
                srcs = [s for s, _ in rnd]
                dsts = [t for _, t in rnd]
                assert len(set(srcs)) == len(srcs)
                assert len(set(dsts)) == len(dsts)


def test_communicator_topology_integration():
    from repro.core.communicator import Communicator
    from repro.core.topology import RoutedPerm, TorusSpec, routed_perm

    spec = TorusSpec((2, 4))
    comm = Communicator(("x",), (8,), topo=spec)
    assert comm.torus_hops(0, 6) == spec.hops(0, 6)
    assert comm.hop_perm(2) == spec.hop_perm(2)
    # spec size must match the communicator
    with pytest.raises(ValueError):
        Communicator(("x",), (8,), topo=TorusSpec((4, 4)))
    with pytest.raises(ValueError):
        Communicator(("x",), (8,)).hop_perm(1)
    # direct edges stay plain perms; multi-hop edges get routed
    assert routed_perm(comm, [(0, 1)]) == ((0, 1),)
    assert isinstance(routed_perm(comm, [(0, 6)]), RoutedPerm)
    assert routed_perm(Communicator(("x",), (8,)), [(0, 6)]) == ((0, 6),)


def test_predicted_latency_monotone_in_hops():
    """Eq. 1 with the route term: every enumerable config's predicted
    latency strictly increases with hop count (the conformance matrix's
    model-side invariant)."""
    from repro.core import latmodel
    from repro.core.config import V5E
    from repro.tune.space import enumerate_configs

    for cfg in enumerate_configs(None):
        for msg in (1 << 10, 1 << 20):
            prev = None
            for h in range(1, 6):
                t = latmodel.pingping_latency(msg, cfg, V5E, hops=h)
                if prev is not None:
                    assert t > prev, (cfg, msg, h)
                prev = t
            # hops=1 must match the classic (pre-route-term) model shape:
            # streaming pipelining adds nothing at depth 1
            assert latmodel.pingping_latency(msg, cfg, V5E, hops=1) == \
                pytest.approx(latmodel.pingping_latency(msg, cfg, V5E))


def test_torus_hardware_spec_carries_hop_constants():
    from repro.core.config import V5E
    from repro.core.topology import TorusSpec

    spec = TorusSpec((4, 4), per_hop_ns=750.0, bisection_gbps=100.0)
    hw = spec.hardware(V5E)
    assert hw.ici_hop_latency == pytest.approx(750e-9)
    assert hw.ici_bw == pytest.approx(100e9 / 16)   # 4*min(shape) links
    assert hw.ici_bw < V5E.ici_bw


def test_calibration_fits_hop_term():
    """A multi-distance sweep resolves the per-hop constant; a
    single-distance sweep keeps the default."""
    from repro.core import latmodel
    from repro.core.config import CommConfig, CommMode, HardwareSpec
    from repro.tune.calibrate import CalibrationResult, fit_latency_model

    hw = HardwareSpec(host_dispatch=25e-6, fused_dispatch=0.8e-6,
                      ici_latency=1.5e-6, ici_hop_latency=2.0e-6,
                      ici_bw=40e9, hbm_bw=600e9)
    meas = []
    for mode in CommMode:
        for size in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
            for hops in (1, 2, 4):
                cfg = CommConfig(mode=mode)
                meas.append((cfg, size,
                             latmodel.pingping_latency(size, cfg, hw,
                                                       hops=hops), hops))
    r = fit_latency_model(meas)
    assert r.hop_latency == pytest.approx(hw.ici_hop_latency, rel=0.2)
    cal = r.to_hardware_spec(hw)
    for cfg, size, sec, hops in meas:
        assert latmodel.pingping_latency(size, cfg, cal, hops=hops) == \
            pytest.approx(sec, rel=0.1)
    # single-distance: hop column untouched, default retained
    r1 = fit_latency_model([m[:3] for m in meas if m[3] == 1])
    assert r1.hop_latency == CalibrationResult.hop_latency
    # single distance > 1: the constant hop cost is collinear with l0 —
    # the fit must price it at the retained default, NOT absorb it into l0
    # and then re-add the default at prediction time (double count)
    m4 = [m for m in meas if m[3] == 4]
    r4 = fit_latency_model(m4)
    assert r4.hop_latency == CalibrationResult.hop_latency
    cal4 = r4.to_hardware_spec(hw)
    for cfg, size, sec, hops in m4:
        assert latmodel.pingping_latency(size, cfg, cal4, hops=hops) == \
            pytest.approx(sec, rel=0.1)


def test_flat_caller_prefers_flat_entries_over_torus_entries():
    """The torus filter works both ways: a flat-mesh caller (torus="")
    whose ring wrap edge happens to share a hop count with a routed torus
    measurement must not be answered by the store-and-forward-tuned config
    — and the torus caller keeps its own."""
    from repro.core.config import CommConfig
    from repro.tune.db import TuneDB, TuneEntry, select_config
    from repro.tune.space import config_to_dict

    flat_cfg = CommConfig(chunk_bytes=1 << 20)
    torus_cfg = CommConfig(chunk_bytes=1 << 14)
    db = TuneDB()
    db.add(TuneEntry(topo="cpu:9", collective="sendrecv", msg_bytes=1024,
                     config=config_to_dict(flat_cfg), us_per_call=10.0,
                     hops=1, torus=""))
    db.add(TuneEntry(topo="cpu:9", collective="sendrecv", msg_bytes=1024,
                     config=config_to_dict(torus_cfg), us_per_call=500.0,
                     hops=2, torus="3x3"))
    assert select_config("sendrecv", 1024, db=db, topo="cpu:9", hops=2,
                         torus="") == flat_cfg
    assert select_config("sendrecv", 1024, db=db, topo="cpu:9", hops=2,
                         torus="3x3") == torus_cfg


def test_auto_config_derives_hops_from_torus_spec():
    """PR 4 pinned that auto_config derives+passes ring hops; on a virtual
    torus the derivation must follow the SPEC's placement, not the flat
    factorization (regression for the multi-hop TorusSpec path)."""
    from repro.core.communicator import Communicator
    from repro.core.topology import TorusSpec, snake_placement
    import repro.tune

    seen = {}
    orig = repro.tune.select_config

    def spy(collective, msg_bytes, **kw):
        seen.update(kw)
        return orig(collective, msg_bytes, **kw)

    repro.tune.select_config = spy
    try:
        flat = Communicator(("data",), (8,))
        flat.auto_config("all_reduce", 1024)
        assert seen.get("hops") == 2       # row-major 2x4 wrap edges

        snake = flat.with_topology(
            TorusSpec((2, 4), placement=snake_placement((2, 4))))
        snake.auto_config("all_reduce", 1024)
        assert seen.get("hops") == 1       # hop-1 rank ring by placement

        tall = flat.with_topology(TorusSpec((1, 8)))
        tall.auto_config("all_reduce", 1024)
        assert seen.get("hops") == 1       # an 8-ring's steps are all direct
    finally:
        repro.tune.select_config = orig


def test_multi_neighbor_rejects_mixed_overlapped_round_cfgs():
    import jax.numpy as jnp
    from repro.core import collectives
    from repro.core.communicator import Communicator
    from repro.core.config import CommConfig, Scheduling

    comm = Communicator(("x",), (4,))
    over = CommConfig(scheduling=Scheduling.OVERLAPPED)
    rounds = [[(0, 1), (1, 0)], [(2, 3), (3, 2)]]
    payloads = [jnp.zeros((4,)), jnp.zeros((4,))]
    with pytest.raises(ValueError):
        collectives.multi_neighbor_exchange(
            payloads, rounds, comm,
            [over, dataclasses.replace(over, window=2)])
    with pytest.raises(ValueError):
        collectives.multi_neighbor_exchange(payloads, rounds, comm, [over])


# ----------------------------------------------------------------------
# Hop-distance sweep -> per-edge winners (deterministic model timer)
# ----------------------------------------------------------------------

class _FakeDev:
    platform = "cpu"


class _FakeDevs:
    def __init__(self, n):
        self.shape = (n,)
        self.size = n
        self.flat = [_FakeDev()] * n


class _FakeMesh:
    """Just enough mesh surface for run_sweep with an injected timer (no
    program is ever built, so no real devices are needed)."""

    def __init__(self, n):
        self.axis_names = ("x",)
        self.devices = _FakeDevs(n)
        self.shape = {"x": n}


def _model_timer(hw):
    from repro.core import latmodel

    def timer(op, mesh, msg_bytes, cfg, cache_key=None, **kw):
        hop_d = (cache_key[3] or 1) if cache_key else 1
        return latmodel.pingping_latency(msg_bytes, cfg, hw, hops=hop_d)

    return timer


def _hop_hw():
    from repro.core.config import HardwareSpec
    return HardwareSpec(host_dispatch=30e-6, fused_dispatch=0.5e-6,
                        ici_latency=1e-6, ici_hop_latency=0.5e-6,
                        ici_bw=50e9)


def test_hop_sweep_yields_per_edge_winners():
    """The acceptance matrix's selection arm: a sweep over >= 3 hop
    distances on a virtual torus records ``TuneEntry.hops`` per measured
    edge, and ``select_config(hops=...)`` returns DIFFERENT winners for at
    least one edge pair — the jumbo segment wins the direct link (fewest
    scheduled commands), small segments win the routed edge (chunk
    wormholing across hops)."""
    from repro.core.topology import TorusSpec
    from repro.tune import TuneDB, select_config
    from repro.tune.sweep import run_sweep

    spec = TorusSpec((2, 4))
    db = run_sweep(mesh=_FakeMesh(8), collectives=("sendrecv",),
                   sizes=(1 << 20,), fast=True, topology=spec,
                   hop_distances=(1, 2, 3), timer=_model_timer(_hop_hw()))
    assert sorted({e.hops for e in db.entries}) == [1, 2, 3]
    assert all(e.torus == "2x4" for e in db.entries)
    topo = db.entries[0].topo

    winners = {h: select_config("sendrecv", 1 << 20, db=db, topo=topo,
                                hops=h) for h in (1, 2, 3)}
    assert winners[1] != winners[3], "hop distance must change the winner"
    assert winners[1].chunk_bytes > winners[3].chunk_bytes
    # the per-edge answer survives the JSON round-trip (hops + torus fields)
    import json
    payload = json.loads(json.dumps(
        {"h": [dataclasses.asdict(e) for e in db.entries]}))
    from repro.tune.db import TuneEntry
    back = TuneDB([TuneEntry(**e) for e in payload["h"]])
    for h, cfg in winners.items():
        assert select_config("sendrecv", 1 << 20, db=back, topo=topo,
                             hops=h) == cfg


def test_hop_sweep_prunes_at_measured_distance():
    """Model-guided pruning prices candidates at the hop distance the sweep
    is about to measure them at: the candidate kept at 3 hops differs from
    the 1-hop incumbent's shadow."""
    from repro.core.config import CommConfig
    from repro.tune.calibrate import fit_latency_model
    from repro.core import latmodel
    from repro.tune.prune import prune_candidates

    hw = _hop_hw()
    meas = []
    for size in (1 << 14, 1 << 20):
        for hops in (1, 2, 3):
            for cfg in (CommConfig(), CommConfig(chunk_bytes=1 << 16)):
                meas.append((cfg, size,
                             latmodel.pingping_latency(size, cfg, hw,
                                                       hops=hops), hops))
    cal = fit_latency_model(meas)
    jumbo = CommConfig(chunk_bytes=1 << 20)
    small = CommConfig(chunk_bytes=1 << 16)
    kept1, skipped1 = prune_candidates([jumbo, small], 1 << 20, cal,
                                       ratio=1.2, collective="sendrecv",
                                       hops=1)
    kept3, skipped3 = prune_candidates([jumbo, small], 1 << 20, cal,
                                       ratio=1.2, collective="sendrecv",
                                       hops=3)
    assert jumbo in kept1 and small in skipped1
    assert small in kept3 and jumbo in skipped3


def test_hop_distances_validation():
    from repro.core.topology import TorusSpec
    from repro.tune.sweep import run_sweep

    with pytest.raises(ValueError):
        run_sweep(mesh=_FakeMesh(8), collectives=("sendrecv",),
                  sizes=(1024,), hop_distances=(1, 2),
                  timer=_model_timer(_hop_hw()))
    with pytest.raises(ValueError):
        run_sweep(mesh=_FakeMesh(8), collectives=("sendrecv",),
                  sizes=(1024,), topology=TorusSpec((2, 4)),
                  hop_distances=(0, 9), timer=_model_timer(_hop_hw()))


def test_driver_selects_distinct_per_round_configs(tmp_path):
    """The SWE driver's per-edge selection: rounds at different hop
    distances get different autotuned configs (unit-level — the live-mesh
    version runs in the conformance subprocess)."""
    from repro.core.communicator import Communicator
    from repro.core.config import CommConfig
    from repro.core.topology import TorusSpec
    from repro.swe.driver import _select_round_configs
    from repro.tune.db import TuneDB, TuneEntry, topology_key
    from repro.tune.space import config_to_dict

    topo = topology_key(n_devices=8)
    jumbo, small = CommConfig(chunk_bytes=1 << 20), CommConfig(chunk_bytes=1 << 16)
    db = TuneDB()
    for msg in (1024, 1 << 16):
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(jumbo),
                         us_per_call=10.0, hops=1))
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(small),
                         us_per_call=12.0, hops=1))
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(jumbo),
                         us_per_call=40.0, hops=2))
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(small),
                         us_per_call=20.0, hops=2))
    path = tmp_path / "tunedb.json"
    db.save(path)

    comm = Communicator(("data",), (8,), topo=TorusSpec((2, 4)))
    rounds = [[(0, 1), (1, 0)],            # direct links
              [(0, 6), (6, 0)]]            # 2-hop routed edges
    cfgs = _select_round_configs(rounds, comm, 1024, tune_db_path=path)
    assert cfgs[0].chunk_bytes == 1 << 20
    assert cfgs[1].chunk_bytes == 1 << 16
    assert len(set(cfgs)) == 2


# ----------------------------------------------------------------------
# Bitwise parity: torus vs flat, across the conformance matrix
# ----------------------------------------------------------------------

def test_torus_parity_matrix_perm_collectives():
    """sendrecv, the multi-neighbor exchange, and the ring all-reduce are
    bit-identical on torus-placed communicators vs the flat mesh over torus
    shapes x placements x (mode, scheduling, transport)."""
    out = run_multidevice("""
import dataclasses
import jax
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, CommMode, Scheduling, Transport
from repro.core.topology import TorusSpec, snake_placement

mesh = make_mesh((8,), ("x",))
x = np.random.RandomState(0).randn(8, 66).astype(np.float32)

def run_all(comm, cfg):
    results = []
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def p2p(xs):
        return collectives.sendrecv(
            xs[0], [(i, (i + 3) % 8) for i in range(8)], comm, cfg)[None]
    results.append(np.asarray(p2p(x)))
    rounds = [comm.ring_perm(1), comm.reverse_ring_perm(1), comm.ring_perm(2)]
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def mn(xs):
        outs = collectives.multi_neighbor_exchange(
            [xs[0]] * len(rounds), rounds, comm, cfg)
        return sum(outs)[None]
    results.append(np.asarray(mn(x)))
    rcfg = dataclasses.replace(cfg, algorithm="ring")
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def ar(xs):
        return collectives.all_reduce(xs[0], comm, rcfg)[None]
    results.append(np.asarray(ar(x)))
    return results

shuffled = (3, 6, 0, 5, 2, 7, 1, 4)
# HOST scheduling lowers the same per-op programs as FUSED (dispatch
# granularity is a caller concern), so a latin square over mode x
# scheduling x transport covers every distinct traced path: both modes
# under both schedulings, both transports under both modes.  The identity
# placement runs the full square; the other placements (snake, shuffled,
# transposed shape) run the two most distinct corners — routing is
# placement-independent code, so the cross-check needs breadth, not the
# full product per placement (keeps the tier-1 matrix affordable).
FULL = [CommConfig(mode=m, scheduling=s, transport=t, chunk_bytes=512,
                   window=2)
        for m, s, t in (
            (CommMode.STREAMING, Scheduling.FUSED, Transport.UNORDERED),
            (CommMode.STREAMING, Scheduling.OVERLAPPED, Transport.ORDERED),
            (CommMode.BUFFERED, Scheduling.FUSED, Transport.ORDERED),
            (CommMode.BUFFERED, Scheduling.OVERLAPPED, Transport.UNORDERED))]
SPECS = [(TorusSpec((2, 4)), FULL),
         (TorusSpec((2, 4), placement=snake_placement((2, 4))), FULL[:2]),
         (TorusSpec((4, 2), placement=shuffled), FULL[1:3])]

flat = Communicator.from_mesh(mesh, "x")
refs = {id(cfg): run_all(flat, cfg) for cfg in FULL}
for spec, cfgs in SPECS:
    for cfg in cfgs:
        got = run_all(flat.with_topology(spec), cfg)
        for i, (r, g) in enumerate(zip(refs[id(cfg)], got)):
            assert r.tobytes() == g.tobytes(), (spec.name, cfg, i)
print("TORUS PARITY MATRIX OK")
""", timeout=900)
    assert "TORUS PARITY MATRIX OK" in out


def test_torus_parity_a2a_hierarchical_and_cache_bypass():
    """all_to_all and the hierarchical all-reduce under a torus spec, plus
    the plan-cache arm: REPRO_PLAN_CACHE=0 stays bitwise-identical under
    the torus transport (routing is re-derived, never re-valued)."""
    out = run_multidevice("""
import os
import dataclasses
import jax
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import collectives, plans
from repro.core.communicator import Communicator
from repro.core.config import CommConfig, CommMode, Scheduling, Transport
from repro.core.topology import TorusSpec

mesh = make_mesh((8,), ("x",))
x = np.random.RandomState(1).randn(8, 64).astype(np.float32)

flat = Communicator.from_mesh(mesh, "x")
spec = TorusSpec((2, 4))
torus = flat.with_topology(spec)

def a2a(comm, cfg):
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def f(xs):
        return collectives.all_to_all(
            xs[0].reshape(8, 8), comm, cfg).reshape(1, 64)
    return np.asarray(f(x))

for cfg in (CommConfig(),
            CommConfig(scheduling=Scheduling.OVERLAPPED, chunk_bytes=512),
            CommConfig(mode=CommMode.BUFFERED)):
    assert a2a(flat, cfg).tobytes() == a2a(torus, cfg).tobytes(), cfg

# hierarchical: 2-axis mesh, inner communicator placed on a 2x2 torus
mesh2 = make_mesh((4, 2), ("inner", "outer"))
inner_flat = Communicator.from_mesh(mesh2, "inner")
inner_torus = inner_flat.with_topology(TorusSpec((2, 2)))
outer = Communicator.from_mesh(mesh2, "outer")
x2 = np.random.RandomState(2).randn(8, 48).astype(np.float32)

def hier(inner, cfg):
    @partial(jax.shard_map, mesh=mesh2,
             in_specs=P(("inner", "outer")), out_specs=P(("inner", "outer")),
             check_vma=False)
    def f(xs):
        return collectives.hierarchical_all_reduce(
            xs[0], inner, outer, cfg)[None]
    return np.asarray(f(x2))

for cfg in (CommConfig(algorithm="ring", chunk_bytes=512), CommConfig()):
    assert hier(inner_flat, cfg).tobytes() == hier(inner_torus, cfg).tobytes()

# plan-cache bypass parity under the torus transport
def perm_ops(cfg):
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def p2p(xs):
        return collectives.sendrecv(
            xs[0], [(i, (i + 3) % 8) for i in range(8)], torus, cfg)[None]
    rounds = [torus.ring_perm(1), torus.ring_perm(2)]
    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def mn(xs):
        outs = collectives.multi_neighbor_exchange(
            [xs[0]] * 2, rounds, torus, cfg)
        return sum(outs)[None]
    return [np.asarray(p2p(x)), np.asarray(mn(x))]

for cfg in (CommConfig(chunk_bytes=512, transport=Transport.ORDERED,
                       window=2),
            CommConfig(scheduling=Scheduling.OVERLAPPED, chunk_bytes=512)):
    os.environ.pop("REPRO_PLAN_CACHE", None)
    plans.clear_cache(); plans.reset_stats()
    cached = perm_ops(cfg)
    assert plans.cache_stats()["plan_hits"] > 0
    os.environ["REPRO_PLAN_CACHE"] = "0"
    plans.clear_cache()
    bypassed = perm_ops(cfg)
    os.environ.pop("REPRO_PLAN_CACHE", None)
    for a, b in zip(cached, bypassed):
        assert a.tobytes() == b.tobytes(), cfg
print("TORUS A2A/HIER/BYPASS OK")
""", timeout=540)
    assert "TORUS A2A/HIER/BYPASS OK" in out


def test_measured_latency_grows_with_hop_distance():
    """The physical arm of the emulation: a real (wall-clock) hop-distance
    sweep measures a 3-hop translation strictly slower than the direct link
    — each extra hop is one more executed permute of the full payload."""
    out = run_multidevice("""
from repro.launch.mesh import make_mesh
from repro.core.config import OPTIMIZED_CONFIG
from repro.core.communicator import Communicator
from repro.core.topology import TorusSpec
from repro.tune.sweep import _build_op, _time_program

mesh = make_mesh((8,), ("x",))
comm = Communicator.from_mesh(mesh, "x", topo=TorusSpec((2, 4)))
# One device-scheduled streaming config (dispatch amortized over the
# compiled loop): the timing is dominated by the permutes themselves, and
# the 3-hop translation executes 3x the permutes of the direct link.
# 4 MiB payload: the host backend's fixed per-collective cost (~4 ms)
# would otherwise swamp the per-hop bandwidth term on a loaded machine.
cfg = OPTIMIZED_CONFIG
times = {}
for d in (1, 3):
    op = _build_op("sendrecv", comm, cfg, hop_distance=d)
    times[d] = _time_program(op, mesh, 1 << 22, cfg, reps=3, inner=8)
ratio = times[3] / times[1]
assert ratio > 1.1, (times, "3-hop routing should cost measurably more")
print("MEASURED HOP SCALING OK", round(ratio, 2))
""", timeout=540)
    assert "MEASURED HOP SCALING OK" in out


def test_swe_driver_on_torus_matches_flat_and_selects_per_edge():
    """Live-mesh conformance of the SWE step on a virtual torus: per-edge
    auto-selection picks distinct round configs from a hop-split TuneDB,
    and the torus simulation stays bitwise-identical to the flat mesh under
    both the serial and the overlapped schedule."""
    out = run_multidevice("""
import numpy as np, jax, dataclasses, tempfile
from repro.launch.mesh import make_mesh
from repro.core.config import CommConfig, Scheduling
from repro.core.communicator import Communicator
from repro.core.topology import TorusSpec
from repro.swe import driver
from repro.tune.db import TuneDB, TuneEntry, topology_key
from repro.tune.space import config_to_dict

db = TuneDB()
topo = topology_key(n_devices=8)
jumbo = CommConfig(chunk_bytes=1 << 20)
small = CommConfig(chunk_bytes=1 << 16)
for msg in (1024, 1 << 16):
    for cfg, us1, us2 in ((jumbo, 10.0, 40.0), (small, 12.0, 20.0)):
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(cfg),
                         us_per_call=us1, hops=1))
        db.add(TuneEntry(topo=topo, collective="multi_neighbor",
                         msg_bytes=msg, config=config_to_dict(cfg),
                         us_per_call=us2, hops=2))
path = tempfile.mktemp(suffix=".json"); db.save(path)

dmesh = make_mesh((8,), ("data",))
spec = TorusSpec((2, 4))
sim = driver.build_simulation(400, dmesh, "auto", tune_db_path=path,
                              topology=spec)
comm = Communicator(("data",), (8,), topo=spec)
round_hops = [comm.max_hops(r) for r in sim.pm.rounds]
if len(set(round_hops)) > 1:
    assert sim.round_cfgs is not None, round_hops
    assert len({c.chunk_bytes for c in sim.round_cfgs}) > 1, \
        [c.chunk_bytes for c in sim.round_cfgs]

s_torus = np.asarray(jax.block_until_ready(
    driver.make_sim_runner(sim, 5)(sim.state, 0.0)))
flat = driver.build_simulation(400, dmesh, sim.comm_cfg)
s_flat = np.asarray(jax.block_until_ready(
    driver.make_sim_runner(flat, 5)(flat.state, 0.0)))
assert s_torus.tobytes() == s_flat.tobytes()

ov = dataclasses.replace(sim.comm_cfg, scheduling=Scheduling.OVERLAPPED)
sim_ov = driver.build_simulation(400, dmesh, ov, topology=spec)
s_ov = np.asarray(jax.block_until_ready(
    driver.make_sim_runner(sim_ov, 5)(sim_ov.state, 0.0)))
assert s_ov.tobytes() == s_flat.tobytes()
print("SWE TORUS CONFORMANCE OK", round_hops)
""", timeout=540)
    assert "SWE TORUS CONFORMANCE OK" in out
