"""Autotuner subsystem: search space, TuneDB, calibration, selection, and
the latmodel regressions the tuner's cost model depends on."""
import dataclasses
import itertools
import json

import numpy as np
import pytest

from helpers import run_multidevice


# ----------------------------------------------------------------------
# Search space
# ----------------------------------------------------------------------

def test_search_space_pruning_matches_commconfig_validation():
    """enumerate_configs must contain exactly the combos CommConfig accepts
    (after canonicalizing fields the collective never reads)."""
    from repro.core.config import CommConfig
    from repro.tune.space import DEFAULT_AXES, enumerate_configs, space_size

    names = list(DEFAULT_AXES)
    valid, invalid = set(), 0
    for combo in itertools.product(*(DEFAULT_AXES[n] for n in names)):
        try:
            valid.add(CommConfig(**dict(zip(names, combo))))
        except ValueError:
            invalid += 1
    assert invalid > 0, "the axes should include invalid combos to prune"
    assert valid, "the axes should include valid combos"

    # No collective filter: enumeration = validation minus window-dedup.
    enumerated = set(enumerate_configs(collective=None))
    assert enumerated <= valid
    for cfg in enumerated:
        CommConfig(**dataclasses.asdict(cfg))   # re-validates
    # The unordered-transport window dedup is the only collapse applied.
    from repro.core.config import Transport
    collapsed = {dataclasses.replace(c, window=CommConfig().window)
                 if c.transport == Transport.UNORDERED else c for c in valid}
    assert enumerated == collapsed
    assert len(enumerated) < space_size()


def test_search_space_collective_canonicalization():
    from repro.tune.space import enumerate_configs
    # sendrecv never reads algorithm/compression -> all candidates share the
    # defaults for those fields, and the space is strictly smaller.
    p2p = enumerate_configs("sendrecv")
    assert all(c.algorithm == "native" for c in p2p)
    assert len(p2p) < len(enumerate_configs("all_reduce"))


def test_config_dict_roundtrip():
    from repro.tune.space import (config_from_dict, config_to_dict,
                                  enumerate_configs)
    for cfg in enumerate_configs("all_reduce"):
        wire = json.loads(json.dumps(config_to_dict(cfg)))
        assert config_from_dict(wire) == cfg


# ----------------------------------------------------------------------
# TuneDB
# ----------------------------------------------------------------------

def _entry(msg_bytes, us, topo="cpu:8", coll="all_reduce", hops=1,
           e2e_us=0.0, **cfg_kw):
    from repro.core.config import CommConfig
    from repro.tune.db import TuneEntry
    from repro.tune.space import config_to_dict
    return TuneEntry(topo=topo, collective=coll, msg_bytes=msg_bytes,
                     config=config_to_dict(CommConfig(**cfg_kw)),
                     us_per_call=us, gbps=msg_bytes / us / 1e3, hops=hops,
                     e2e_us=e2e_us)


def test_tunedb_roundtrip_and_nearest(tmp_path):
    from repro.tune.db import TuneDB
    db = TuneDB()
    db.add(_entry(1024, 50.0))
    db.add(_entry(1024, 20.0, window=8))          # faster config, same key
    db.add(_entry(1 << 20, 900.0))
    path = tmp_path / "tunedb.json"
    db.save(path)
    back = TuneDB.load(path)
    assert len(back) == len(db) == 3

    assert back.best("all_reduce", 1024, "cpu:8").us_per_call == 20.0
    # nearest in LOG space: 16 KiB is closer to 1 KiB than to 1 MiB
    near = back.nearest("all_reduce", 16 << 10, "cpu:8")
    assert near.msg_bytes == 1024 and near.us_per_call == 20.0
    assert back.nearest("all_reduce", 700 << 10, "cpu:8").msg_bytes == 1 << 20
    # unknown collective / topo -> None
    assert back.best("all_to_all", 1024, "cpu:8") is None
    assert back.nearest("all_reduce", 1024, "tpu:64") is None


def test_tunedb_add_keeps_fastest_per_config():
    from repro.tune.db import TuneDB
    db = TuneDB()
    db.add(_entry(1024, 50.0))
    db.add(_entry(1024, 80.0))     # same config, slower rerun -> ignored
    db.add(_entry(1024, 30.0))     # same config, faster rerun -> replaces
    assert len(db) == 1
    assert db.best("all_reduce", 1024).us_per_call == 30.0


def test_select_config_cold_cache_falls_back_to_optimized(tmp_path):
    from repro.core.config import OPTIMIZED_CONFIG
    from repro.tune.db import TuneDB, select_config
    assert select_config("all_reduce", 1 << 16,
                         db=TuneDB()) == OPTIMIZED_CONFIG
    # missing file behaves the same
    assert select_config("all_reduce", 1 << 16,
                         path=tmp_path / "nope.json") == OPTIMIZED_CONFIG


def test_select_config_never_crosses_platforms():
    """A config tuned on another platform's cost structure must not beat the
    OPTIMIZED_CONFIG fallback."""
    from repro.core.config import OPTIMIZED_CONFIG
    from repro.tune.db import TuneDB, select_config
    db = TuneDB()
    db.add(_entry(1024, 10.0, topo="cpu:8", window=8))
    # same platform, different device count -> relaxes to it
    assert select_config("all_reduce", 1024, db=db, topo="cpu:4").window == 8
    # different platform -> fallback, never the cpu-tuned entry
    assert select_config("all_reduce", 1024, db=db,
                         topo="tpu:8") == OPTIMIZED_CONFIG


def test_communicator_auto_config_keys_on_comm_size():
    """Communicator.auto_config looks up THIS communicator's size, not the
    whole process's device count."""
    from repro.core.communicator import Communicator
    from repro.tune.db import TuneDB, topology_key
    import repro.tune.db as dbmod

    comm = Communicator(("data",), (4,))
    topo4 = topology_key(n_devices=4)          # e.g. cpu:4 under pytest
    db = TuneDB()
    db.add(_entry(1024, 10.0, topo=topo4, window=8))
    path = dbmod.default_db_path()
    seen = {}
    orig = dbmod.select_config

    def spy(collective, msg_bytes, **kw):
        seen.update(kw)
        return orig(collective, msg_bytes, db=db, topo=kw.get("topo"))

    dbmod.select_config = spy
    try:
        import repro.tune
        repro.tune.select_config, orig_pkg = spy, repro.tune.select_config
        try:
            cfg = comm.auto_config("all_reduce", 1024)
        finally:
            repro.tune.select_config = orig_pkg
    finally:
        dbmod.select_config = orig
    assert seen.get("topo") == topo4
    assert cfg.window == 8


def test_hop_aware_selection_prefers_matched_hops(tmp_path):
    """Per-edge hop-aware selection (the paper's direct-link vs
    Ethernet-switch distinction): a DB with conflicting 1-hop/3-hop winners
    must answer per hop distance, not with the global minimum."""
    from repro.tune.db import TuneDB, select_config

    db = TuneDB()
    # direct links: tiny window wins; routed 3-hop edges: window scaling wins
    db.add(_entry(1024, 10.0, window=1, hops=1))
    db.add(_entry(1024, 12.0, window=8, hops=3))

    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         hops=1).window == 1
    # hop-matched beats globally fastest
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         hops=3).window == 8
    # no hop hint: fastest measurement overall
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8").window == 1
    # unmeasured distance relaxes to the nearest measured one
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         hops=4).window == 8

    # hops survive the JSON round-trip and distinguish add() data points
    path = tmp_path / "tunedb.json"
    db.save(path)
    back = TuneDB.load(path)
    assert len(back) == 2
    assert sorted(e.hops for e in back.entries) == [1, 3]
    assert select_config("all_reduce", 1024, db=back, topo="cpu:8",
                         hops=3).window == 8


def test_tunedb_add_same_config_different_hops_kept():
    from repro.tune.db import TuneDB
    db = TuneDB()
    db.add(_entry(1024, 10.0, hops=1))
    db.add(_entry(1024, 30.0, hops=3))   # same config, other distance: kept
    db.add(_entry(1024, 25.0, hops=3))   # faster rerun at 3 hops: replaces
    assert len(db) == 2
    assert db.best("all_reduce", 1024, "cpu:8", hops=3).us_per_call == 25.0


def test_select_config_returns_measured_best():
    from repro.tune.db import TuneDB, select_config, topology_key
    topo = topology_key()   # this process's topology (cpu:1 under pytest)
    db = TuneDB()
    db.add(_entry(1024, 50.0, topo=topo))
    db.add(_entry(1024, 10.0, topo=topo, window=8))
    cfg = select_config("all_reduce", 1024, db=db)
    assert cfg.window == 8


# ----------------------------------------------------------------------
# Variance-aware selection (p95 near-tie break) + lossy-wire selection
# ----------------------------------------------------------------------

def test_p95_breaks_near_ties():
    """Two configs within NEAR_TIE on the mean: the lower measured tail
    wins; an entry with no recorded p95 cannot win the tie-break."""
    import dataclasses as dc
    from repro.tune.db import TuneDB, select_config, topology_key
    topo = topology_key()
    db = TuneDB()
    # 2% apart on the mean (inside the 5% near-tie band), tails disagree
    db.add(dc.replace(_entry(1024, 100.0, topo=topo), p95_us=180.0))
    db.add(dc.replace(_entry(1024, 102.0, topo=topo, window=8),
                      p95_us=110.0))
    cfg = select_config("all_reduce", 1024, db=db)
    assert cfg.window == 8                   # steadier tail wins the tie
    # an unknown tail never beats a measured one on missing data
    db2 = TuneDB()
    db2.add(_entry(1024, 100.0, topo=topo))              # p95 unrecorded
    db2.add(dc.replace(_entry(1024, 102.0, topo=topo, window=8),
                       p95_us=110.0))
    assert select_config("all_reduce", 1024, db=db2).window == 8
    # outside the near-tie band the mean decides, tails notwithstanding
    db3 = TuneDB()
    db3.add(dc.replace(_entry(1024, 100.0, topo=topo), p95_us=500.0))
    db3.add(dc.replace(_entry(1024, 150.0, topo=topo, window=8),
                       p95_us=101.0))
    assert select_config("all_reduce", 1024, db=db3).window == 4


def test_select_config_prefers_matching_loss():
    """Jumbo frames win the clean sweep, small GUARANTEED segments win the
    lossy one — the answer must come from the matching-loss measurement
    (nearest measured rate when there is no exact match)."""
    import dataclasses as dc
    from repro.core.config import Reliability
    from repro.tune.db import TuneDB, select_config, topology_key
    topo = topology_key()
    db = TuneDB()
    db.add(_entry(1 << 20, 50.0, topo=topo, chunk_bytes=1 << 20))
    db.add(dc.replace(
        _entry(1 << 20, 80.0, topo=topo, chunk_bytes=4096,
               reliability=Reliability.GUARANTEED), loss=0.05))
    clean = select_config("all_reduce", 1 << 20, db=db)
    assert clean.chunk_bytes == 1 << 20
    lossy = select_config("all_reduce", 1 << 20, db=db, loss=0.05)
    assert lossy.chunk_bytes == 4096
    assert lossy.reliability == Reliability.GUARANTEED
    # nearest measured rate answers an unswept loss
    near = select_config("all_reduce", 1 << 20, db=db, loss=0.08)
    assert near.chunk_bytes == 4096


def test_reliability_config_json_roundtrip():
    from repro.core.config import CommConfig, Reliability
    from repro.tune.space import config_from_dict, config_to_dict
    cfg = CommConfig(reliability=Reliability.GUARANTEED, ack_timeout=3,
                     max_retransmits=5, backoff_base=2, backoff_cap=8)
    wire = json.loads(json.dumps(config_to_dict(cfg)))
    assert wire["reliability"] == "guaranteed"
    back = config_from_dict(wire)
    assert back == cfg
    assert back.reliability is Reliability.GUARANTEED
    # best-effort default survives too
    assert config_from_dict(json.loads(json.dumps(
        config_to_dict(CommConfig())))).reliability is \
        Reliability.BEST_EFFORT


# ----------------------------------------------------------------------
# End-to-end objective (overlap-aware selection)
# ----------------------------------------------------------------------

def test_e2e_objective_disagrees_with_latency():
    """The §5 scenario: the bare-latency winner loses the consumer loop.
    select_config must answer per objective."""
    from repro.tune.db import TuneDB, select_config

    db = TuneDB()
    # microbench winner: buffered, but its consumer loop is slow
    db.add(_entry(1024, 10.0, e2e_us=90.0, mode="buffered", window=1))
    # microbench loser: overlapped/chunked, but the consumer hides the comm
    db.add(_entry(1024, 14.0, e2e_us=40.0, window=8))

    assert select_config("all_reduce", 1024, db=db, topo="cpu:8").window == 1
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         objective="latency").window == 1
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         objective="e2e").window == 8
    with pytest.raises(ValueError):
        select_config("all_reduce", 1024, db=db, objective="nope")


def test_e2e_objective_falls_back_to_latency():
    """Entries without a consumer-loop measurement rank by bare latency
    under either objective; measured e2e outranks latency-only entries."""
    from repro.tune.db import TuneDB, select_config
    db = TuneDB()
    db.add(_entry(1024, 10.0, window=1))             # no e2e measured
    db.add(_entry(1024, 20.0, window=8))
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         objective="e2e").window == 1
    # one measured e2e entry beats any latency-only proxy
    db.add(_entry(1024, 30.0, e2e_us=50.0, window=4))
    assert select_config("all_reduce", 1024, db=db, topo="cpu:8",
                         objective="e2e").window == 4


def test_tunedb_e2e_roundtrip_and_merge(tmp_path):
    from repro.tune.db import TuneDB
    db = TuneDB()
    db.add(_entry(1024, 50.0, e2e_us=120.0))
    # slower latency rerun carrying a better e2e: latency keeps 50, e2e 100
    db.add(_entry(1024, 60.0, e2e_us=100.0))
    # faster latency rerun without e2e: latency 40, e2e preserved
    db.add(_entry(1024, 40.0))
    assert len(db) == 1
    e = db.entries[0]
    assert e.us_per_call == 40.0 and e.e2e_us == 100.0
    assert e.latency_us == e.us_per_call     # the alias
    assert e.metric() == 40.0 and e.metric("e2e") == 100.0

    path = tmp_path / "tunedb.json"
    db.save(path)
    back = TuneDB.load(path)
    assert back.entries[0].e2e_us == 100.0
    # pre-e2e DBs (no e2e_us key) still load
    import json
    payload = json.loads(path.read_text())
    for ent in payload["entries"]:
        del ent["e2e_us"]
    path.write_text(json.dumps(payload))
    old = TuneDB.load(path)
    assert old.entries[0].e2e_us == 0.0


def test_e2e_consumer_latency_model():
    """The overlap-aware Eq. 2 consumer term: overlapped hides comm under
    compute (max), fused exposes part of it, host serializes."""
    from repro.core import latmodel
    from repro.core.config import (CommConfig, CommMode, Scheduling, V5E)

    msg, compute = 1 << 20, 50e-6
    over = CommConfig(scheduling=Scheduling.OVERLAPPED)
    fused = CommConfig(scheduling=Scheduling.FUSED)
    host = CommConfig(scheduling=Scheduling.HOST, mode=CommMode.BUFFERED)
    comm_s = latmodel.pingping_latency(msg, over, V5E)
    t_over = latmodel.e2e_consumer_latency(msg, over, compute, V5E)
    t_fused = latmodel.e2e_consumer_latency(msg, fused, compute, V5E)
    t_host = latmodel.e2e_consumer_latency(msg, host, compute, V5E)
    assert t_over == pytest.approx(max(compute, comm_s))   # full hiding
    assert t_over < t_fused < t_host
    # serialized lower/upper bounds hold for any config
    for cfg, t in ((over, t_over), (fused, t_fused), (host, t_host)):
        c = latmodel.pingping_latency(msg, cfg, V5E)
        assert max(compute, c) - 1e-12 <= t <= compute + c + 1e-12


def test_prune_on_e2e_objective_reorders_candidates():
    """Pruning on the e2e objective must keep the overlapped candidate that
    latency-objective pruning ranks as strictly worse."""
    from repro.core.config import CommConfig, Scheduling
    from repro.tune.prune import (calibration_from_db, predicted_e2e,
                                  predicted_latency, prune_candidates)

    cal = calibration_from_db(_synthetic_db(_synthetic_truth_hw()),
                              topo="cpu:8")
    over = CommConfig(scheduling=Scheduling.OVERLAPPED, chunk_bytes=1 << 16)
    fused = CommConfig(scheduling=Scheduling.FUSED)
    msg = 1 << 20
    # bare latency: the chunked overlapped config pays per-chunk commands
    assert predicted_latency(over, msg, cal, "all_reduce") >= \
        predicted_latency(fused, msg, cal, "all_reduce")
    # with hideable compute dominating, e2e prediction flips the order
    compute_s = 10.0 * predicted_latency(fused, msg, cal, "all_reduce")
    assert predicted_e2e(over, msg, cal, compute_s, "all_reduce") < \
        predicted_e2e(fused, msg, cal, compute_s, "all_reduce")
    kept, skipped = prune_candidates([over, fused], msg, cal, ratio=1.05,
                                     collective="all_reduce",
                                     objective="e2e", compute_s=compute_s)
    assert over in kept
    kept_lat, _ = prune_candidates([over, fused], msg, cal, ratio=1.05,
                                   collective="all_reduce")
    assert fused in kept_lat


def test_enumerate_configs_e2e_keeps_overlapped_consumers():
    """Under the e2e objective the overlapped all_reduce variants stay
    distinct (the consumer loop distinguishes them); the latency objective
    still collapses them (the bare collective cannot)."""
    from repro.core.config import Scheduling
    from repro.tune.space import enumerate_configs

    lat = enumerate_configs("all_reduce")
    e2e = enumerate_configs("all_reduce", objective="e2e")
    assert not any(c.scheduling == Scheduling.OVERLAPPED for c in lat)
    assert any(c.scheduling == Scheduling.OVERLAPPED for c in e2e)
    assert len(e2e) > len(lat)
    # non-consumer collectives are unchanged
    assert enumerate_configs("all_gather", objective="e2e") == \
        enumerate_configs("all_gather")


def test_communicator_auto_config_passes_ring_hops():
    """The hop-aware preference must be live from auto_config: the ring
    pattern's worst-case hop distance reaches select_config."""
    from repro.core.communicator import Communicator
    import repro.tune

    comm = Communicator(("data",), (8,))     # 2x4 torus -> max ring hop 2
    seen = {}
    orig = repro.tune.select_config

    def spy(collective, msg_bytes, **kw):
        seen.update(kw)
        return orig(collective, msg_bytes, **kw)

    repro.tune.select_config = spy
    try:
        comm.auto_config("all_reduce", 1024)
        assert seen.get("hops") == comm.max_hops(comm.ring_perm())
        assert seen.get("hops", 0) >= 1
        assert seen.get("objective") == "latency"
        comm.auto_config("all_reduce", 1024, hops=3, objective="e2e")
        assert seen.get("hops") == 3 and seen.get("objective") == "e2e"
    finally:
        repro.tune.select_config = orig


def test_program_cache_key_separates_mesh_factorizations():
    """topology_key is platform:n_devices only — the program-cache key must
    additionally carry the mesh structure, or an 8-rank-axis sweep and a
    4x2 inner/outer sweep (same device count) would replay each other's
    compiled programs and record silently wrong measurements."""
    from repro.tune.sweep import _mesh_key

    class FakeDevs:
        def __init__(self, shape):
            self.shape = shape

    class FakeMesh:
        def __init__(self, axis_names, shape):
            self.axis_names = axis_names
            self.devices = FakeDevs(shape)

    flat = _mesh_key(FakeMesh(("x",), (8,)))
    two_axis = _mesh_key(FakeMesh(("inner", "outer"), (4, 2)))
    assert flat != two_axis
    assert _mesh_key(FakeMesh(("x",), (8,))) == flat


def test_e2e_sweep_records_consumer_loop(tmp_path):
    out = run_multidevice("""
from repro.launch.mesh import make_mesh
from repro.tune import TuneDB, run_sweep, select_config
from repro.tune.sweep import sweep_summary

mesh = make_mesh((8,), ("x",))
stats = {}
db = run_sweep(mesh=mesh, collectives=("all_reduce",), sizes=(16384,),
               fast=True, max_configs=6, reps=1, inner=2,
               objective="e2e", stats=stats)
ents = [e for e in db.entries if e.collective == "all_reduce"]
assert ents and all(e.e2e_us > 0.0 for e in ents), stats
assert stats["e2e_measured"] == len(ents), stats
cfg = select_config("all_reduce", 16384, db=db, topo=ents[0].topo,
                    objective="e2e")
best_e2e = min(e.e2e_us for e in ents)
picked = [e for e in ents if e.e2e_us == best_e2e]
assert cfg == picked[0].comm_config
assert "consumer-loop e2e" in sweep_summary(stats)
print("E2E SWEEP OK")
""")
    assert "E2E SWEEP OK" in out


def test_moe_all_to_all_e2e_sweep_selects_measured_best(tmp_path):
    """The MoE dispatch -> expert-FFN -> combine loop is the third CONSUMERS
    entry: an e2e-objective all_to_all sweep must record consumer-loop times
    and select_config(objective='e2e') must return the measured winner."""
    out = run_multidevice("""
from repro.launch.mesh import make_mesh
from repro.tune import TuneDB, run_sweep, select_config
from repro.tune.sweep import CONSUMERS, consumer_flops

assert CONSUMERS["all_to_all"] == ("moe_loop",)
assert consumer_flops("all_to_all", 1 << 14) > 0

mesh = make_mesh((8,), ("x",))
stats = {}
db = run_sweep(mesh=mesh, collectives=("all_to_all",), sizes=(16384,),
               fast=True, max_configs=5, reps=1, inner=2,
               objective="e2e", stats=stats)
ents = [e for e in db.entries if e.collective == "all_to_all"]
assert ents and all(e.e2e_us > 0.0 for e in ents), stats
assert stats["e2e_measured"] == len(ents), stats
cfg = select_config("all_to_all", 16384, db=db, topo=ents[0].topo,
                    objective="e2e")
best = min(ents, key=lambda e: e.e2e_us)
assert cfg == best.comm_config
print("MOE E2E SWEEP OK")
""")
    assert "MOE E2E SWEEP OK" in out


def test_consumer_axis_prefers_matching_entries():
    """The TuneDB's consumer axis: a decode_step caller is answered by the
    decode_step-loop measurement when one exists, a prefill caller by the
    prefill-loop one — distinct winners from the same DB — and an unswept
    consumer relaxes to every entry instead of failing."""
    from repro.core.config import CommConfig, CommMode, Scheduling
    from repro.tune.db import TuneDB, TuneEntry, select_config
    from repro.tune.space import config_to_dict

    fast_small = CommConfig(scheduling=Scheduling.OVERLAPPED,
                            chunk_bytes=4096)
    fast_big = CommConfig(mode=CommMode.BUFFERED)
    db = TuneDB()
    for consumer, winner, loser in (("decode_step", fast_small, fast_big),
                                    ("prefill", fast_big, fast_small)):
        db.add(TuneEntry(topo="cpu:8", collective="all_reduce",
                         msg_bytes=16384, config=config_to_dict(winner),
                         us_per_call=10.0, e2e_us=20.0, consumer=consumer))
        db.add(TuneEntry(topo="cpu:8", collective="all_reduce",
                         msg_bytes=16384, config=config_to_dict(loser),
                         us_per_call=9.0, e2e_us=55.0, consumer=consumer))
    # 4 distinct (config, consumer) entries survive add()'s merge.
    assert len(db.entries) == 4
    pick = lambda c: select_config(  # noqa: E731
        "all_reduce", 16384, db=db, topo="cpu:8", objective="e2e",
        consumer=c)
    assert pick("decode_step") == fast_small
    assert pick("prefill") == fast_big
    # Unswept consumer: relax to all entries (global e2e winner), and the
    # bare-latency objective ignores the consumer-loop measurements.
    assert pick("halo_fold") == fast_small
    assert select_config("all_reduce", 16384, db=db, topo="cpu:8",
                         objective="latency") == fast_big
    # Round-trips through JSON (old DBs load with consumer="" defaults).
    entries = TuneDB([TuneEntry(**d) for d in
                      [dataclasses.asdict(e) for e in db.entries]])
    assert {e.consumer for e in entries.entries} == {"decode_step", "prefill"}


# ----------------------------------------------------------------------
# Calibration
# ----------------------------------------------------------------------

def test_calibration_recovers_known_constants():
    """Fitting on synthetic Eq. 1 timings must recover the generating
    HardwareSpec constants."""
    from repro.core import latmodel
    from repro.core.config import (CommConfig, CommMode, HardwareSpec,
                                   Scheduling)
    from repro.tune.calibrate import fit_latency_model

    hw = HardwareSpec(host_dispatch=25e-6, fused_dispatch=0.8e-6,
                      ici_latency=1.5e-6, ici_bw=40e9, hbm_bw=600e9)
    meas = []
    for mode in CommMode:
        for sched in Scheduling:
            for size in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
                cfg = CommConfig(mode=mode, scheduling=sched)
                meas.append((cfg, size,
                             latmodel.pingping_latency(size, cfg, hw)))
    r = fit_latency_model(meas)
    assert r.l_k_host == pytest.approx(hw.host_dispatch, rel=0.15)
    assert r.l_k_fused == pytest.approx(hw.fused_dispatch, rel=0.25)
    assert r.link_bw == pytest.approx(hw.ici_bw, rel=0.15)
    assert r.staging_bw == pytest.approx(hw.hbm_bw, rel=0.15)
    assert r.rms_rel_err < 0.05

    # and the calibrated spec reproduces the measurements through latmodel
    cal = r.to_hardware_spec(hw)
    for cfg, size, sec in meas:
        assert latmodel.pingping_latency(size, cfg, cal) == pytest.approx(
            sec, rel=0.1)


def test_calibration_report_and_db_path():
    from repro.core.config import CommConfig, CommMode, Scheduling, V5E
    from repro.core import latmodel
    from repro.tune.calibrate import calibrate_from_db, model_vs_measured
    from repro.tune.db import TuneDB, TuneEntry
    from repro.tune.space import config_to_dict

    db = TuneDB()
    for mode in CommMode:
        for sched in Scheduling:
            for size in (1 << 12, 1 << 16, 1 << 20):
                cfg = CommConfig(mode=mode, scheduling=sched)
                sec = latmodel.pingping_latency(size, cfg, V5E)
                db.add(TuneEntry(topo="cpu:8", collective="sendrecv",
                                 msg_bytes=size,
                                 config=config_to_dict(cfg),
                                 us_per_call=sec * 1e6))
    r = calibrate_from_db(db)
    assert "l_k(host)" in r.summary()
    rows = model_vs_measured(r, db)
    assert len(rows) == len(db)
    assert all("ratio=" in row for row in rows)


def test_fit_latency_model_empty_raises():
    from repro.tune.calibrate import fit_latency_model
    with pytest.raises(ValueError):
        fit_latency_model([])


# ----------------------------------------------------------------------
# Calibration-driven pruning (model-guided search)
# ----------------------------------------------------------------------

def _synthetic_truth_hw():
    """Ground-truth substrate for the synthetic-TuneDB pruning regression:
    realistic dispatch-cost separation (30 us host vs 0.5 us fused)."""
    from repro.core.config import HardwareSpec
    return HardwareSpec(host_dispatch=30e-6, fused_dispatch=0.5e-6,
                        ici_latency=1e-6, ici_bw=50e9, hbm_bw=819e9)


def _synthetic_db(hw, noise=0.03):
    """sendrecv measurements = ground-truth Eq.1 latency x (1 +- noise)."""
    import numpy as np
    from repro.core import latmodel
    from repro.tune.db import TuneDB, TuneEntry
    from repro.tune.space import config_to_dict, enumerate_configs
    rng = np.random.RandomState(7)
    db = TuneDB()
    for size in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
        for cfg in enumerate_configs("sendrecv"):
            sec = latmodel.pingping_latency(size, cfg, hw)
            sec *= 1.0 + noise * rng.randn()
            db.add(TuneEntry(topo="cpu:8", collective="sendrecv",
                             msg_bytes=size, config=config_to_dict(cfg),
                             us_per_call=sec * 1e6))
    return db


def test_pruning_skips_30pct_and_keeps_winner_within_noise():
    """The acceptance regression: on the standard sweep space the calibrated
    model must skip >= 30% of candidates while the pruned sweep's winner
    stays within measurement noise of the exhaustive winner."""
    import numpy as np
    from repro.core import latmodel
    from repro.tune.prune import calibration_from_db, prune_candidates
    from repro.tune.space import enumerate_configs

    hw = _synthetic_truth_hw()
    noise = 0.03
    cal = calibration_from_db(_synthetic_db(hw, noise), topo="cpu:8")
    assert cal is not None and cal.rms_rel_err < 0.15

    rng = np.random.RandomState(11)

    def measure(cfg, size):  # synthetic measurement = truth x noise
        return (latmodel.pingping_latency(size, cfg, hw)
                * (1.0 + noise * rng.randn()))

    total = kept_total = 0
    for coll in ("all_reduce", "sendrecv", "all_to_all"):
        cands = enumerate_configs(coll)
        for size in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
            kept, skipped = prune_candidates(cands, size, cal,
                                             collective=coll)
            assert kept, (coll, size)
            total += len(cands)
            kept_total += len(kept)
            # winner parity: best measured config among the kept set is
            # within noise of the best over the exhaustive set
            measured = {id(c): measure(c, size) for c in cands}
            best_all = min(cands, key=lambda c: measured[id(c)])
            best_kept = min(kept, key=lambda c: measured[id(c)])
            t_all = latmodel.pingping_latency(size, best_all, hw)
            t_kept = latmodel.pingping_latency(size, best_kept, hw)
            assert t_kept <= t_all * (1.0 + 5 * noise), (coll, size)
    skipped_frac = 1.0 - kept_total / total
    assert skipped_frac >= 0.30, f"pruned only {skipped_frac:.0%}"


def test_prune_candidates_always_keeps_incumbent():
    from repro.tune.prune import calibration_from_db, predicted_latency, \
        prune_candidates
    from repro.tune.space import enumerate_configs

    hw = _synthetic_truth_hw()
    cal = calibration_from_db(_synthetic_db(hw), topo="cpu:8")
    cands = enumerate_configs("all_reduce")
    kept, skipped = prune_candidates(cands, 1 << 14, cal,
                                     collective="all_reduce")
    assert len(kept) + len(skipped) == len(cands)
    preds = {id(c): predicted_latency(c, 1 << 14, cal, "all_reduce")
             for c in cands}
    best = min(preds.values())
    assert all(preds[id(c)] <= 2.0 * best for c in kept)
    assert all(preds[id(c)] > 2.0 * best for c in skipped)


def test_calibration_from_db_cold_cache_returns_none():
    from repro.tune.db import TuneDB
    from repro.tune.prune import calibration_from_db
    assert calibration_from_db(TuneDB(), topo="cpu:8") is None


def test_chunk_aware_prediction_prices_small_segments():
    """The Eq.3-style per-chunk command term: a 64 KiB-segment streaming
    sendrecv at 1 MiB must be predicted ~16 commands' worth slower than the
    jumbo config; non-chunking collectives see a single command."""
    import dataclasses
    from repro.core.config import CommConfig
    from repro.tune.prune import calibration_from_db, predicted_latency

    cal = calibration_from_db(_synthetic_db(_synthetic_truth_hw()),
                              topo="cpu:8")
    jumbo = CommConfig(chunk_bytes=1 << 20)
    small = dataclasses.replace(jumbo, chunk_bytes=1 << 16)
    msg = 1 << 20
    t_jumbo = predicted_latency(jumbo, msg, cal, "sendrecv")
    t_small = predicted_latency(small, msg, cal, "sendrecv")
    assert t_small > t_jumbo
    # all_reduce never splits the wire: segment size is prediction-neutral
    assert predicted_latency(small, msg, cal, "all_reduce") == \
        predicted_latency(jumbo, msg, cal, "all_reduce")


def test_sweep_new_collectives_and_pruning_e2e(tmp_path):
    out = run_multidevice("""
from repro.launch.mesh import make_mesh
from repro.tune import CalibrationResult, TuneDB, run_sweep

mesh = make_mesh((8,), ("x",))
cal = CalibrationResult(l_k_host=30e-6, l_k_fused=0.5e-6,
                        link_latency=1e-6, link_bw=50e9, staging_bw=819e9,
                        n_points=16, rms_rel_err=0.05)
stats = {}
db = run_sweep(mesh=mesh,
               collectives=("all_to_all", "hierarchical_all_reduce"),
               sizes=(1024,), fast=True, reps=1, inner=2,
               prune=True, calibration=cal, stats=stats)
colls = {e.collective for e in db.entries}
assert "all_to_all" in colls and "hierarchical_all_reduce" in colls, colls
assert stats["pruned"] > 0, stats
assert stats["measured"] < stats["total"], stats
assert stats["wall_s"] > 0 and stats["est_exhaustive_s"] > stats["wall_s"]
print("NEW COLLECTIVE SWEEP OK", stats["measured"], stats["total"])
""")
    assert "NEW COLLECTIVE SWEEP OK" in out


# ----------------------------------------------------------------------
# Latmodel regressions (the tuner's cost model)
# ----------------------------------------------------------------------

def test_buffered_peak_bw_formula():
    """Series-bandwidth law: (1/bw_link + 1/(bw_mem/2))^-1, and the paper's
    own numbers: 12.5 GB/s link + 14 GB/s mem -> 6.6 GB/s."""
    import dataclasses as dc
    from repro.core import latmodel
    from repro.core.config import V5E
    expect = 1.0 / (1.0 / V5E.ici_bw + 2.0 / V5E.hbm_bw)
    assert latmodel.buffered_peak_bw(V5E) == pytest.approx(expect)
    fpga = dc.replace(V5E, ici_bw=12.5e9, hbm_bw=2 * 14e9)
    assert latmodel.buffered_peak_bw(fpga) == pytest.approx(6.6e9, rel=0.01)


def test_stall_fraction_monotone_in_l_k():
    """More dispatch latency can only stall the pipeline more (paper Fig. 9:
    the MPI baseline's 30 us l_k is what produces the 75-80% stall)."""
    import dataclasses as dc
    from repro.core import latmodel
    from repro.core.config import BASELINE_CONFIG, V5E
    w = latmodel.SWEWorkload(
        e_total=48000, e_core=5600, e_send=270, e_recv=270, d_ext=0,
        l_pipe=100, n_max=4, flop_per_element=260.0, freq=256e6,
        msg_bytes=810)
    stalls = [latmodel.stall_fraction(
        w, BASELINE_CONFIG, dc.replace(V5E, host_dispatch=lk))
        for lk in (1e-6, 5e-6, 15e-6, 30e-6, 60e-6)]
    assert all(a <= b for a, b in zip(stalls, stalls[1:]))
    assert stalls[-1] > stalls[0]
    # throughput moves the other way
    thr = [latmodel.eq2_throughput(
        w, BASELINE_CONFIG, dc.replace(V5E, host_dispatch=lk))
        for lk in (1e-6, 30e-6, 60e-6)]
    assert thr[0] >= thr[1] >= thr[2]


# ----------------------------------------------------------------------
# Measured sweep -> selection -> SWE driver, end to end (8 devices)
# ----------------------------------------------------------------------

def test_sweep_select_and_auto_driver_e2e(tmp_path):
    out = run_multidevice(f"""
import jax
from repro.launch.mesh import make_mesh
from repro.tune import TuneDB, run_sweep, select_config
from repro.core.config import CommConfig

mesh = make_mesh((8,), ("x",))
db = run_sweep(mesh=mesh, collectives=("sendrecv",), sizes=(1024,),
               fast=True, max_configs=2, reps=1, inner=2)
assert len(db) >= 1, "sweep produced no entries"
path = db.save(r"{tmp_path / 'tunedb.json'}")
cfg = select_config("sendrecv", 1024, mesh=mesh, path=path)
assert isinstance(cfg, CommConfig)

# the SWE driver consumes the same TuneDB via comm_cfg="auto"
from repro.swe import driver
dmesh = make_mesh((8,), ("data",))
sim = driver.build_simulation(400, dmesh, "auto", tune_db_path=path)
assert isinstance(sim.comm_cfg, CommConfig)
s = driver.make_sim_runner(sim, 3)(sim.state, 0.0)
jax.block_until_ready(s)
print("TUNE E2E OK")
""")
    assert "TUNE E2E OK" in out
