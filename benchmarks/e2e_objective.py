"""End-to-end objective rows: does the bare-latency winner win end-to-end?

The paper's §5 result in benchmark form.  For each consumer-loop benchmark
(the row-parallel matmul+reduce layer, the halo-fold step) a small candidate
set is measured twice — bare collective latency (the microbenchmark the
tuner's default objective ranks by) and the consumer loop end-to-end — then
``select_config`` answers under both objectives and the rows record the
measured e2e time of each winner:

- ``e2e_<consumer>_lat_winner_us``  — e2e µs/iter of the bare-latency winner
- ``e2e_<consumer>_e2e_winner_us``  — e2e µs/iter of the e2e-objective winner
- ``e2e_gain_<consumer>``           — their ratio (>1: the microbench winner
  loses end-to-end, the §5 disagreement)

The row-parallel candidate set is chosen so the bare microbenchmark
*cannot* rank it: a native all-reduce executes the identical program under
buffered/streaming mode and fused/overlapped scheduling — only the consumer
loop (which chunks the matmul+reduce pipeline under streaming/overlapped)
separates the candidates.  The derived column carries the overlap-aware
Eq. 2 prediction (``latmodel.e2e_consumer_latency``, v5e constants): on
hardware with async collectives the model favors the overlapped config;
this host's synchronous CPU collectives pay the chunking without the
overlap win — both sides of that story are machine-tracked.
"""
from __future__ import annotations

from repro.core import latmodel
from repro.core.config import (CommConfig, CommMode, Scheduling, Transport,
                               V5E)

MSG_BYTES = 1 << 14

# Row-parallel candidates: identical bare all_reduce programs (native psum
# ignores mode/chunking), distinct consumer loops.
_ROWPAR_CANDS = (
    ("buffered_fused", CommConfig(mode=CommMode.BUFFERED,
                                  scheduling=Scheduling.FUSED)),
    ("streaming_fused_4k", CommConfig(chunk_bytes=1 << 12)),
    ("streaming_fused_16k", CommConfig(chunk_bytes=1 << 14)),
    ("streaming_overlap_4k", CommConfig(scheduling=Scheduling.OVERLAPPED,
                                        chunk_bytes=1 << 12)),
    ("streaming_overlap_16k", CommConfig(scheduling=Scheduling.OVERLAPPED,
                                         chunk_bytes=1 << 14)),
)

# Halo-fold candidates: here the bare multi_neighbor programs do differ.
_HALO_CANDS = (
    ("buffered_fused", CommConfig(mode=CommMode.BUFFERED,
                                  scheduling=Scheduling.FUSED,
                                  transport=Transport.ORDERED, window=1)),
    ("streaming_fused", CommConfig(chunk_bytes=1 << 12)),
    ("streaming_overlap", CommConfig(scheduling=Scheduling.OVERLAPPED,
                                     chunk_bytes=1 << 12)),
)

_CONSUMER_SETS = {"all_reduce": ("rowpar", _ROWPAR_CANDS),
                  "multi_neighbor": ("halo", _HALO_CANDS)}


def _predicted_e2e_us(collective: str, cfg: CommConfig) -> float:
    from repro.tune.sweep import consumer_flops
    compute_s = consumer_flops(collective, MSG_BYTES) / V5E.peak_flops
    return latmodel.e2e_consumer_latency(MSG_BYTES, cfg, compute_s, V5E) * 1e6


def _bench_collective(collective: str, tag: str, cands) -> list:
    import jax
    from repro.launch.mesh import make_mesh
    from repro.core.communicator import Communicator
    from repro.tune.db import TuneDB, TuneEntry, select_config, topology_key
    from repro.tune.space import config_to_dict
    from repro.tune import sweep as tune_sweep

    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))
    comm = Communicator.from_mesh(mesh, "x")
    topo = topology_key(mesh)
    db = TuneDB()
    named = {}
    for name, cfg in cands:
        op = tune_sweep._build_op(collective, comm, cfg)
        mkey = tune_sweep._mesh_key(mesh)
        lat_s = tune_sweep._time_program(
            op, mesh, MSG_BYTES, cfg, reps=3, inner=4,
            cache_key=("bench_e2e", topo, mkey, collective,
                       tuple(sorted(config_to_dict(cfg).items())),
                       MSG_BYTES))
        cop, shape = tune_sweep._build_consumer_op(collective, comm, cfg,
                                                   MSG_BYTES)
        e2e_s = tune_sweep._time_program(
            cop, mesh, MSG_BYTES, cfg, reps=3, inner=4, per_dev_shape=shape,
            cache_key=("bench_e2e_consumer", topo, mkey, collective,
                       tuple(sorted(config_to_dict(cfg).items())),
                       MSG_BYTES))
        named[tuple(sorted(config_to_dict(cfg).items()))] = name
        db.add(TuneEntry(topo=topo, collective=collective,
                         msg_bytes=MSG_BYTES, config=config_to_dict(cfg),
                         us_per_call=lat_s * 1e6,
                         gbps=MSG_BYTES / lat_s / 1e9,
                         e2e_us=e2e_s * 1e6))

    def lookup(objective):
        cfg = select_config(collective, MSG_BYTES, db=db, topo=topo,
                            objective=objective)
        key = tuple(sorted(config_to_dict(cfg).items()))
        entry = next(e for e in db.entries
                     if tuple(sorted(e.config.items())) == key)
        return named[key], cfg, entry

    lat_name, lat_cfg, lat_entry = lookup("latency")
    e2e_name, e2e_cfg, e2e_entry = lookup("e2e")
    gain = lat_entry.e2e_us / max(e2e_entry.e2e_us, 1e-9)
    pred_gain = (_predicted_e2e_us(collective, lat_cfg)
                 / max(_predicted_e2e_us(collective, e2e_cfg), 1e-9))
    return [
        (f"e2e_{tag}_lat_winner_us", lat_entry.e2e_us,
         f"{lat_name}_bare{lat_entry.us_per_call:.1f}us_"
         f"pred{_predicted_e2e_us(collective, lat_cfg):.1f}us"),
        (f"e2e_{tag}_e2e_winner_us", e2e_entry.e2e_us,
         f"{e2e_name}_bare{e2e_entry.us_per_call:.1f}us_"
         f"pred{_predicted_e2e_us(collective, e2e_cfg):.1f}us"),
        (f"e2e_gain_{tag}", gain,
         f"e2e_winner={e2e_name}_vs_lat_winner={lat_name}_"
         f"predicted{pred_gain:.2f}x"),
    ]


def run():
    import jax
    if jax.device_count() < 2:
        return [("e2e_objective", 0.0, "skipped_1device")]
    rows = []
    for collective, (tag, cands) in _CONSUMER_SETS.items():
        rows.extend(_bench_collective(collective, tag, cands))
    return rows
