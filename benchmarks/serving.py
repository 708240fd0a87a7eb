"""Serving rows: per-phase auto-config vs a one-config-fits-both serve path.

The tentpole question in benchmark form: serving's two phases consume the
same TP all-reduce with opposite cost structures — decode's tiny
latency-bound per-token combine vs prefill's throughput-bound bulk reduce —
so the config that wins prefill is not necessarily the one decode should
run.  A candidate set is measured under BOTH sweep consumer loops
(``decode_step`` at the decode message size, ``prefill`` at the prefill
message size), the measurements land in one consumer-tagged TuneDB, and
``select_config(consumer=...)`` answers per phase:

- ``srv_decode_auto_us_tok``       — decode-loop µs/iter of decode's own
  (``consumer="decode_step"``) winner;
- ``srv_decode_prefillcfg_us_tok`` — decode-loop µs/iter of the config the
  *prefill* consumer selected (one-config serving's decode cost);
- ``srv_phase_win``                — their ratio (>= 1 by construction:
  decode's winner is the argmin of the decode-loop measurements; 1.0 means
  both phases honestly agree on this host);
- ``srv_tok_s_rank_48``            — tokens/s/rank of the real serving
  decode step (``build_serve_fn(comm="auto")``) on 48 emulated ranks,
  resolving per-phase configs from the DB this process measured;
- ``srv_distinct_48``              — 1.0 when the 48-rank serve path
  resolved DIFFERENT prefill/decode configs from that shared DB.

The 48-rank leg is a subprocess (``--child``) so the emulated device count
is real, not inherited.  New rows ride this PR report-only until a second
committed baseline lands.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

# Decode moves one (batch, d_model) f32 partial per layer; prefill the whole
# prompt's — the message-size axis the phases diverge along.
DEC_MSG = 4 << 10
PRE_MSG = 1 << 20

CHILD_DEVICES = 48
CHILD_STEPS = 6


def _cands():
    from repro.core.config import CommConfig, CommMode, Scheduling
    # One monolithic candidate, one jumbo-chunk streamer, and two overlapped
    # pipelines whose chunk counts differ by phase: at DEC_MSG the 512-byte
    # pipeline pays 8 per-chunk combines for nothing, at PRE_MSG it is the
    # paper's segmented overlap.  The bare all_reduce microbench cannot rank
    # any of them (identical native psum) — only the consumer loops can.
    return (
        ("buffered_fused", CommConfig(mode=CommMode.BUFFERED,
                                      scheduling=Scheduling.FUSED)),
        ("streaming_fused_64k", CommConfig(chunk_bytes=1 << 16)),
        ("streaming_overlap_64k", CommConfig(scheduling=Scheduling.OVERLAPPED,
                                             chunk_bytes=1 << 16)),
        ("streaming_overlap_512", CommConfig(scheduling=Scheduling.OVERLAPPED,
                                             chunk_bytes=512)),
    )


def _measure_db():
    """Measure every candidate under both phase consumers -> (db, named,
    per-phase {config key: e2e µs} tables)."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.core.communicator import Communicator
    from repro.tune.db import TuneDB, TuneEntry, topology_key
    from repro.tune.space import config_to_dict
    from repro.tune import sweep as tune_sweep

    n = jax.device_count()
    mesh = make_mesh((n,), ("x",))
    comm = Communicator.from_mesh(mesh, "x")
    topo = topology_key(mesh)
    mkey = tune_sweep._mesh_key(mesh)
    db = TuneDB()
    named = {}
    e2e = {"decode_step": {}, "prefill": {}}
    for name, cfg in _cands():
        ckey = tuple(sorted(config_to_dict(cfg).items()))
        named[ckey] = name
        for consumer, msg in (("decode_step", DEC_MSG), ("prefill", PRE_MSG)):
            op = tune_sweep._build_op("all_reduce", comm, cfg)
            lat_s = tune_sweep._time_program(
                op, mesh, msg, cfg, reps=3, inner=4,
                cache_key=("bench_srv", topo, mkey, "all_reduce", ckey, msg))
            cop, shape = tune_sweep._build_consumer_op(
                "all_reduce", comm, cfg, msg, consumer=consumer)
            e2e_s = tune_sweep._time_program(
                cop, mesh, msg, cfg, reps=3, inner=4, per_dev_shape=shape,
                cache_key=("bench_srv_consumer", topo, mkey, "all_reduce",
                           consumer, ckey, msg))
            e2e[consumer][ckey] = e2e_s * 1e6
            db.add(TuneEntry(topo=topo, collective="all_reduce",
                             msg_bytes=msg, config=config_to_dict(cfg),
                             us_per_call=lat_s * 1e6,
                             gbps=msg / lat_s / 1e9,
                             e2e_us=e2e_s * 1e6, consumer=consumer))
    return db, named, e2e


def _select(db, consumer: str, msg: int):
    from repro.tune.db import select_config, topology_key
    from repro.tune.space import config_to_dict
    cfg = select_config("all_reduce", msg, db=db, topo=topology_key(),
                        objective="e2e", consumer=consumer)
    return cfg, tuple(sorted(config_to_dict(cfg).items()))


def _child_rows(db) -> list:
    """Resolve per-phase configs and decode for real on 48 emulated ranks."""
    with tempfile.TemporaryDirectory(prefix="repro-srv-bench-") as td:
        db_path = os.path.join(td, "tunedb.json")
        db.save(db_path)
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{CHILD_DEVICES}")
        repo = Path(__file__).resolve().parents[1]
        env["PYTHONPATH"] = str(repo / "src")
        proc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--child",
             db_path], capture_output=True, text=True, timeout=560, env=env,
            cwd=str(repo))
    if proc.returncode != 0:
        raise RuntimeError(f"48-rank serve child failed (rc="
                           f"{proc.returncode}): {proc.stderr[-500:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return [
        ("srv_tok_s_rank_48", out["tok_s_rank"],
         f"decode{out['decode_cfg']}_steps{CHILD_STEPS}"
         f"_ranks{CHILD_DEVICES}"),
        ("srv_distinct_48", 1.0 if out["distinct"] else 0.0,
         f"prefill{out['prefill_cfg']}_decode{out['decode_cfg']}"),
    ]


def _child(db_path: str) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_smoke_config
    from repro.launch import input_specs as isp, setup
    from repro.launch.mesh import make_mesh
    from repro.train import serve as serve_mod

    n = jax.device_count()
    mesh = make_mesh((n // 4, 4), ("data", "model"))
    cfg = dataclasses.replace(get_smoke_config("qwen3-8b"),
                              dtype=jnp.float32)
    B, prompt, gen = n // 4, 8, CHILD_STEPS
    shape_p = isp.ShapeSpec("serve", prompt, B, "prefill")
    shape_d = isp.ShapeSpec("serve", prompt + gen, B, "decode")
    sess = setup.build_session(cfg, mesh, serve_mod.resolve_serve_comm(
        cfg, mesh, "auto", shape_d, tune_db_path=db_path), concrete=True)
    rt_p, prefill_fn, _ = serve_mod.build_serve_fn(
        cfg, mesh, "auto", shape_p, tune_db_path=db_path,
        cache_capacity=serve_mod.cache_len(cfg, shape_d))
    rt_d, decode_fn, _ = serve_mod.build_serve_fn(
        cfg, mesh, "auto", shape_d, tune_db_path=db_path)

    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (B, prompt)).astype(np.int32))
    state = jax.block_until_ready(prefill_fn(sess.params, {"tokens": toks}))
    tok = jnp.argmax(state.last_logits, axis=-1).astype(jnp.int32)
    state = jax.block_until_ready(decode_fn(sess.params, tok, state))  # warm
    t0 = time.perf_counter()
    for _ in range(CHILD_STEPS):
        tok = jnp.argmax(state.last_logits, axis=-1).astype(jnp.int32)
        state = decode_fn(sess.params, tok, state)
    jax.block_until_ready(state.last_logits)
    wall = time.perf_counter() - t0

    def tag(c):
        return f"[{c.mode.value}/{c.scheduling.value}/chunk{c.chunk_bytes}]"

    print(json.dumps({
        "prefill_cfg": tag(rt_p.comm), "decode_cfg": tag(rt_d.comm),
        "distinct": rt_p.comm != rt_d.comm,
        "tok_s_rank": B * CHILD_STEPS / wall / n}))


def run():
    import jax
    from repro.launch.mesh import exit_unless_host_cpu
    exit_unless_host_cpu("benchmarks.serving")   # starts a 48-device child
    if jax.device_count() < 4:
        return [("srv", 0.0, "skipped_lt4devices")]
    db, named, e2e = _measure_db()
    _, dec_key = _select(db, "decode_step", DEC_MSG)
    _, pre_key = _select(db, "prefill", PRE_MSG)
    dec_auto = e2e["decode_step"][dec_key]
    dec_under_pre = e2e["decode_step"][pre_key]
    rows = [
        ("srv_decode_auto_us_tok", dec_auto, f"winner_{named[dec_key]}"),
        ("srv_decode_prefillcfg_us_tok", dec_under_pre,
         f"prefill_winner_{named[pre_key]}"),
        ("srv_phase_win", dec_under_pre / max(dec_auto, 1e-9),
         f"decode={named[dec_key]}_vs_prefill={named[pre_key]}"),
    ]
    rows.extend(_child_rows(db))
    return rows


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        _child(sys.argv[2])
    else:
        for r in run():
            print(r)
