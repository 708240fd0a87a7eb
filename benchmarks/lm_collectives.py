"""LM-collective overlap rows: predicted vs measured, overlap vs fused.

The paper's levers applied to the LM training path's two latency-sensitive
collectives:

- **TP reduce** — the per-layer row-parallel combine
  (``streaming.overlapped_matmul_allreduce``): fused = one psum after the
  full matmul; overlapped = chunked, double-buffered reduce pipelined
  against the matmul.
- **MoE all-to-all** — the dispatch/combine exchange
  (``streaming.chunked_all_to_all`` via ``collectives.all_to_all``):
  fused = one all-to-all; overlapped = independent wire chunks.

Each row reports the measured wall clock on this host's devices with the
chunk-aware Eq. 1 prediction in the derived column; the ``*_speedup`` rows
pair the measured fused/overlap ratio with the predicted one.  Like the
fig11 rows, host-CPU collectives execute synchronously — the prediction
says what a latency-hiding scheduler buys, the measurement what this
substrate pays; the rows make both machine-trackable across PRs.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import latmodel
from repro.core.config import (CommConfig, CommMode, OVERLAPPED_CONFIG,
                               Scheduling, V5E)

# Fused reference: buffered combine (single psum / single all-to-all).
TP_FUSED = CommConfig(mode=CommMode.BUFFERED, scheduling=Scheduling.FUSED)
TP_OVERLAP = OVERLAPPED_CONFIG

# Workload shapes (small enough for host-CPU wall clocks, large enough for
# multiple wire chunks under the overlapped config's 1 MiB segments when
# scaled by _CHUNK override below).
TOKENS, D_FF, D_MODEL = 512, 512, 256
MOE_CAP, MOE_D = 64, 256

# Chunk size used for the overlapped rows: small enough that the bench
# messages split into several chunks (the production default of 1 MiB would
# leave these CPU-sized payloads unchunked).
_CHUNK = 1 << 14


def _overlap_cfg() -> CommConfig:
    import dataclasses
    return dataclasses.replace(TP_OVERLAP, chunk_bytes=_CHUNK)


def _predicted_us(msg_bytes: int, cfg: CommConfig) -> float:
    return latmodel.pingping_latency(msg_bytes, cfg, V5E) * 1e6


def _predicted_layer_us(msg_bytes: int, cfg: CommConfig, flops: float) -> float:
    """Eq. 2-style layer prediction: compute + combine, with the overlapped
    schedule hiding the wire under the matmul (max instead of sum) while
    still paying one scheduled command per wire chunk."""
    t_mm = flops / V5E.peak_flops
    if cfg.scheduling == Scheduling.OVERLAPPED:
        t_wire = latmodel.l_c(msg_bytes, cfg, V5E)
        t_issue = latmodel.n_commands(msg_bytes, cfg) * latmodel.l_k(cfg, V5E)
        return (max(t_mm, t_wire) + t_issue) * 1e6
    return (t_mm + latmodel.pingping_latency(msg_bytes, cfg, V5E)) * 1e6


def _time(fn, args, reps: int = 3) -> float:
    """Seconds per call of the jit-compiled fn (compile+warmup excluded)."""
    import jax
    out = jax.block_until_ready(fn(*args))           # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def tp_reduce_rows():
    """Row-parallel TP combine: fused psum vs chunk-overlapped reduce."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.models import layers
    from repro.models.common import MeshContext, ModelConfig, Runtime

    n = jax.device_count()
    if n < 2:
        return [("lmcoll_tp_reduce", 0.0, "skipped_1device")]
    tp = min(4, n)
    mesh = make_mesh((tp,), ("model",))
    cfg_model = ModelConfig(name="bench", family="dense", n_layers=1,
                            d_model=D_MODEL, n_heads=4, n_kv_heads=4,
                            d_ff=D_FF, vocab_size=1024)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(TOKENS, D_FF), jnp.float32)
    w = jnp.asarray(rng.randn(D_FF, D_MODEL), jnp.float32)
    msg_bytes = TOKENS * D_MODEL * 4          # the reduced partial sum

    flops = 2.0 * TOKENS * D_FF * D_MODEL     # per-device matmul FLOPs
    rows = []
    measured = {}
    for name, cc in (("fused", TP_FUSED), ("overlap", _overlap_cfg())):
        rt = Runtime(cfg=cfg_model,
                     mesh=MeshContext(data_axes=(), model_size=tp,
                                      data_sizes=()),
                     comm=cc)

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P(None, "model"), P("model", None)),
                 out_specs=P(), check_vma=False)
        def f(xs, ws, rt=rt):
            return layers.row_parallel(xs, ws, rt)

        sec = _time(jax.jit(f), (x, w))
        measured[name] = sec
        rows.append((f"lmcoll_tp_reduce_{name}_tp{tp}", sec * 1e6,
                     f"pred{_predicted_layer_us(msg_bytes, cc, flops):.1f}us"))
    pred = (_predicted_layer_us(msg_bytes, TP_FUSED, flops)
            / _predicted_layer_us(msg_bytes, _overlap_cfg(), flops))
    rows.append((f"lmcoll_tp_reduce_speedup_tp{tp}",
                 measured["fused"] / measured["overlap"],
                 f"predicted{pred:.2f}x"))
    return rows


def moe_a2a_rows():
    """MoE dispatch-shaped all-to-all: fused vs chunk-overlapped."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import collectives
    from repro.core.communicator import Communicator

    n = jax.device_count()
    if n < 2:
        return [("lmcoll_moe_a2a", 0.0, "skipped_1device")]
    dp = min(4, n)
    mesh = make_mesh((dp,), ("data",))
    comm = Communicator.from_mesh(mesh, "data")
    rng = np.random.RandomState(1)
    # (dp, cap, D) bucketed dispatch payload per device
    x = jnp.asarray(rng.randn(dp * dp, MOE_CAP, MOE_D), jnp.float32)
    msg_bytes = dp * MOE_CAP * MOE_D * 4

    rows = []
    measured = {}
    for name, cc in (("fused", TP_FUSED), ("overlap", _overlap_cfg())):
        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"), check_vma=False)
        def f(v, cc=cc):
            return collectives.all_to_all(v, comm, cc, split_axis=0,
                                          concat_axis=0)

        sec = _time(jax.jit(f), (x,))
        measured[name] = sec
        rows.append((f"lmcoll_moe_a2a_{name}_dp{dp}", sec * 1e6,
                     f"pred{_predicted_us(msg_bytes, cc):.1f}us"))
    pred = (_predicted_us(msg_bytes, TP_FUSED)
            / _predicted_us(msg_bytes, _overlap_cfg()))
    rows.append((f"lmcoll_moe_a2a_speedup_dp{dp}",
                 measured["fused"] / measured["overlap"],
                 f"predicted{pred:.2f}x"))
    return rows


def run():
    return tp_reduce_rows() + moe_a2a_rows()
